"""Device time of the entry selection (``navis.entrance``: ``Engine._entries``,
the entrance-graph search) in the traced ``_search_many``, per query."""
from bench import scopes


def read(rec, trace):
    return scopes.ms_per(rec, trace, __file__, "_search_many",
                         "navis.entrance")
