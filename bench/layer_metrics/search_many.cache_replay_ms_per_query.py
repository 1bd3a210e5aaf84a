"""Device time of the ordered replay of the wave's page-access traces into
the shared cache (``navis.cache_replay``: ``cache.apply_traces``) in the
traced ``_search_many``, per query."""
from bench import scopes


def read(rec, trace):
    return scopes.ms_per(rec, trace, __file__, "_search_many",
                         "navis.cache_replay")
