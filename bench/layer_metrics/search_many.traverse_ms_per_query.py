"""Device time of the beam traversal (``navis.traverse``:
``search.disk_traverse``, its fetch, score and merge hops) in the traced
``_search_many``, per query."""
from bench import scopes


def read(rec, trace):
    return scopes.ms_per(rec, trace, __file__, "_search_many",
                         "navis.traverse")
