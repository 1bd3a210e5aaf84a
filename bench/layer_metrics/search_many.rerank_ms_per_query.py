"""Device time of the CASR rerank (``navis.rerank``: ``casr.casr_rerank``)
in the traced ``_search_many``, per query."""
from bench import scopes


def read(rec, trace):
    return scopes.ms_per(rec, trace, __file__, "_search_many",
                         "navis.rerank")
