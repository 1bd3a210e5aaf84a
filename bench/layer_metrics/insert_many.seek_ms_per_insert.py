"""Device time of the position seek (``navis.seek``: the vmapped ``seek_one``
of ``Engine._insert_many``, its entrance, traversal, rerank and neighbour
selection) in the traced ``_insert_many``, per insert."""
from bench import scopes


def read(rec, trace):
    return scopes.ms_per(rec, trace, __file__, "_insert_many",
                         "navis.seek")
