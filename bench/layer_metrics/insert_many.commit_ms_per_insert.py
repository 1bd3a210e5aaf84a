"""Device time of the serial commit scan (``navis.commit``: the ``lax.scan`` of
``Engine._insert_many``, its linking and entrance update) in the traced
``_insert_many``, per insert."""
from bench import scopes


def read(rec, trace):
    return scopes.ms_per(rec, trace, __file__, "_insert_many",
                         "navis.commit")
