"""Device time of the symmetric-PQ lookups (``navis.sym``:
``pq.sym_distance``, r x pq_m table reads a call) in the traced
``_insert_many``, per insert: the sum over every scope path that ends in
``navis.sym`` (the commit's re-validation, reciprocal wiring and entrance
update, and the seek's neighbour selection).  An op has one path, so the
paths are disjoint and their sum is their union."""
from bench import scopes


def read(rec, trace):
    split = (scopes.of_run(rec, trace, __file__) or {}).get(
        "_insert_many", {})
    s = sum(v for path, v in split.items()
            if path.rsplit("/", 1)[-1] == "navis.sym")
    n = sum(w["n"] for w in rec["insert_waves"])
    return s * 1e3 / n if s and n else None
