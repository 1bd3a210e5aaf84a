"""Full-width vectors the exact rerank (CASR) read per query of the
measured search waves, from the search counters' ``vectors_read`` (the
selective vector read).  None where the program keeps no such counter."""


def read(rec, trace):
    vecs = rec["counters"]["search"].get("vectors_read")
    n = sum(w["n"] for w in rec["search_waves"])
    return vecs / n if vecs is not None and n else None
