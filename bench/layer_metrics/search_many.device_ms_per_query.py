"""Device time of the jitted ``_search_many`` program in the traced window,
per query searched there."""


def read(rec, trace):
    s = (trace or {}).get("programs", {}).get("_search_many")
    n = sum(w["n"] for w in rec["search_waves"])
    return s * 1e3 / n if s and n else None
