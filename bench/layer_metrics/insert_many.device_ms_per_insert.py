"""Device time of the jitted ``_insert_many`` program in the traced window,
per insert there."""


def read(rec, trace):
    s = (trace or {}).get("programs", {}).get("_insert_many")
    n = sum(w["n"] for w in rec["insert_waves"])
    return s * 1e3 / n if s and n else None
