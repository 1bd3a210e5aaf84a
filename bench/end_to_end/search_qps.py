"""Queries completed over the whole window, per second of the window."""


def read(rec, trace):
    n = sum(w["n"] for w in rec["search_waves"])
    return n / rec["window_s"] if n else None
