"""Inserts acknowledged over the whole window, per second of the window."""


def read(rec, trace):
    n = sum(w["n"] - w["dropped"] for w in rec["insert_waves"])
    return n / rec["window_s"] if n else None
