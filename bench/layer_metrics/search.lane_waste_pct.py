"""Share of the vmapped traversal's lockstep lanes that idle for the
slowest query of their wave: per wave 1 - mean/max of the per-query
serial rounds ``search_many`` returns, weighted by queries."""


def read(rec, trace):
    num = den = 0.0
    for w in rec["search_waves"]:
        r = w["rounds"]
        if r and max(r) > 0:
            num += len(r) * (1.0 - sum(r) / len(r) / max(r))
            den += len(r)
    return 100.0 * num / den if den else None
