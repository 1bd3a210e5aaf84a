"""The search program's share of the HBM roofline: the bytes its
traversal and rerank must move (``bench/costs.py``, from the widths and
the program's hop and vector-read counts) over peak HBM bandwidth, over
the ``_search_many`` program's device time."""
from bench.costs import search_bytes


def read(rec, trace):
    s = (trace or {}).get("programs", {}).get("_search_many")
    c = rec["counters"]["search"]
    reranked = ((c["useful_vec_bytes_read"] + c["wasted_vec_bytes_read"])
                // (rec["widths"]["dim"] * 4))
    need = search_bytes(rec["widths"], c["hops"], reranked)
    if not s or need <= 0:
        return None
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / s
