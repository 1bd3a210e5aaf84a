"""Bytes a search must move, from a configuration's widths and the
program's counts: the yardstick of ``search_many_roofline``."""
from __future__ import annotations


def search_bytes(widths: dict, hops: int, reranked: int) -> int:
    """Each hop expands ``beam_width`` vertices and gathers, for each, its
    ``r`` neighbour ids (4 B) and their ``pq_m``-byte PQ codes; each
    reranked candidate reads its ``dim`` float32 vector."""
    per_hop = widths["beam_width"] * widths["r"] * (4 + widths["pq_m"])
    return hops * per_hop + reranked * widths["dim"] * 4
