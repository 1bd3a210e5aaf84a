"""95th percentile, over every query of the traced part of the window, of
its wave's wall time: a wave's queries are issued together and answered
together.  With about five search waves traced it is their slowest."""
import numpy as np


def read(rec, trace):
    lat = [w["wall_s"] for w in rec["search_waves"] for _ in range(w["n"])]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
