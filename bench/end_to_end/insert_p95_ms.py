"""95th percentile, over every insert of the window, of its wave's wall
time: an insert is acknowledged when its ``insert_many`` wave returns."""
import numpy as np


def read(rec, trace):
    lat = [w["wall_s"] for w in rec["insert_waves"] for _ in range(w["n"])]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
