"""Load generator (`bench/loadgen.py`): 95th percentile of how late the
generator sent an open-loop request after it was due, in ms."""
import numpy as np


def read(rec):
    if rec["loop"] != "open" or not rec["requests"]:
        return None
    lag = [r["send"] - r["due"] for r in rec["requests"]]
    return float(np.percentile(lag, 95)) * 1e3
