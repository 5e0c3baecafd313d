"""Front end (`serve.build_query_sketches`): mean host-clock time to sketch
one request's query columns, in ms (the benchmark's own span)."""
import numpy as np


def read(rec):
    got = [r["sketch_s"] for r in rec["requests"] if r["sketch_s"] > 0]
    return float(np.mean(got)) * 1e3 if got else None
