"""Scoring, sketch join (`kernels/sketch_join.sketch_join_moments`):
device time of the join kernel per completed query in the traced window,
in ms, found by name as in `cand_dev_ms` (the served plans vmap it over
the query batch: ``%vmap_jit_sketch_join_moments__.N``). Under a rank
estimator it also writes the aligned and hit planes that `rank_moments`
reads."""
from trace_reduce import op_name


def read(rec):
    t = rec.get("trace")
    if not t or not t["queries"]:
        return None
    sec = sum(v for label, v in t["op_seconds"].items()
              if "sketch_join_moments" in op_name(label))
    return sec / t["queries"] * 1e3 if sec > 0 else None
