"""Scoring (`kernels/sketch_join`, `kernels/rank_transform`, qn): device
time of the stage-2 kernels per completed query in the traced window, in
ms, found as in `cand_dev_ms`."""
from trace_reduce import op_name

KERNELS = ("sketch_join_moments", "rank_moments", "qn_correlation")


def read(rec):
    t = rec.get("trace")
    if not t or not t["queries"]:
        return None
    sec = sum(v for label, v in t["op_seconds"].items()
              if any(k in op_name(label) for k in KERNELS))
    return sec / t["queries"] * 1e3 if sec > 0 else None
