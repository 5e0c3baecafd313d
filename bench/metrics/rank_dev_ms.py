"""Scoring, rank branch (`kernels/rank_transform.rank_moments`): device
time of the fused rank-and-moments kernel per completed query in the
traced window, in ms, found by name as in `cand_dev_ms`. The kernel runs
inside the estimator's ``lax.switch`` branch of the served plan and keeps
its own name there (``%rank_moments.N = ... custom-call``)."""
from trace_reduce import op_name


def read(rec):
    t = rec.get("trace")
    if not t or not t["queries"]:
        return None
    sec = sum(v for label, v in t["op_seconds"].items()
              if "rank_moments" in op_name(label))
    return sec / t["queries"] * 1e3 if sec > 0 else None
