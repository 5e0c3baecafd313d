"""Candidates (`engine/candidates`, `kernels/postings`,
`kernels/containment`): device time of the stage-1 kernels per completed
query in the traced window, in ms. On a TPU v5e a Pallas kernel's trace
event is named after the jitted function that holds it
(``%postings_merge.1 = ...``, ``%vmap_jit_sketch_join_moments__.2 = ...``),
so a kernel is found by its name inside the operation's own name."""
from trace_reduce import op_name

KERNELS = ("postings_merge", "postings_select", "containment_hits")


def read(rec):
    t = rec.get("trace")
    if not t or not t["queries"]:
        return None
    sec = sum(v for label, v in t["op_seconds"].items()
              if any(k in op_name(label) for k in KERNELS))
    return sec / t["queries"] * 1e3 if sec > 0 else None
