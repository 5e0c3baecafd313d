"""The served Spearman path against the benchmark's plain reference.

A tiny SBN lake goes through the benchmark's own set-up (`bench/run.py`:
corpus, `LiveIndex` ingest, `Server`, `AsyncScheduler`, warm-up) with the
Pallas kernels in interpret mode, under the traffic of each `sbn-n1024`
cell: exact ``safe`` (the fused inverted plan) in an open loop and the full
scan (``prune="off"``) in a closed loop. Every answer is checked against
`bench/reference.py` by `bench/check.py` with the cell's own limits, and
the window must compile nothing. One case runs at the configuration's own
sketch size, n = 1024, on a lake small enough for the interpreter.
"""
import sys
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run  # noqa: E402  (bench/run.py)

from repro.engine import plans as PL  # noqa: E402
from repro.kernels.ops import KernelConfig  # noqa: E402

SEED = 2 ** 31 + 1015


def _cell(name, n, rows_min, rows_max):
    c = run.load_cell(name)
    c.config.update(tables=24, rows_min=rows_min, rows_max=rows_max, n=n,
                    append_batch=8, query_chunk=max(512, 2 * n))
    c.traffic.update(pool=6)
    if c.traffic["loop"] == "open":
        c.traffic.update(rate_qps=6.0)
    else:
        c.traffic.update(clients=3)
    return c


@pytest.mark.parametrize("name,n,rows_min,rows_max", [
    ("sbn-n1024.safe-spearman.open80", 64, 256, 1500),
    ("sbn-n1024.scan-spearman.closed8", 64, 256, 1500),
    # sketches full at n = 1024: every table has more rows than n
    ("sbn-n1024.safe-spearman.open80", 1024, 1100, 3000),
    ("sbn-n1024.scan-spearman.closed8", 1024, 1100, 3000),
])
def test_served_spearman_matches_reference(name, n, rows_min, rows_max):
    cell = _cell(name, n, rows_min, rows_max)
    assert cell.traffic["request"]["estimator"] == "spearman"
    # prune_base 4 puts survivor rungs under the tiny lake's width, so
    # ``safe`` dispatches the fused inverted plan and not the scan
    policy = PL.ShapePolicy(candidates="inverted", prune_base=4,
                            kernels=KernelConfig("interpret"))
    s = run.setup(cell, SEED, jax.devices()[0], policy)
    try:
        stages0 = s.srv.throughput()["stages"]
        rec = run.window(s, SEED, 1.0, trace=False)
        stages = s.srv.throughput()["stages"]
    finally:
        s.sched.close()
    assert rec["requests"] and all(r["done"] is not None
                                   for r in rec["requests"])
    correct, checks = run.judge(s, rec)
    assert correct, checks
    assert checks["m_gap"]["value"] == 0
    assert checks["order_errors"]["value"] == 0
    assert checks["lost"]["value"] == 0
    assert rec["compiles"] == dict(cache_misses=0, lowerings=0)
    served = "fused" if cell.traffic["request"]["prune"] == "safe" else "scan"
    count = lambda st: st.get(served, {}).get("count", 0)
    assert count(stages) > count(stages0)
