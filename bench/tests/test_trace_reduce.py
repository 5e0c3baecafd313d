"""`bench/trace_reduce.py`: union of busy intervals, device time per
operation, idle time while requests are in flight, and the naming of idle
gaps by host spans — on fabricated events with known answers, on a small
trace recorded on a TPU v5e (`trace_small.json`), and `load` on a trace the
CPU backend writes here."""
import json
from pathlib import Path

import numpy as np
import pytest

import trace_reduce as TR

MS = 1e6  # ns

EVENTS = dict(
    device_ops=[
        ["/device:TPU:0", "%postings_select.1 = s32[1,65536] custom-call(...)",
         0 * MS, 10 * MS],
        ["/device:TPU:0", "%vmap_jit_sketch_join_moments__.2 = (f32[64,8]) custom-call(...)",
         5 * MS, 10 * MS],            # overlaps a: union 0..15
        ["/device:TPU:0", "%fusion.3 = u32[65536] fusion(%keys.1)", 30 * MS, 10 * MS],
        ["/device:TPU:0", "%fusion.4 = f32[1024] fusion(%x)", 95 * MS, 10 * MS],  # clipped at 100
    ],
    host_spans=[["window_start", 0.0, 1000.0],
                ["sketch_build", 15 * MS, 14 * MS],
                ["gen_sleep", 40 * MS, 50 * MS],
                ["submit", 90 * MS, 1 * MS]])


def test_fabricated_events():
    got = TR.reduce(EVENTS, (0.0, 100 * MS), inflight=[(0, 20 * MS),
                                                       (10 * MS, 35 * MS)])
    assert got["busy_s"] == pytest.approx(0.030)
    assert got["window_s"] == pytest.approx(0.100)
    assert got["inflight_s"] == pytest.approx(0.035)
    # busy ∩ in flight: 0..15 and 30..35
    assert got["busy_inflight_s"] == pytest.approx(0.020)
    short = dict(got["device_ops"])
    assert short["postings_select"] == pytest.approx(0.010)
    assert short["vmap_jit_sketch_join_moments__"] == pytest.approx(0.010)
    assert short["fusion"] == pytest.approx(0.015)
    assert got["idle_gaps"] == [["gen_sleep", pytest.approx(0.055)],
                                ["sketch_build", pytest.approx(0.015)]]


def test_union_and_intersect():
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == \
        [(0, 3), (5, 9)]
    assert TR.intersect([(0, 3), (5, 9)], [(2, 6), (8, 20)]) == \
        [(2, 3), (5, 6), (8, 9)]


def _bins(intervals, t0, t1, step):
    """Brute-force coverage on a grid (the check of the interval code)."""
    grid = np.arange(t0, t1, step)
    on = np.zeros(grid.size, bool)
    for s, e in intervals:
        on |= (grid >= s) & (grid < e)
    return on


def test_recorded_v5e_trace():
    ev = json.loads((Path(__file__).parent / "trace_small.json").read_text())
    t0 = TR.mark_ns(ev)
    t1 = max(s + d for _, _, s, d in ev["device_ops"])
    fly = [tuple(x) for x in ev["inflight"]]
    got = TR.reduce(ev, (t0, t1), fly)
    step = 1000.0   # 1 us grid
    busy = _bins([(s, s + d) for _, _, s, d in ev["device_ops"]], t0, t1,
                 step)
    inf = _bins(fly, t0, t1, step)
    assert got["busy_s"] == pytest.approx(busy.sum() * step * 1e-9,
                                          rel=2e-3)
    assert got["busy_inflight_s"] == pytest.approx(
        (busy & inf).sum() * step * 1e-9, rel=2e-3)
    per = {}
    for _, label, s, d in ev["device_ops"]:
        k = TR.short_label(label)
        per[k] = per.get(k, 0.0) + (min(s + d, t1) - max(s, t0)) * 1e-9
    for k, v in got["device_ops"]:
        assert v == pytest.approx(per[k])
    assert {k for k, _ in got["idle_gaps"]} <= {
        "gen_sleep", "sketch_build", "submit", "await_result", "other"}


def test_load_reads_host_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.MARK):
        pass
    with jax.profiler.TraceAnnotation("submit"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = TR.load(TR.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in ev["host_spans"]]
    assert TR.MARK in names and "submit" in names
    assert ev["device_ops"] == []      # the CPU has no device plane
