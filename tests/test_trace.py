"""Program spans (`repro.engine.trace`) and per-ticket stamps.

  * tracing off: `span` hands back the one shared no-op context, and a
    served query adds nothing to the totals;
  * tracing on: one query through `AsyncScheduler` on an inverted ``safe``
    server records a span at every layer it crosses, and the stage span
    counts equal the stage counts;
  * every ticket's stamps are ordered submit ≤ admit ≤ served ≤ done;
  * a `jax.profiler` trace holds the spans, by their bare names, on a
    ``/host:`` plane.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.engine import index as IX
from repro.engine import plans as PL
from repro.engine import serve as SV
from repro.engine import trace as TR
from repro.engine.scheduler import AsyncScheduler

from test_two_stage import _corpus, _queries

N_SKETCH = 32


@pytest.fixture
def fresh(monkeypatch):
    """Tracing off, with totals of this test's own."""
    monkeypatch.setattr(TR, "_totals", {})
    monkeypatch.setattr(TR, "_on", False)


@pytest.fixture(scope="module")
def server():
    rng = np.random.default_rng(7)
    idx = IX.build_index(_corpus(rng, n_tables=12), n=N_SKETCH, pad_to=32)
    srv = SV.Server(jax.make_mesh((1,), ("shard",)), idx,
                    PL.ShapePolicy(k_max=5, prune_base=4,
                                   candidates="inverted"),
                    request=PL.Request(k=5, prune="safe"), buckets=(1, 4),
                    cache=SV.CompileCache())
    srv.warmup(modes=("safe",))
    return srv


def _columns(seed, nq=1):
    qs = _queries(np.random.default_rng(seed), nq=nq)
    return [k for k, _ in qs], [v for _, v in qs]


def _serve_one(srv, seed):
    """Sketch one query and serve it through a one-worker scheduler."""
    keys, vals = _columns(seed)
    sks = SV.build_query_sketches(keys, vals, n=N_SKETCH)
    with AsyncScheduler(srv, workers=1) as sched:
        ticket = sched.submit(sks)
        ticket.result(timeout=120)
    return ticket


def test_off_is_a_shared_noop_and_records_nothing(fresh, server):
    assert not TR._on
    a, b = TR.span("serve.fused"), TR.span("front.sketch", seq=1)
    assert a is b
    with a:
        pass
    _serve_one(server, 1)
    assert TR.totals() == {}


def test_on_records_every_layer_of_a_served_query(fresh, server):
    ex = server._entries[server._order[0]].exec
    fused0 = ex.stage_stats().get("fused", 0)
    TR.enable(True)
    try:
        _serve_one(server, 2)
    finally:
        TR.enable(False)
    got = TR.totals()
    assert set(got) <= set(TR.SPANS)
    for name in ("front.sketch", "front.layout", "front.build",
                 "sched.admit", "sched.batch", "serve.query",
                 "serve.fused", "serve.wait"):
        assert got[name]["count"] >= 1, name
        assert got[name]["wall_ns"] > 0 and got[name]["cpu_ns"] >= 0, name
    assert got["sched.batch"]["count"] == got["serve.query"]["count"] == 1
    assert got["serve.fused"]["count"] == \
        ex.stage_stats()["fused"] - fused0
    # nested spans are inside their parent's wall time
    assert got["serve.query"]["wall_ns"] <= got["sched.batch"]["wall_ns"]


def test_nested_spans_inherit_metadata(fresh, monkeypatch):
    seen = []

    class Ann:
        def __init__(self, name, **meta):
            seen.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(TR.jax.profiler, "TraceAnnotation", Ann)
    TR.enable(True)
    with TR.span("sched.batch", seq=4, width=2):
        with TR.span("serve.wait"):
            pass
    with TR.span("serve.wait"):
        pass
    assert seen == [("sched.batch", dict(seq=4, width=2)),
                    ("serve.wait", dict(seq=4, width=2)),
                    ("serve.wait", {})]


@pytest.mark.parametrize("on", [False, True])
def test_ticket_stamps_are_ordered(fresh, server, on):
    TR.enable(on)
    try:
        keys, vals = _columns(3, nq=3)
        sks = jax.tree.map(np.asarray, SV.build_query_sketches(
            keys, vals, n=N_SKETCH))
        with AsyncScheduler(server, workers=2) as sched:
            tickets = [sched.submit(jax.tree.map(lambda a: a[i:i + 1], sks))
                       for i in range(3)]
            for t in tickets:
                t.result(timeout=120)
    finally:
        TR.enable(False)
    for t in tickets:
        assert t.t_submit <= t.t_admit <= t.t_served <= t.t_done
        assert t.batch is not None
    # tickets that shared a dispatch share its stamps
    for a in tickets:
        for b in tickets:
            if a.batch == b.batch:
                assert (a.t_admit, a.t_served) == (b.t_admit, b.t_served)


def test_profiler_trace_holds_the_spans_on_a_host_plane(fresh, server,
                                                        tmp_path):
    _serve_one(server, 4)                  # warm: no compiles in the trace
    TR.enable(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve_one(server, 5)
    finally:
        jax.profiler.stop_trace()
        TR.enable(False)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    for name in ("front.sketch", "sched.admit", "sched.batch",
                 "serve.query", "serve.fused", "serve.wait"):
        assert name in names, name
