#!/usr/bin/env python3
"""Run one benchmark cell of the join-correlation server on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process builds the cell's data lake from ``--seed`` and indexes it
(`LiveIndex.append` in batches, then `compact`), places it behind a
`Server` and an `AsyncScheduler`, warms every shape the cell's traffic
uses, then offers that traffic for ``--seconds`` and waits for every answer.
Every answer is then compared with the plain reference (`bench/check.py`).
The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window), ``device``, ``breakdown`` (``--trace 1``) and ``checks``, the
compared numbers with their limits, which also end stderr.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json``, its traffic in
``bench/traffic/<traffic>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``. Exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import check  # noqa: E402
import corpus  # noqa: E402
import loadgen  # noqa: E402
import trace_reduce  # noqa: E402

#: seconds past the window's end an answer may take before it is failed
GRACE_S = 60.0


class CellError(RuntimeError):
    """The benchmark's files do not describe the asked-for cell."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _in_cell(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _in_cell(m, name)])


def chip_peaks(kind: str, root: Path = HERE) -> dict:
    """The published peaks of a chip by its JAX ``device_kind``; a chip
    missing from ``bench/peaks.json`` is an error, never a default."""
    table = json.loads((root / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise CellError(f"no peaks for device kind {kind!r} in "
                        f"bench/peaks.json")
    return table[kind]


def read_metric(name: str, record: dict):
    """Run ``bench/metrics/<name>.py``'s ``read`` on the run's record."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


class _Lowerings:
    """Counts JAX lowerings of new programs (jit misses, eager operations
    at a new shape) through `jax.monitoring`."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, *args, **kwargs):
        if event == self.EVENT:
            self.count += 1


@dataclasses.dataclass
class Setup:
    """Everything set-up leaves for the window and the check."""
    cell: Cell
    lake: corpus.Lake
    queries: list
    srv: object
    sched: object
    request: object
    build: object
    names: list
    ingest_s: float
    warmup_s: float
    lowerings: _Lowerings


def setup(cell: Cell, seed: int, device, policy=None) -> Setup:
    """Generate and index the lake, place it behind a `Server` and an
    `AsyncScheduler`, and warm every shape the cell's traffic uses: the
    server's plans for the cell's prune mode and buckets, the query sketch
    at each number of row chunks the pool's queries have, and the
    scheduler path at each group width up to the largest bucket."""
    import jax
    from repro.data.pipeline import TableGroup
    from repro.engine import plans as PL
    from repro.engine import serve as SV
    from repro.engine.lifecycle import LiveIndex
    from repro.engine.scheduler import AsyncScheduler

    lowerings = _Lowerings()
    gen, tr = cell.config, cell.traffic
    n, chunk = int(gen["n"]), int(gen["query_chunk"])
    rng = np.random.default_rng(seed)
    t = time.monotonic()
    live = LiveIndex(n=n, delta_cap=int(gen["tables"]))
    lake = corpus.generate(gen, int(tr["pool"]), rng, on_batch=lambda b: (
        live.append([TableGroup(keys=g.keys, values=g.values, name=g.name,
                                column_names=g.column_names) for g in b])))
    live.compact()
    ingest_s = time.monotonic() - t

    t = time.monotonic()
    mesh = jax.make_mesh((1,), ("shard",), devices=[device])
    req = PL.Request(**tr["request"])
    srv = SV.Server(mesh, live, policy or PL.ShapePolicy(candidates="auto"),
                    request=req, buckets=tuple(tr["buckets"]))
    srv.warmup(modes=(req.prune,), include_ladder=False)
    queries = lake.pool

    def build(i: int):
        keys, vals = queries[i]
        return jax.block_until_ready(SV.build_query_sketches(
            [keys], [vals], n=n, chunk=chunk))

    # the query sketch at every number of row chunks a query can have
    longest = max(k.size for k, _ in queries)
    for c in range(1, -(-longest // chunk) + 1):
        jax.block_until_ready(SV.build_query_sketches(
            [np.arange(c * chunk, dtype=np.uint32)],
            [np.zeros(c * chunk, np.float32)], n=n, chunk=chunk))
    sched = AsyncScheduler(srv, workers=int(tr["workers"]))
    # one group of each width the scheduler can form: a lone ticket is
    # served from its device sketch, a merged group from host arrays
    for width in range(1, max(srv.buckets) + 1):
        sks = [build(i % len(queries)) for i in range(width)]
        merged = sks[0] if width == 1 else jax.tree.map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *sks)
        sched.submit(merged, request=req).result(timeout=600)
    warmup_s = time.monotonic() - t
    return Setup(cell=cell, lake=lake, queries=queries, srv=srv,
                 sched=sched, request=req, build=build,
                 names=list(srv.names), ingest_s=ingest_s,
                 warmup_s=warmup_s, lowerings=lowerings)


def _counters(s: Setup) -> dict:
    st, tp = s.sched.stats(), s.srv.throughput()
    return dict(completed=st["completed"], batches=st["batches"],
                queries=tp["queries"],
                device_dispatches=tp["device_dispatches"],
                cache_misses=s.srv.cache.misses,
                lowerings=s.lowerings.count)


def draw_requests(traffic: dict, seed: int, seconds: float, pool: int,
                  rate: Optional[float] = None):
    """The window's requests from the seed: open-loop due times (None for a
    closed loop) and the pool entry each request asks about."""
    lrng = np.random.default_rng([seed, 2])
    if traffic["loop"] == "open":
        due = loadgen.open_schedule(float(rate or traffic["rate_qps"]),
                                    seconds, lrng)
        return due, lrng.integers(pool, size=due.size)
    return None, lrng.integers(pool, size=1 << 16)


def window(s: Setup, seed: int, seconds: float, trace: bool,
           rate: Optional[float] = None) -> dict:
    """Offer the cell's traffic for ``seconds`` and wait for every answer.
    Returns the run's record (times in seconds from the window's start)."""
    import jax
    tr = s.cell.traffic
    submit = lambda sk: s.sched.submit(sk, request=s.request)
    due, picks = draw_requests(tr, seed, seconds, len(s.queries), rate)
    before = _counters(s)
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace \
        else None
    if trace:
        # the reduction reads device operations and the benchmark's own
        # host spans (level 1): the Python tracer and the runtime's host
        # events, one set per eager operation of the sketch build, would
        # only make the trace slower to stop
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    m0 = time.monotonic()
    with jax.profiler.TraceAnnotation(trace_reduce.MARK):
        pass
    mark = (m0 + time.monotonic()) / 2
    t0 = time.monotonic()
    if tr["loop"] == "open":
        reqs = loadgen.run_open(due, picks, s.build, submit, t0, seconds,
                                GRACE_S)
    else:
        reqs = loadgen.run_closed(int(tr["clients"]),
                                  lambda i: int(picks[i % picks.size]),
                                  s.build, submit, t0, seconds, GRACE_S)
    t_end = time.monotonic()
    after = _counters(s)
    events = None
    trace_s = {}
    if trace:
        jax.profiler.stop_trace()
        trace_s["stop"] = time.monotonic() - t_end
        events = trace_reduce.load(trace_reduce.find_xplane(tmp.name))
        trace_s["load"] = time.monotonic() - t_end - trace_s["stop"]
        tmp.cleanup()
    delta = {k: after[k] - before[k] for k in after}
    rec = dict(
        loop=tr["loop"], window_s=float(seconds), t0=t0, t_end=t_end,
        requests=[dict(query=r.query, due=r.due - t0, send=r.send - t0,
                       sketch_s=r.sketch_s,
                       done=None if r.done is None else r.done - t0,
                       error=r.error) for r in reqs],
        answers=[r.result for r in reqs],
        scheduler=dict(completed=delta["completed"],
                       batches=delta["batches"]),
        server=dict(queries=delta["queries"],
                    device_dispatches=delta["device_dispatches"]),
        compiles=dict(cache_misses=delta["cache_misses"],
                      lowerings=delta["lowerings"]),
        setup=dict(ingest_s=s.ingest_s, warmup_s=s.warmup_s,
                   setup_s=t0 - T_START),
        trace=None, trace_s=trace_s)
    if events is not None:
        off = trace_reduce.mark_ns(events) - mark * 1e9
        ns = lambda t: t * 1e9 + off
        fly = [(ns(r.due), ns(r.done if r.done is not None else t_end))
               for r in reqs]
        red = trace_reduce.reduce(events, (ns(t0), ns(t_end)), fly)
        red["queries"] = sum(r.done is not None for r in reqs)
        rec["trace"] = red
        trace_s["reduce"] = time.monotonic() - t_end - sum(trace_s.values())
    return rec


def end_to_end(rec: dict) -> dict:
    """The end-to-end numbers of a run's record. A request with no answer
    counts as slower than any answer: its latency is the window plus the
    grace."""
    worst = rec["window_s"] + GRACE_S
    lat = np.array([(r["done"] if r["done"] is not None else worst)
                    - r["due"] for r in rec["requests"]])
    done_in = sum(r["done"] is not None and r["done"] <= rec["window_s"]
                  for r in rec["requests"])
    return dict(setup_s=rec["setup"]["setup_s"],
                p50_ms=float(np.percentile(lat, 50)) * 1e3,
                p95_ms=float(np.percentile(lat, 95)) * 1e3,
                qps=done_in / rec["window_s"])


def answers_by_name(out, names) -> Optional[dict]:
    """A served ``(scores, ids, r, m)`` row as the check reads it."""
    if out is None:
        return None
    s, g, r, m = (np.asarray(a)[0] for a in out)
    keep = np.isfinite(s) & (g >= 0)
    return dict(names=[names[int(i)] for i in g[keep]],
                score=[float(x) for x in s[keep]],
                r=[float(x) for x in r[keep]],
                m=[float(x) for x in m[keep]])


def reference_answers(lake, queries, config: dict, request, picks,
                      dtype=np.float64) -> list:
    """The reference's answer to the pool entries ``picks``, in order."""
    from reference import Reference
    ref = Reference(lake.tables, [t.column_names for t in lake.tables],
                    int(config["n"]))
    memo = {}
    for q in set(int(x) for x in picks):
        keys, vals = queries[q]
        memo[q] = ref.query(keys, vals, k=request.k,
                            estimator=request.estimator,
                            scorer=request.scorer, alpha=request.alpha,
                            min_sample=request.min_sample, dtype=dtype)
    return [memo[int(q)] for q in picks]


def judge(s: Setup, rec: dict, answers=None):
    """(correct, checks) of a window's answers against the reference;
    ``answers`` stands in for the program's (the control)."""
    if answers is None:
        answers = [answers_by_name(a, s.names) for a in rec["answers"]]
    refs = reference_answers(s.lake, s.queries, s.cell.config, s.request,
                             [r["query"] for r in rec["requests"]])
    limits = s.cell.traffic["limits"]
    return check.verdict(
        check.compare(answers, refs, limits, s.request.k), limits)


def device_info(dev) -> dict:
    """The ``device`` entry: the chip as JAX reports it and its peak
    memory so far."""
    import jax
    stats = dev.memory_stats() or {}
    return dict(platform=dev.platform, kind=dev.device_kind,
                count=len(jax.devices()),
                memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))


def assemble(cell: Cell, rec: dict, device: dict, correct: bool,
             checks: dict, trace: bool) -> dict:
    """The result line's object of one run."""
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = read_metric(m["name"], rec)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
        device = dict(device, busy_s=rec["trace"]["busy_s"],
                      window_s=rec["trace"]["window_s"])
    else:
        e2e = end_to_end(rec)
        metrics = {m["name"]: dict(value=e2e[m["name"]], unit=m["unit"])
                   for m in cell.end_to_end}
    out = dict(correct=bool(correct), attempted=len(rec["requests"]),
               failed=sum(r["done"] is None for r in rec["requests"]),
               metrics=metrics, device=device)
    if trace:
        out["breakdown"] = dict(device_ops=rec["trace"]["device_ops"],
                                idle_gaps=rec["trace"]["idle_gaps"])
    out["checks"] = checks
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             policy=None) -> dict:
    """One whole run of ``cell``: set-up, the window, then (with the
    program's state freed) the check against the reference."""
    dev = devices[0]
    s = setup(cell, seed, dev, policy)
    rec = window(s, seed, seconds, trace)
    t = time.monotonic()
    device = device_info(dev)
    s.sched.close()
    s.srv = s.sched = None
    correct, checks = judge(s, rec)
    traced = ", ".join(f"{k} {v:.1f} s" for k, v in rec["trace_s"].items())
    print(f"bench: set-up {rec['setup']['setup_s']:.1f} s (ingest "
          f"{s.ingest_s:.1f}, warm-up {s.warmup_s:.1f}); window "
          f"{seconds:g} s, every answer in after "
          f"{rec['t_end'] - rec['t0']:.1f} s; trace: {traced or 'off'}; "
          f"reference check {time.monotonic() - t:.1f} s", file=sys.stderr)
    return assemble(cell, rec, device, correct, checks, trace)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (OSError, KeyError, ValueError, CellError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.cache import place_compile_cache
    except ImportError as e:
        print(f"bench: the program under test is missing: {e}",
              file=sys.stderr)
        return 2
    place_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 1
    try:
        chip_peaks(devices[0].device_kind)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    out = run_cell(cell, args.seed % (1 << 63), args.seconds,
                   bool(args.trace), devices[:cell.chips])
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
