"""Open-loop serving-under-load benchmark: async scheduler vs sequential
dispatch (DESIGN.md §9).

A Poisson load generator submits single-query tickets at a fixed offered
rate — open loop: arrivals never wait for completions, so backlog is real
backlog. The same pre-seeded arrival trace is replayed against two
dispatch disciplines over one warmed `Server`:

  * **sequential** — `AsyncScheduler(workers=1, max_coalesce=1)`: one
    engine dispatch per query, FIFO. This is what a naive serving loop
    does, and its capacity is 1/(single-dispatch latency).
  * **scheduler** — the real `AsyncScheduler`: whatever backlog
    accumulates while a worker is busy coalesces into one batched
    dispatch, covered by the engine's measured-cost bucket ladder.

For each offered rate the bench reports completion-latency p50/p99
(submit → result, queue wait included), throughput, and **goodput**
(queries completing within the SLO, per second of wall time). Past the
sequential capacity the sequential discipline's queue grows without bound
and its goodput collapses, while continuous batching amortises the scan
and keeps the scheduler's goodput at the offered rate — the gap is the
point of the tentpole.

The measured phase runs against a warmed server and a warmed scheduler
path, and asserts **zero compiles** end to end (`CompileCache.misses`
flat) plus scheduler-goodput ≥ sequential-goodput at every rate at or
above capacity. ``--smoke`` shrinks the corpus/horizon for CI and keeps
both gates.

``--mesh`` adds the sharded-serving section (DESIGN.md §10): the same
open-loop replay against a `Server` whose index is column-sharded over 8
devices, gated on (a) bit-identical results vs the single-device server
(shared `CompileCache`, uneven C), (b) zero steady-state compiles, and
(c) scheduler goodput beating sequential dispatch above capacity. It
runs in one process over every device the backend has; ``--mesh`` gives
the CPU backend 8 devices before JAX initialises (no effect on a TPU).

Emits ``BENCH_serving.json`` (the ``"sharded"`` key holds the mesh
section; either entrypoint preserves the other's section on rewrite).
"""
from __future__ import annotations

import json
import time

import numpy as np
import jax

from repro.data.pipeline import Table, sbn_pair
from repro.engine import index as IX
from repro.engine import plans as PL
from repro.engine import serve as SV
from repro.engine.scheduler import AsyncScheduler
from repro.launch.mesh import make_host_mesh

ARTIFACT = "BENCH_serving.json"


def _corpus(rng, n_tables, n_queries, n_rows):
    tables, queries = [], []
    for i in range(n_tables):
        tx, ty, r, c = sbn_pair(rng, n_max=n_rows)
        tables.append(Table(keys=ty.keys, values=ty.values, name=f"t{i}"))
        if len(queries) < n_queries:
            queries.append(tx)
    return tables, queries


def _build_server(tables, n_sketch, buckets):
    mesh = make_host_mesh()
    ndev = int(mesh.devices.size)
    pad = ((len(tables) + ndev - 1) // ndev) * ndev
    idx = IX.build_index(tables, n=n_sketch, pad_to=pad)
    shape = PL.ShapePolicy(k_max=10)
    req = PL.Request(k=10, scorer="s4")
    srv = SV.Server(make_host_mesh(), idx, shape, request=req,
                    buckets=buckets)
    srv.warmup(modes=("off",))
    return srv


def _single_query_pool(queries, n_sketch):
    """Per-query sketch pytrees with a leading [1] axis, as host numpy —
    submit-time slicing must not trigger eager device ops."""
    qsks = SV.build_query_sketches([q.keys for q in queries],
                                   [q.values for q in queries], n=n_sketch)
    host = jax.tree.map(np.asarray, qsks)
    return [jax.tree.map(lambda a, i=i: a[i:i + 1], host)
            for i in range(len(queries))]


def _warm_scheduler_path(srv, pool, slo_ms):
    """Run a burst through a throwaway scheduler so the measured runs see
    a steady-state path: merge widths, result conversion, and the bucket
    ladder all exercised once."""
    with AsyncScheduler(srv, workers=2, slo_ms=slo_ms) as sched:
        tickets = [sched.submit(sk) for sk in pool]
        for t in tickets:
            t.result(timeout=300.0)


def _replay(srv, pool, gaps_s, *, workers, max_coalesce, slo_ms):
    """Replay one arrival trace open-loop and collect per-query latencies.

    Returns (latencies_s, on_time, wall_s, sched_stats)."""
    n = len(gaps_s)
    sched = AsyncScheduler(srv, workers=workers, max_coalesce=max_coalesce,
                           slo_ms=slo_ms)
    tickets = []
    t0 = time.monotonic()
    due = t0
    for i in range(n):
        due += gaps_s[i]
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        tickets.append(sched.submit(pool[i % len(pool)]))
    for t in tickets:
        t.result(timeout=600.0)
    wall = time.monotonic() - t0
    stats = sched.stats()
    sched.close()
    lats = np.array([t.latency_s for t in tickets])
    on_time = int(sum(not t.missed_deadline for t in tickets))
    return lats, on_time, wall, stats


def run(n_tables: int = 256, n_queries: int = 64, n_sketch: int = 128,
        n_rows: int = 4000, seed: int = 7, horizon_s: float = 8.0,
        slo_ms: float = 400.0, offered: tuple = (0.5, 1.0, 3.0),
        buckets: tuple = (1, 8, 32), workers: int = 2,
        artifact: str | None = ARTIFACT, smoke: bool = False):
    rng = np.random.default_rng(seed)
    tables, queries = _corpus(rng, n_tables, n_queries, n_rows)
    srv = _build_server(tables, n_sketch, buckets)
    pool = _single_query_pool(queries, n_sketch)
    _warm_scheduler_path(srv, pool, slo_ms)

    # sequential capacity: median single-dispatch latency on the warmed
    # server sets the 1.0× offered-load point
    svc = []
    for sk in pool[: min(16, len(pool))]:
        t0 = time.perf_counter()
        jax.block_until_ready(srv.query_batch(sk))
        svc.append(time.perf_counter() - t0)
    service_s = float(np.median(svc))
    capacity_qps = 1.0 / service_s
    print(f"single-dispatch service: {service_s * 1e3:.1f} ms "
          f"-> sequential capacity ~{capacity_qps:.1f} qps")

    compiles0 = srv.cache.misses
    runs = []
    for mult in offered:
        rate = mult * capacity_qps
        n_arr = max(int(rate * horizon_s), 8)
        gaps = rng.exponential(1.0 / rate, size=n_arr)
        for mode in ("sequential", "scheduler"):
            kw = (dict(workers=1, max_coalesce=1) if mode == "sequential"
                  else dict(workers=workers, max_coalesce=None))
            lats, on_time, wall, stats = _replay(srv, pool, gaps,
                                                 slo_ms=slo_ms, **kw)
            row = dict(mode=mode, offered_x=float(mult),
                       offered_qps=float(rate), n_queries=n_arr,
                       p50_ms=float(np.percentile(lats, 50) * 1e3),
                       p99_ms=float(np.percentile(lats, 99) * 1e3),
                       on_time=on_time,
                       goodput_qps=on_time / wall,
                       throughput_qps=len(lats) / wall,
                       wall_s=float(wall),
                       avg_coalesce=float(stats["avg_coalesce"]),
                       batches=int(stats["batches"]),
                       deadline_misses=int(stats["deadline_misses"]))
            runs.append(row)
            print(f"  {mult:>4.1f}x {mode:>10s}: p50 {row['p50_ms']:8.1f} ms"
                  f"  p99 {row['p99_ms']:8.1f} ms  goodput "
                  f"{row['goodput_qps']:6.1f}/{rate:.1f} qps  "
                  f"coalesce x{row['avg_coalesce']:.1f}")
    compiles_steady = srv.cache.misses - compiles0

    # -- gates (also enforced by the CI smoke) -------------------------------
    assert compiles_steady == 0, (
        f"steady-state serving triggered {compiles_steady} compiles — the "
        "scheduler must ride the warmed plan cache (DESIGN.md §9)")
    for mult in offered:
        pair = {r["mode"]: r for r in runs if r["offered_x"] == float(mult)}
        seq, sch = pair["sequential"], pair["scheduler"]
        if mult > 1.0:
            # overload is where batching matters: sequential dispatch falls
            # arbitrarily far behind an open-loop arrival process faster
            # than its service rate, coalescing keeps up
            assert sch["goodput_qps"] > seq["goodput_qps"], (
                f"at {mult}x offered load the scheduler's goodput "
                f"({sch['goodput_qps']:.1f} qps) must beat sequential "
                f"dispatch ({seq['goodput_qps']:.1f} qps)")
        elif mult == 1.0:
            # at exactly capacity the sequential baseline keeps up by
            # definition (service time == inter-arrival time), so demand
            # parity, not superiority: the scheduler must not collapse
            # under its queueing/coalescing overhead
            assert sch["goodput_qps"] > 0.5 * seq["goodput_qps"], (
                f"at 1.0x offered load the scheduler's goodput "
                f"({sch['goodput_qps']:.1f} qps) collapsed vs sequential "
                f"dispatch ({seq['goodput_qps']:.1f} qps)")
    print("serving gates: OK (0 compiles; scheduler goodput beats "
          "sequential above capacity, holds at capacity)")

    # per-stage serving counts (DESIGN.md §11): the replayed queries'
    # dispatch mix, device launches vs host select/combine
    tp = srv.throughput()
    stages = tp.get("stages", {})
    if stages:
        print("  stage mix: " + "  ".join(
            f"{name} x{rec['count']}"
            for name, rec in sorted(stages.items())))

    out = dict(config=dict(n_tables=n_tables, n_queries=n_queries,
                           n_sketch=n_sketch, n_rows=n_rows,
                           horizon_s=horizon_s, slo_ms=slo_ms,
                           buckets=list(buckets), workers=workers,
                           seed=seed, smoke=bool(smoke)),
               service_ms=service_s * 1e3,
               sequential_capacity_qps=capacity_qps,
               compiles_steady_state=compiles_steady,
               stages=stages,
               device_dispatches=tp.get("device_dispatches", 0),
               runs=runs)
    if artifact:
        _merge_artifact(artifact, out)
        print(f"wrote {artifact}")

    # flat record for the benchmarks/run.py CSV printer
    flat = dict(service_ms=out["service_ms"],
                capacity_qps=capacity_qps,
                compiles_steady_state=compiles_steady)
    for r in runs:
        tag = f"{r['mode'][:3]}_{r['offered_x']:g}x"
        flat[f"{tag}_goodput_qps"] = r["goodput_qps"]
        flat[f"{tag}_p50_ms"] = r["p50_ms"]
        flat[f"{tag}_p99_ms"] = r["p99_ms"]
    return flat


def _merge_artifact(artifact: str, section: dict):
    """Rewrite ``artifact`` with ``section``'s keys while preserving any
    keys the other entrypoint owns (`run` owns the top level, `run_mesh`
    owns ``"sharded"``) — the two refresh independently."""
    try:
        with open(artifact) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        prev = {}
    prev.update(section)
    with open(artifact, "w") as f:
        json.dump(prev, f, indent=2)


def run_mesh(n_tables: int = 131, n_queries: int = 32, n_sketch: int = 128,
             n_rows: int = 2000, seed: int = 11, horizon_s: float = 4.0,
             slo_ms: float = 400.0, offered: tuple = (1.0, 3.0),
             buckets: tuple = (1, 8), workers: int = 2,
             parity_queries: int = 16,
             artifact: str | None = ARTIFACT, smoke: bool = False):
    """The sharded section: replay the open-loop bench against an 8-way
    column-sharded server, after gating bit-identity against the
    single-device server (DESIGN.md §10). ``n_tables`` is deliberately not
    divisible by 8 — `place_shard`'s masked pad columns are on the path."""
    ndev = jax.device_count()
    if ndev < 2:
        raise RuntimeError(
            "the sharded section needs a multi-device backend: run "
            "`python -m benchmarks.bench_serving --mesh`, which gives the "
            "CPU backend 8 devices before JAX initialises")

    rng = np.random.default_rng(seed)
    tables, queries = _corpus(rng, n_tables, n_queries, n_rows)
    idx = IX.build_index(tables, n=n_sketch)      # uneven C: pads per mesh
    shape = PL.ShapePolicy(k_max=10)
    req = PL.Request(k=10, scorer="s4")
    cache = SV.CompileCache()                     # shared: keys must not collide
    mesh1 = jax.make_mesh((1,), ("shard",), devices=jax.devices()[:1])
    mesh8 = jax.make_mesh((ndev,), ("shard",))
    srv1 = SV.Server(mesh1, idx, shape, request=req, buckets=buckets,
                     cache=cache)
    srv8 = SV.Server(mesh8, idx, shape, request=req, buckets=buckets,
                     cache=cache)
    srv1.warmup(modes=("off",))
    srv8.warmup(modes=("off",))
    pool = _single_query_pool(queries, n_sketch)
    _warm_scheduler_path(srv8, pool, slo_ms)
    compiles0 = cache.misses

    # -- parity gate: sharded == single-host, bit for bit --------------------
    mismatches = 0
    for sk in pool[:parity_queries]:
        o1 = srv1.query_batch(sk)
        o8 = srv8.query_batch(sk)
        for a, b in zip(o1, o8):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                mismatches += 1
    assert mismatches == 0, (
        f"{mismatches} sharded-vs-single-host mismatches — the cross-shard "
        "combine must be bit-identical (DESIGN.md §10)")
    print(f"sharded parity: {parity_queries} queries bit-identical "
          f"(D={ndev} vs D=1)")

    svc = []
    for sk in pool[: min(16, len(pool))]:
        t0 = time.perf_counter()
        jax.block_until_ready(srv8.query_batch(sk))
        svc.append(time.perf_counter() - t0)
    service_s = float(np.median(svc))
    capacity_qps = 1.0 / service_s
    print(f"sharded single-dispatch service: {service_s * 1e3:.1f} ms "
          f"-> sequential capacity ~{capacity_qps:.1f} qps")

    runs = []
    for mult in offered:
        rate = mult * capacity_qps
        n_arr = max(int(rate * horizon_s), 8)
        gaps = rng.exponential(1.0 / rate, size=n_arr)
        for mode in ("sequential", "scheduler"):
            kw = (dict(workers=1, max_coalesce=1) if mode == "sequential"
                  else dict(workers=workers, max_coalesce=None))
            lats, on_time, wall, stats = _replay(srv8, pool, gaps,
                                                 slo_ms=slo_ms, **kw)
            row = dict(mode=mode, offered_x=float(mult),
                       offered_qps=float(rate), n_queries=n_arr,
                       p50_ms=float(np.percentile(lats, 50) * 1e3),
                       p99_ms=float(np.percentile(lats, 99) * 1e3),
                       on_time=on_time,
                       goodput_qps=on_time / wall,
                       throughput_qps=len(lats) / wall,
                       wall_s=float(wall),
                       avg_coalesce=float(stats["avg_coalesce"]),
                       batches=int(stats["batches"]),
                       deadline_misses=int(stats["deadline_misses"]))
            runs.append(row)
            print(f"  {mult:>4.1f}x {mode:>10s}: p50 {row['p50_ms']:8.1f} ms"
                  f"  p99 {row['p99_ms']:8.1f} ms  goodput "
                  f"{row['goodput_qps']:6.1f}/{rate:.1f} qps  "
                  f"coalesce x{row['avg_coalesce']:.1f}")
    compiles_steady = cache.misses - compiles0

    # -- gates (also enforced by the CI smoke) -------------------------------
    assert compiles_steady == 0, (
        f"sharded steady-state serving triggered {compiles_steady} compiles "
        "— mesh re-placement must ride the warmed plan cache")
    for mult in offered:
        pair = {r["mode"]: r for r in runs if r["offered_x"] == float(mult)}
        seq, sch = pair["sequential"], pair["scheduler"]
        if mult > 1.0:
            assert sch["goodput_qps"] > seq["goodput_qps"], (
                f"at {mult}x offered load the sharded scheduler's goodput "
                f"({sch['goodput_qps']:.1f} qps) must beat sequential "
                f"dispatch ({seq['goodput_qps']:.1f} qps)")
        elif mult == 1.0:
            assert sch["goodput_qps"] > 0.5 * seq["goodput_qps"], (
                f"at 1.0x offered load the sharded scheduler's goodput "
                f"({sch['goodput_qps']:.1f} qps) collapsed vs sequential "
                f"dispatch ({seq['goodput_qps']:.1f} qps)")
    print("sharded serving gates: OK (bit-identical parity; 0 compiles; "
          "scheduler goodput beats sequential above capacity)")

    sharded = dict(config=dict(n_tables=n_tables, n_queries=n_queries,
                               n_sketch=n_sketch, n_rows=n_rows,
                               horizon_s=horizon_s, slo_ms=slo_ms,
                               buckets=list(buckets), workers=workers,
                               seed=seed, smoke=bool(smoke), ndev=ndev),
                   parity=dict(queries=parity_queries, bitwise_equal=True),
                   service_ms=service_s * 1e3,
                   sequential_capacity_qps=capacity_qps,
                   compiles_steady_state=compiles_steady,
                   runs=runs)
    if artifact:
        _merge_artifact(artifact, {"sharded": sharded})
        print(f"wrote {artifact} (sharded section)")

    flat = dict(sharded_ndev=ndev,
                sharded_parity_queries=parity_queries,
                sharded_service_ms=sharded["service_ms"],
                sharded_capacity_qps=capacity_qps,
                sharded_compiles_steady_state=compiles_steady)
    for r in runs:
        tag = f"sharded_{r['mode'][:3]}_{r['offered_x']:g}x"
        flat[f"{tag}_goodput_qps"] = r["goodput_qps"]
        flat[f"{tag}_p99_ms"] = r["p99_ms"]
    print("SHARDED-FLAT " + json.dumps(flat))
    return flat


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="small corpus + short horizon (CI gate)")
    p.add_argument("--mesh", action="store_true",
                   help="sharded-serving section: column-sharded server "
                        "over every device (8 virtual ones on the CPU)")
    p.add_argument("--artifact", default=ARTIFACT)
    a = p.parse_args(argv)
    artifact = a.artifact or None
    if a.mesh:
        # must precede the first device query of the process
        jax.config.update("jax_num_cpu_devices", 8)
        if a.smoke:
            return run_mesh(n_tables=61, n_queries=16, n_sketch=64,
                            n_rows=1500, horizon_s=2.0, offered=(1.0, 3.0),
                            buckets=(1, 8), parity_queries=8,
                            artifact=None, smoke=True)
        return run_mesh(artifact=artifact)
    if a.smoke:
        return run(n_tables=64, n_queries=24, n_sketch=64, n_rows=1500,
                   horizon_s=2.5, offered=(1.0, 3.0), buckets=(1, 8, 16),
                   artifact=None, smoke=True)
    return run(artifact=artifact)


if __name__ == "__main__":
    from repro.launch.cache import place_compile_cache
    place_compile_cache()
    main()
