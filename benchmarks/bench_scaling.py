"""Stage-1 scaling benchmark (DESIGN.md §7): linear containment scan vs the
QCR-style inverted key index, over corpus sizes 512 → 100k+ columns.

The candidate-generation layer is pluggable (`ShapePolicy.candidates`);
this benchmark measures what that buys. Per scale, with identical synthetic
corpora and queries:

  * ``stage1`` — per-dispatch cost of `Server.stage1_hits` through each
    source: the scan is O(C) per query, the inverted probe is
    O(n · (W + log E)) — *corpus-size-independent*, so its curve should be
    near-flat while the scan's grows linearly;
  * ``e2e_safe`` — p50 end-to-end ``prune='safe'`` `query_batch` latency
    through each source. The inverted source serves this through the fused
    single-dispatch device-resident plan (DESIGN.md §11); its legacy
    two-dispatch path (host [B, C] scatter + host select + second launch)
    is measured alongside as ``e2e_safe_two_dispatch_p50_ms`` — the §11
    before/after. The **e2e flatness** of the fused curve is the headline:
    with stage-1 corpus-size-independent AND no O(C) host tail, end-to-end
    latency should barely grow 512 → 131k columns;
  * the timed fused loop is cross-checked against the per-stage dispatch
    counters: exactly ONE device dispatch ("fused") per `query_batch`, zero
    dense probes, host selects or second launches;
  * exactness is asserted on every run: both sources must return identical
    hit counts (the `prune='safe'` ground-truth contract).

A mutation sweep (appends / deletes / compaction on the warmed capacity
rungs, through a live inverted-source server) asserts **zero** compiles
after warmup — postings shapes ride the segment capacity ladder and the
gather window its own ``2^i`` ladder.

Corpora are synthesised directly at the sketch-plane level (distinct keys
per column drawn from per-domain pools, rows fib-ascending like real KMV
minima) so the 100k+ scales build in seconds; stage-1 cost depends only on
the planes' shapes and overlap structure, not on how they were built.

Emits ``BENCH_scaling.json`` (schema in benchmarks/README.md). ``--smoke``
runs CI-sized scales, writes no artifact, and *asserts* the inverted source
beats the scan at the largest smoke scale. All numbers are container-load-
sensitive (see benchmarks/README.md).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np
import jax.numpy as jnp

from repro.core.containment import fib_u32_np
from repro.core.sketch import Agg, CorrelationSketch
from repro.data.pipeline import Table
from repro.engine import index as IX
from repro.engine import lifecycle as LC
from repro.engine import plans as PL
from repro.engine import serve as SV
from repro.launch.mesh import make_host_mesh

ARTIFACT = "BENCH_scaling.json"
SOURCES = ("scan", "inverted")


def _fib_sorted(kh: np.ndarray) -> np.ndarray:
    """Sort each row fib-ascending — the stored-minima convention of real
    KMV sketches (`repro.engine.index.key_minima` reads the last slot)."""
    order = np.argsort(fib_u32_np(kh), axis=1, kind="stable")
    return np.take_along_axis(kh, order, axis=1)


def _distinct_rows(rng, pool_size: int, rows: int, n: int) -> np.ndarray:
    """[rows, n] index matrix, distinct within each row (resample the rare
    duplicate rows — with pool_size ≫ n a round or two suffices)."""
    idx = rng.integers(0, pool_size, size=(rows, n))
    while True:
        s = np.sort(idx, axis=1)
        bad = (s[:, 1:] == s[:, :-1]).any(axis=1)
        if not bad.any():
            return idx
        idx[bad] = rng.integers(0, pool_size, size=(int(bad.sum()), n))


def synth_planes(rng, C: int, n: int, domains: int, pool: int):
    """[C, n] key-hash rows with real overlap structure: per-domain pools of
    distinct u32 hashes, each column holding n distinct draws from its
    domain's pool. The key universe (domains x pool) scales with the corpus
    (`synth_index`), so per-key column multiplicity — and the postings
    window rung — stays constant as C grows, like a real open-data corpus
    whose key universe grows with it."""
    pools = []
    for _ in range(domains):
        vals = np.unique(rng.integers(1, 1 << 31, size=2 * pool)
                         .astype(np.uint32))
        pools.append(vals[:pool])
    kh = np.empty((C, n), np.uint32)
    for d in range(domains):
        cols = np.arange(d, C, domains)
        kh[cols] = pools[d][_distinct_rows(rng, pool, len(cols), n)]
    return _fib_sorted(kh), pools


def synth_index(rng, C: int, n: int, domains: int | None = None,
                pool: int = 4096, cols_per_domain: int = 64) -> tuple:
    # the domain count scales with the corpus (a data lake grows by gaining
    # *unrelated* collections): queries stay selective — bounded in-domain
    # candidates — no matter how large the lake, which is exactly the
    # regime where stage-1 cost decides end-to-end latency. Per-domain
    # density (columns per domain → per-key multiplicity → postings window
    # rung → survivor-set width) is held CONSTANT across scales so the
    # sweep varies corpus size and nothing else; letting density grow with
    # C (as pre-§11 revisions did between the two smallest scales) widens
    # the gather window and the survivor sets alongside the corpus and the
    # "e2e growth" measured is density growth, not scale growth
    domains = domains if domains is not None else max(8, C // cols_per_domain)
    kh, pools = synth_planes(rng, C, n, domains, pool)
    shard = IX.IndexShard(
        key_hash=jnp.asarray(kh),
        values=jnp.asarray(rng.standard_normal((C, n)).astype(np.float32)),
        mask=jnp.ones((C, n), jnp.float32),
        col_min=jnp.full((C,), -4.0, jnp.float32),
        col_max=jnp.full((C,), 4.0, jnp.float32),
        rows=jnp.full((C,), float(pool), jnp.float32))
    idx = IX.SketchIndex(shard=shard, names=[f"c{i}" for i in range(C)], n=n)
    return idx, pools


def synth_queries(rng, pools, nq: int, n: int) -> CorrelationSketch:
    """A [nq]-leading query sketch batch drawn from the same domain pools
    (so every query has real in-domain candidates)."""
    kh = np.stack([
        _fib_sorted(rng.choice(pools[q % len(pools)], size=(1, n),
                               replace=False).astype(np.uint32))[0]
        for q in range(nq)])
    ones = jnp.ones((nq, n), jnp.float32)
    return CorrelationSketch(
        key_hash=jnp.asarray(kh),
        acc=jnp.asarray(rng.standard_normal((nq, n)).astype(np.float32)),
        cnt=ones, order=ones, mask=jnp.ones((nq, n), bool),
        col_min=jnp.full((nq,), -4.0, jnp.float32),
        col_max=jnp.full((nq,), 4.0, jnp.float32),
        rows=jnp.full((nq,), 4096.0, jnp.float32), agg=Agg.MEAN)


def _p50(samples) -> float:
    return float(np.median(samples))


def measure_scale(rng, C: int, n: int, batch: int, repeats: int,
                  mesh) -> dict:
    """One corpus size: stage-1 and e2e-safe timings through both sources,
    plus the exactness cross-check."""
    idx, pools = synth_index(rng, C, n)
    sks = synth_queries(rng, pools, batch, n)
    rec = {"n_columns": C}
    hits = {}
    for cand in SOURCES:
        # the survivor ladder base is corpus-size-independent: in-domain
        # candidate sets are bounded (constant per-domain density), so the
        # survivor union is too, and the adaptive rung climbs on demand if
        # a query ever overflows.  Scaling the base with C (as pre-§11
        # revisions did) silently floors stage-2 at O(base) columns per
        # batch and drowns the tail this benchmark exists to measure.
        shape = PL.ShapePolicy(k_max=10, candidates=cand, prune_base=64)
        srv = SV.Server(mesh, idx, shape, buckets=(batch,),
                        cache=SV.CompileCache())
        srv.warmup(modes=("safe",))
        req = PL.Request(k=10, prune="safe")
        # one untimed dispatch of each op: first-call python/plan overhead
        # must not pollute the timed samples
        srv.stage1_hits(sks)
        srv.query_batch(sks, request=req)   # also adapts the fused rung
        misses = srv.cache.misses
        ex = srv._entries[srv._order[0]].exec
        s1 = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            h = srv.stage1_hits(sks)
            s1.append(time.perf_counter() - t0)
        n0 = ex.stage_stats()
        e2e = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            srv.query_batch(sks, request=req)
            e2e.append(time.perf_counter() - t0)
        n1 = ex.stage_stats()
        assert srv.cache.misses == misses, f"compile after warmup ({cand})"
        hits[cand] = h
        delta = {k: n1.get(k, 0) - n0.get(k, 0)
                 for k in set(n0) | set(n1)}
        rec[cand] = dict(
            stage1_p50_ms=1e3 * _p50(s1),
            stage1_per_query_ms=1e3 * _p50(s1) / batch,
            e2e_safe_p50_ms=1e3 * _p50(e2e))
        if cand == "inverted":
            rec["window"] = ex.source().W
            rec["postings_entries"] = ex.source().E
            # the DESIGN.md §11 dispatch contract, confirmed by counters:
            # post-adaptation, every safe query batch is ONE fused device
            # dispatch — no dense probe, no host select, no second launch
            assert delta.get("fused", 0) == repeats, delta
            for stage in ("stage1", "stage2", "scan", "select"):
                assert delta.get(stage, 0) == 0, (stage, delta)
            rec[cand]["fused_dispatches_per_query_batch"] = (
                delta["fused"] / repeats)
            # the legacy two-dispatch path (host select between launches) —
            # the §11 before/after comparison oracle
            ex.fused_safe = False
            try:
                srv.query_batch(sks, request=req)       # untimed first call
                two = []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    srv.query_batch(sks, request=req)
                    two.append(time.perf_counter() - t0)
            finally:
                ex.fused_safe = True
            assert srv.cache.misses == misses, "two-dispatch path compiled"
            rec[cand]["e2e_safe_two_dispatch_p50_ms"] = 1e3 * _p50(two)
    np.testing.assert_array_equal(hits["scan"], hits["inverted"]), \
        "sources disagree on hit counts"
    return rec


def mutation_sweep(rng, n: int = 64, delta_cap: int = 16) -> dict:
    """Zero-compile contract under mutation: a live inverted-source server,
    warmed once, then appends / deletes / compaction on the warmed capacity
    rungs — `CompileCache.misses` must stay flat."""
    def tbl(name, m=600):
        return Table(keys=rng.choice(1 << 20, size=m, replace=False)
                     .astype(np.uint32),
                     values=rng.standard_normal(m).astype(np.float32),
                     name=name)
    live = LC.LiveIndex(n=n, delta_cap=delta_cap)
    live.append([tbl(f"t{i}") for i in range(6)])
    srv = SV.Server(make_host_mesh(), live,
                    PL.ShapePolicy(k_max=4, prune_base=4,
                                   candidates="inverted"),
                    buckets=(4,), cache=SV.CompileCache())
    srv.warmup(modes=("off", "safe", "topm"), include_ladder=True)
    sks = synth_queries(rng, [np.arange(1, 4096, dtype=np.uint32)], 4, n)
    before = srv.cache.misses
    ops = 0
    for step in range(3):
        live.append([tbl(f"x{step}")])
        live.delete(f"t{step}")
        ops += 2
        for prune in ("off", "safe", "topm"):
            srv.query_batch(sks, request=PL.Request(k=4, prune=prune))
    live.compact()
    ops += 1
    srv.query_batch(sks, request=PL.Request(k=4, prune="safe"))
    assert srv.cache.misses == before, \
        f"mutation sweep compiled: {srv.cache.misses} != {before}"
    return dict(mutations=ops, misses_before=before,
                misses_after=srv.cache.misses, zero_compiles=True)


def run(scales=(512, 4096, 32768, 131072), n_sketch: int = 64,
        batch: int = 8, repeats: int = 11, seed: int = 7,
        smoke: bool = False, artifact: str | None = ARTIFACT):
    rng = np.random.default_rng(seed)
    mesh = make_host_mesh()
    recs = [measure_scale(rng, C, n_sketch, batch, repeats, mesh)
            for C in scales]
    sweep = mutation_sweep(rng, n=n_sketch)

    ratio = lambda cand, k: (recs[-1][cand][k] / max(recs[0][cand][k], 1e-9))
    summary = dict(
        scale_span=scales[-1] / scales[0],
        scan_stage1_growth=ratio("scan", "stage1_p50_ms"),
        inverted_stage1_growth=ratio("inverted", "stage1_p50_ms"),
        # e2e flatness (DESIGN.md §11): end-to-end safe latency growth over
        # the whole scale span — the fused device-resident path should hold
        # this near 1 where the two-dispatch path grows with its O(C) tail
        inverted_e2e_growth=ratio("inverted", "e2e_safe_p50_ms"),
        inverted_e2e_two_dispatch_growth=ratio(
            "inverted", "e2e_safe_two_dispatch_p50_ms"),
        fused_vs_two_dispatch_at_max=(
            recs[-1]["inverted"]["e2e_safe_two_dispatch_p50_ms"]
            / max(recs[-1]["inverted"]["e2e_safe_p50_ms"], 1e-9)),
        stage1_speedup_at_max=(recs[-1]["scan"]["stage1_p50_ms"]
                               / max(recs[-1]["inverted"]["stage1_p50_ms"],
                                     1e-9)),
        e2e_safe_speedup_at_max=(recs[-1]["scan"]["e2e_safe_p50_ms"]
                                 / max(recs[-1]["inverted"]["e2e_safe_p50_ms"],
                                       1e-9)))
    if smoke:
        assert (recs[-1]["inverted"]["stage1_p50_ms"]
                < recs[-1]["scan"]["stage1_p50_ms"]), (
            "inverted source must beat the scan at the largest smoke scale: "
            f"{recs[-1]}")
        assert (recs[-1]["inverted"]["e2e_safe_p50_ms"]
                < recs[-1]["inverted"]["e2e_safe_two_dispatch_p50_ms"]), (
            "fused single-dispatch path must beat the two-dispatch path at "
            f"the largest smoke scale: {recs[-1]['inverted']}")
        # flatness gate with CI-noise margin: the acceptance bound on the
        # full run (131k ≤ 2x 512) is checked on the artifact
        assert summary["inverted_e2e_growth"] <= 3.0, summary
    result = dict(n_sketch=n_sketch, batch=batch, repeats=repeats,
                  scales=recs, summary=summary, mutation_sweep=sweep)
    if artifact:
        with open(artifact, "w") as f:
            json.dump(result, f, indent=2)

    flat_recs = []
    for rec in recs:
        flat = {"n_columns": rec["n_columns"]}
        for cand in SOURCES:
            for k, v in rec[cand].items():
                flat[f"{cand}_{k}"] = v
        flat_recs.append(flat)
    flat_recs.append(dict(n_columns=0, **{f"summary_{k}": v
                                          for k, v in summary.items()},
                          zero_compiles=sweep["zero_compiles"]))
    return flat_recs


def main():
    import argparse
    ap = argparse.ArgumentParser(
        description="stage-1 scaling: linear scan vs inverted key index "
                    "(emits BENCH_scaling.json; see benchmarks/README.md)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized scales, no artifact; asserts the inverted "
                         "source beats the scan at the largest scale and "
                         "that the mutation sweep compiles nothing")
    args = ap.parse_args()
    if args.smoke:
        recs = run(scales=(512, 4096, 16384), n_sketch=32, batch=4,
                   repeats=3, smoke=True, artifact=None)
    else:
        recs = run()
    for r in recs:
        print("scaling," + ",".join(f"{k}={v:.4g}" if isinstance(v, float)
                                    else f"{k}={v}" for k, v in r.items()))
    if not args.smoke:
        print(f"wrote {os.path.abspath(ARTIFACT)}")


if __name__ == "__main__":
    from repro.launch.cache import place_compile_cache
    place_compile_cache()
    main()
