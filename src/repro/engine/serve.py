"""Unified request serving over the plan/executor engine (DESIGN.md §4/§6).

One `Server` facade serves every index flavour:

  * a static `repro.engine.index.SketchIndex` (or an already-placed
    `IndexShard`) is treated as a **single-segment live index**;
  * a mutating `repro.engine.lifecycle.LiveIndex` is served across its
    segments with a deterministic cross-segment top-k combine and a
    `refresh()` that picks up mutations.

Under the facade, one `_SegmentExec` per resident segment shape dispatches
the compiled plans of `repro.engine.plans`:

  * **compile cache O(shapes)** — programs are keyed on (plan kind, bucket,
    index shape, `ShapePolicy`); per-request semantics (k, scorer,
    estimator, prune mode, α, eligibility floor) ride in as traced operands
    or host-side slices, so a post-warmup request sweep over every scorer ×
    estimator × k ≤ k_max × prune mode triggers **zero compiles**
    (`CompileCache.misses` is the counter the tests pin);
  * **batched sketch construction** — incoming query columns are cut into
    fixed-length row chunks, sketched with a vmapped `build_sketch` and
    folded with the (exact) KMV merge, in one compiled program;
  * **pad-to-bucket batching** + **measured-cost planning** — request
    batches are covered by the cheapest mix of warmed bucket dispatches
    (exact DP over `warmup()` timings);
  * **per-bucket score_chunk** — large batches shrink the candidate block so
    the ``[B, chunk, n]`` intersect intermediates stay cache-resident;
  * **two-stage retrieval** (``Request.prune``, DESIGN.md §5) — ``safe``
    dispatches probe → host filter → gather-compacted scoring on the fixed
    ``prune_base · 2^i`` rung ladder; ``topm`` dispatches the fused plan;
  * **joinability-only queries** — `search_joinable` serves the paper's
    *first* stage (§2/Defn. 3) as a standalone workload.

`QueryServer` (here) and `repro.engine.lifecycle.LiveQueryServer` survive
only as deprecated aliases of `Server`.

Padding rows are copies of the last real query; because the s4 normalisation
is per query row, they cannot perturb real results, and they are sliced off
before returning.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import containment as CT
from repro.core.sketch import PAD_KEY, Agg, CorrelationSketch, build_sketch, merge
from repro.engine import candidates as CD
from repro.engine import plans as PL
from repro.engine import query as Q
from repro.engine import trace as TR
from repro.engine.index import (IndexShard, KeyMinima, SketchIndex,
                                build_postings, key_minima, place_shard,
                                precompute_prep, query_arrays,
                                shard_for_mesh)


def build_query_sketches(keys_list: Sequence[np.ndarray],
                         values_list: Sequence[np.ndarray], *,
                         n: int, agg: Agg = Agg.MEAN,
                         chunk: int = 8192) -> CorrelationSketch:
    """Sketch a batch of query columns in one compiled program.

    Every column is cut into fixed-length ``chunk`` row blocks
    (validity-masked; only real blocks are laid out), and one jitted
    program sketches all blocks with a vmapped `build_sketch` and folds each
    query's block sketches with the KMV merge — exact by the closure
    property, identical to sketching each column alone. The program
    compiles once per input shape (chunk length, ``NQ``, total blocks, fold
    rounds); `front_stats` counts calls and compiles. Returns a
    `CorrelationSketch` whose leaves carry a leading ``[NQ]`` axis, ready
    for `repro.engine.index.query_arrays`.
    """
    assert len(keys_list) == len(values_list) and keys_list, "empty query batch"
    _count("calls")
    with TR.span("front.sketch"):
        return _build_query_sketches(keys_list, values_list, n, agg, chunk)


def _build_query_sketches(keys_list, values_list, n, agg, chunk):
    # ragged layout: only real chunks are materialised and sketched, so one
    # long query costs its own chunks, not nq × its chunk count. (The fold
    # still runs max-chunk-count rounds over all nq rows, but each round is
    # an n-sized merge — noise next to the chunk-sized builds.)
    with TR.span("front.layout"):
        counts = np.array([max(1, -(-len(k) // chunk)) for k in keys_list])
        starts = np.cumsum(np.concatenate([[0], counts]))
        total = int(starts[-1])
        keys = np.zeros((total, chunk), np.uint32)
        vals = np.zeros((total, chunk), np.float32)
        valid = np.zeros((total, chunk), bool)
        offs = np.zeros((total,), np.float32)
        for i, (k, v) in enumerate(zip(keys_list, values_list)):
            m = len(k)
            s = starts[i]
            flat_k = np.zeros(counts[i] * chunk, np.uint32)
            flat_v = np.zeros(counts[i] * chunk, np.float32)
            flat_k[:m] = np.asarray(k, np.uint32)
            flat_v[:m] = np.asarray(v, np.float32)
            keys[s:s + counts[i]] = flat_k.reshape(counts[i], chunk)
            vals[s:s + counts[i]] = flat_v.reshape(counts[i], chunk)
            valid[s:s + counts[i]] = (np.arange(counts[i] * chunk) < m
                                      ).reshape(counts[i], chunk)
            offs[s:s + counts[i]] = np.arange(counts[i],
                                              dtype=np.float32) * chunk
        # fold round j (row j-1) merges chunk j into every query that still
        # has one; exhausted queries keep their fold result
        j = np.arange(1, counts.max())[:, None]
        has = counts[None, :] > j
        sel = np.where(has, starts[None, :-1] + j, 0).astype(np.int32)

    with TR.span("front.build"):
        return _sketch_and_fold(keys, vals, valid, offs,
                                starts[:-1].astype(np.int32), sel, has,
                                n=n, agg=agg)


@functools.partial(jax.jit, static_argnames=("n", "agg"))
def _sketch_and_fold(keys, vals, valid, offs, first, sel, has, *, n, agg):
    """All ``[T, chunk]`` row blocks sketched, then one fold round per row
    of ``sel``/``has`` (``[R, NQ]``: each query's next block, and whether it
    has one) over the ``[NQ]`` sketches that start at blocks ``first``."""
    _count("programs")                    # runs once per trace
    parts = jax.vmap(lambda k, v, ok, off: build_sketch(
        k, v, n=n, agg=agg, valid=ok, order_offset=off))(
            keys, vals, valid, offs)

    def fold(out, rnd):
        sel_j, has_j = rnd
        merged = jax.vmap(merge)(out, jax.tree.map(lambda a: a[sel_j], parts))
        return jax.tree.map(
            lambda m_, o: jnp.where(
                has_j.reshape(has_j.shape + (1,) * (o.ndim - 1)), m_, o),
            merged, out), None

    out, _ = jax.lax.scan(fold, jax.tree.map(lambda a: a[first], parts),
                          (sel, has))
    return out


_front_lock = threading.Lock()
_front = {"calls": 0, "programs": 0}


def _count(name: str) -> None:
    with _front_lock:
        _front[name] += 1


def front_stats() -> Dict[str, int]:
    """Front-end counters since the process started: ``calls`` of
    `build_query_sketches` and ``programs``, the traces (compiles) of its
    program. A warmed server keeps ``programs`` flat while ``calls`` grows."""
    with _front_lock:
        return dict(_front)


class CompileCache:
    """Shared program cache for the serving layers (DESIGN.md §4/§6).

    Maps a hashable program key → built (jitted) callable, counting misses:
    every miss is a program construction, i.e. an XLA compile at first
    dispatch, so ``misses`` is the serving layer's compile counter — the
    lifecycle and plan tests assert it stays flat across index mutations
    *and* across per-request semantic sweeps. One cache can back many
    segment executors (and many `Server`s), so segments with equal shapes
    share programs.

    Thread-safe: ``get`` holds a lock across the lookup *and* the build, so
    two concurrent callers racing on a cold key build exactly one program
    and ``misses`` stays an exact compile count (the naive check-then-act
    would double-build and overcount, making the zero-compile CI gates
    flaky under the async scheduler's worker pool). Builds are program
    *construction* (`jax.jit` wrapping — cheap); the XLA compile itself
    happens lazily at first dispatch, outside the lock.
    """

    def __init__(self):
        self._programs: Dict[tuple, object] = {}
        self._lock = threading.RLock()
        self.misses = 0

    def get(self, key: tuple, build):
        """Look up ``key``, building (and counting a miss) on first use."""
        with self._lock:
            fn = self._programs.get(key)
            if fn is None:
                self.misses += 1
                fn = build()
                self._programs[key] = fn
        return fn

    def __len__(self) -> int:
        return len(self._programs)

    def __contains__(self, key) -> bool:
        return key in self._programs


@functools.lru_cache(maxsize=1024)
def _plan_cover(nq: int, buckets: tuple, costs: tuple) -> tuple:
    """Min-cost cover of ``nq`` queries by bucket dispatches: exact DP over
    per-dispatch ``costs`` (a tuple of (bucket, seconds) pairs). Parent
    pointers + one backtrack keep it O(nq·buckets) time, O(nq) memory."""
    cost = dict(costs)
    best = [0.0] * (nq + 1)
    take = [0] * (nq + 1)
    for q in range(1, nq + 1):
        best[q], take[q] = min((best[max(0, q - b)] + cost[b], b)
                               for b in buckets)
    plan = []
    q = nq
    while q > 0:
        plan.append(take[q])
        q = max(0, q - take[q])
    return tuple(sorted(plan))   # dispatch order is cost-irrelevant; be stable


@dataclasses.dataclass(frozen=True)
class JoinabilityResult:
    """Top-k joinability search results (host numpy, all ``[NQ, k]``).

    ``ids`` index the server's column catalog (−1 for empty tail slots when
    fewer than k candidates have any key overlap); ``score`` is the ranking
    metric requested from `search_joinable`; the remaining fields are the
    per-result `repro.core.containment.JoinabilityEstimates` statistics —
    ``hits`` is the exact sketch-intersection size, ``containment`` carries
    its §2.1 Hoeffding CI ``[ci_lo, ci_hi]``.
    """
    ids: np.ndarray          # i32 [NQ, k]
    score: np.ndarray        # f32 [NQ, k] — the requested ranking metric
    hits: np.ndarray         # f32 [NQ, k]
    containment: np.ndarray  # f32 [NQ, k]
    ci_lo: np.ndarray        # f32 [NQ, k]
    ci_hi: np.ndarray        # f32 [NQ, k]
    jaccard: np.ndarray      # f32 [NQ, k]
    join_size: np.ndarray    # f32 [NQ, k]

    _FIELDS = ("ids", "score", "hits", "containment", "ci_lo", "ci_hi",
               "jaccard", "join_size")


#: metrics `search_joinable` can rank by (fields of JoinabilityEstimates)
JOIN_METRICS = ("containment", "jaccard", "join_size", "hits")

#: Process-wide launch lock for multi-partition (sharded) programs.
#: Concurrent launches of SPMD executables from different host threads can
#: interleave their per-device collective rendezvous — each program holds
#: some device queues while its collectives wait on the rest — and
#: deadlock. One host feeds one mesh, so launches are serialized; serving
#: throughput comes from coalescing into wider buckets, not from
#: concurrent program launches (DESIGN.md §10).
_MESH_DISPATCH_LOCK = threading.RLock()

#: per-stage telemetry vocabulary (DESIGN.md §11): each stage is counted
#: and is the span ``serve.<stage>``. Device-launch stages: "stage1" (probe
#: / source hit counts), "stage2" (pruned scoring), "scan" (full scan —
#: direct or fallback), "topm" (fused top-M plan), "fused" (the
#: single-dispatch inverted safe plan). Host stages: "select" (host
#: survivor selection + rung choice), "combine" (`combine_local_topk`).
_DEVICE_STAGES = ("stage1", "stage2", "scan", "topm", "fused")
_STAGE_NAMES = _DEVICE_STAGES + ("select", "combine")
_STAGE_SPANS = {name: f"serve.{name}" for name in _STAGE_NAMES}


def _add_counts(into: Dict[str, int], counts: Dict[str, int]) -> None:
    """Add ``counts`` into ``into`` key by key."""
    for name, v in counts.items():
        into[name] = into.get(name, 0) + v


def _add_pairs(into: Dict[str, List[int]], pairs) -> None:
    """Add ``[live, total]`` pairs into ``into`` key by key."""
    for name, (live, total) in pairs.items():
        acc = into.setdefault(name, [0, 0])
        acc[0] += live
        acc[1] += total


class _SegmentExec:
    """Plan executor for one resident (shard, `ShapePolicy`) pair — the
    engine room behind `Server` (one per segment on a live index, exactly
    one for a static index).

    Owns the bucketed dispatch loop: program lookup in the shared
    `CompileCache` (keys carry only compile-relevant shape — see `_key`),
    the per-bucket `PreppedShard`s, measured-cost bucket planning, the
    two-stage dispatch plumbing and per-dispatch telemetry. Request
    semantics arrive per call as a `repro.engine.plans.Request`.
    """

    def __init__(self, mesh, shard: IndexShard, shape: PL.ShapePolicy,
                 buckets: Sequence[int] = (1, 8, 32), prep=None,
                 index: Optional[SketchIndex] = None,
                 batch_rows: Optional[int] = None,
                 cache: Optional[CompileCache] = None, postings=None):
        self.mesh = mesh
        self.shard = shard
        #: host `Postings` for the inverted candidate source — passed in by
        #: the live-index refresh (incrementally maintained per segment) or
        #: built lazily from a host view of the shard on first use
        self._postings_host = postings
        self._sources: Dict[str, object] = {}
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        assert self.buckets and all(b > 0 for b in self.buckets)
        self.batch_rows = int(batch_rows or 8 * shape.score_chunk)
        self.C = shard.num_columns
        self.n = shard.sketch_size
        # pin the context-dependent shape fields (shard count, rank combine,
        # and the candidates='auto' resolution against this segment's
        # device-padded column count) so the concrete values participate in
        # every compile-cache key — executors on different-size meshes never
        # share programs, and every segment picks its own candidate source
        shape = PL.resolve_shape(shape, mesh, num_columns=self.C)
        # clamp the static rank width to the candidate count: a segment
        # smaller than k_max still serves (the facade pads rows back out)
        if shape.k_max > self.C:
            shape = dataclasses.replace(shape, k_max=self.C)
        self.shape = shape
        self.k_max = shape.k_max
        #: host-side cross-shard rank combine (DESIGN.md §10): plans emit
        #: per-device local top-ks and every dispatch finishes with
        #: `plans.combine_local_topk`
        self._host_combine = shape.combine == "host"
        #: sharded dispatches serialize through `_MESH_DISPATCH_LOCK`
        self._serialize = shape.mesh_shards > 1
        self.index = index
        self.cache = cache if cache is not None else CompileCache()
        #: PreppedShards keyed by effective score_chunk; a legacy ``prep``
        #: argument seeds the base-chunk entry
        self._preps: Dict[int, object] = {}
        if prep is not None:
            self._preps[shape.score_chunk] = prep
        # only the XLA sortmerge intersect consumes the precomputed sort
        # structure; don't build/ship two index-sized arrays otherwise
        self._use_prep = PL.uses_sortmerge(shape)
        #: per-candidate KMV key-minima layout (joinability estimates) and
        #: the index-constant D̂_C estimates derived from it; computed
        #: lazily from a host view of the shard
        self._minima: Optional[KeyMinima] = None
        self._minima_dc: Optional[np.ndarray] = None
        #: measured seconds per dispatch for each bucket (filled by warmup)
        self._bucket_cost: Dict[int, float] = {}
        #: guards the lazily built shared resources (_preps, _sources,
        #: _minima) — concurrent dispatches must not double-build a prep or
        #: a postings layout
        self._res_lock = threading.RLock()
        #: per-dispatch telemetry: (bucket B, real queries, seconds) — a
        #: bounded window so a long-lived server doesn't leak; totals for
        #: qps are kept separately and never reset. Guarded by ``_tel_lock``:
        #: racy ``+=`` under concurrent callers silently loses updates.
        self._tel_lock = threading.Lock()
        self.dispatch_log: Deque[Tuple[int, int, float]] = deque(maxlen=4096)
        self._total_queries = 0
        self._total_dispatches = 0
        self._total_s = 0.0
        #: per-stage invocation counts keyed by stage name ("stage1",
        #: "select", "stage2", "scan", "topm", "fused", "combine"; DESIGN.md
        #: §11), under `_tel_lock` like the dispatch log. Their times are
        #: the spans of the same names (`repro.engine.trace`)
        self._stage_n: Dict[str, int] = {}
        #: candidate rows handed to stage-2 scoring, by estimator, under
        #: `_tel_lock`: bucket width × the rung M (pruned and fused plans),
        #: × ``prune_m`` per device (fused topm) or × the padded C (scan).
        #: The rank estimators' work scales with it; their time is the
        #: device trace's, since they run inside the fused programs
        self._scored_n: Dict[str, int] = {}
        #: ``{estimator: [live, total]}`` pair tiles of the Pallas
        #: `rank_moments` (its occupancy skip computes only the live ones,
        #: DESIGN.md §8), from each plan's trailing output, under
        #: `_tel_lock`; nothing for pearson, qn or the XLA kernels
        self._tiles_n: Dict[str, List[int]] = {}
        #: fused inverted-safe dispatch (DESIGN.md §11): last sufficient
        #: survivor rung (adapted per dispatch, guarded by ``_res_lock``)
        #: and the toggle back to the legacy two-dispatch host-selected
        #: path (benchmarks/tests flip it to expose the comparison oracle)
        self._fused_rung: Optional[int] = None
        self.fused_safe = True

    # -- shape policy per bucket ---------------------------------------------
    def chunk_for(self, B: int) -> int:
        """Bucket-B score_chunk: shrunk toward the row budget (floored at 64
        rows, and never *raised* above the configured value — a user-lowered
        score_chunk is a memory bound and stays binding)."""
        return min(self.shape.score_chunk, max(64, self.batch_rows // B))

    def shape_for(self, B: int) -> PL.ShapePolicy:
        chunk = self.chunk_for(B)
        if chunk == self.shape.score_chunk:
            return self.shape
        return dataclasses.replace(self.shape, score_chunk=chunk)

    def _key(self, kind: str, B: int, extra: tuple = ()) -> tuple:
        """Compile-cache key: plan kind + bucket + index shape + the
        compile-relevant shape policy — and **nothing request-shaped**."""
        sh = self.shape_for(B)
        return (kind, B, self.C, self.n, sh.score_chunk, sh.intersect,
                sh.kernels, sh.k_max, sh.mesh_shards,
                sh.combine) + tuple(extra)

    # -- compiled plans ------------------------------------------------------
    def prep(self, B: Optional[int] = None):
        """Device-resident candidate sort structure for bucket B's chunking
        (built once per (index, score_chunk) — a cache lookup when the index
        handle carries a persisted prep)."""
        if not self._use_prep:
            return None
        sh = self.shape_for(B) if B is not None else self.shape
        with self._res_lock:
            prep = self._preps.get(sh.score_chunk)
            if prep is None:
                if self.index is not None:
                    prep = precompute_prep(self.index, self.mesh, self.shard,
                                           sh)
                else:
                    fn = self.cache.get(
                        ("prep", self.C, self.n, sh.score_chunk,
                         sh.mesh_shards),
                        lambda: PL.make_prep_fn(self.mesh, self.C, self.n,
                                                sh))
                    prep = jax.block_until_ready(fn(self.shard))
                self._preps[sh.score_chunk] = prep
        return prep

    def _prep_args(self, B: Optional[int] = None):
        prep = self.prep(B)
        return (prep,) if prep is not None else ()

    def scan_fn(self, B: int):
        """The bucket-B full-scan plan (`plans.make_scan_fn`) — one compiled
        program for every scorer × estimator × α × floor × k ≤ k_max."""
        return self.cache.get(
            self._key("scan", B),
            lambda: PL.make_scan_fn(self.mesh, self.C, self.n,
                                    self.shape_for(B), batch=B,
                                    with_prep=self._use_prep))

    def probe_fn(self, B: int, emit_tables: bool = False):
        """Stage-1 containment-scan plan for bucket B (hits ``[B, C]``);
        with ``emit_tables`` it also returns the probe state the pruned
        plan reuses (only meaningful on the prep-backed sortmerge path)."""
        emit = emit_tables and self._use_prep
        return self.cache.get(
            self._key("probe", B, (emit,)),
            lambda: PL.make_probe_fn(self.mesh, self.C, self.n,
                                     self.shape_for(B), batch=B,
                                     with_prep=self._use_prep,
                                     emit_tables=emit))

    def prune_fn(self, B: int, M: int):
        """Pruned scoring plan for ladder rung M: survivors are gathered
        and scored on device against the resident shard + the stage-1 probe
        tables (`plans.make_pruned_fn`)."""
        return self.cache.get(
            self._key("prune", B, (M,)),
            lambda: PL.make_pruned_fn(self.mesh, self.C, self.n,
                                      self.shape_for(B), M, batch=B,
                                      with_prep=self._use_prep))

    def prune_plain_fn(self, B: int, M: int):
        """Table-free variant of `prune_fn` for candidate sources that do
        not emit scan probe state (the inverted source, DESIGN.md §7):
        `plans.make_pruned_fn(with_prep=False)` gathers the survivor
        sub-shard and scores it standalone. Identical plan when the scan
        path is table-free anyway (non-prep backends)."""
        if not self._use_prep:
            return self.prune_fn(B, M)
        return self.cache.get(
            self._key("prune", B, (M, "plain")),
            lambda: PL.make_pruned_fn(self.mesh, self.C, self.n,
                                      self.shape_for(B), M, batch=B,
                                      with_prep=False))

    def inverted_fused_fn(self, B: int, M: int, W: int):
        """Fused single-dispatch inverted ``safe`` plan for survivor rung
        ``M`` and postings window ``W`` (`plans.make_inverted_fn`): probe →
        select → gather → score → rank device-resident, returning the
        ranked output plus the exact survivor count (DESIGN.md §11). Keyed
        on (M, E, W) — all three ride fixed ladders, so mutation-driven
        segment turnover reuses warmed programs."""
        src = self.source("inverted")
        return self.cache.get(
            self._key("inv-fused", B, (M, src.E, W)),
            lambda: PL.make_inverted_fn(self.mesh, self.C, self.n,
                                        self.shape_for(B), M, src.E, W,
                                        batch=B))

    def source(self, kind: Optional[str] = None):
        """The stage-1 candidate source of this executor
        (`repro.engine.candidates`): the `ShapePolicy.candidates` choice by
        default, or an explicit ``kind`` override. Constructed lazily and
        cached; the inverted source builds its postings from a host view of
        the shard unless the live-index refresh supplied incrementally
        maintained ones."""
        kind = kind if kind is not None else self.shape.candidates
        with self._res_lock:
            src = self._sources.get(kind)
            if src is None:
                if kind == "scan":
                    src = CD.ScanSource(self)
                elif kind == "inverted":
                    if self._postings_host is None:
                        self._postings_host = build_postings(
                            np.asarray(self.shard.key_hash),
                            np.asarray(self.shard.mask))
                    src = CD.InvertedSource(self._postings_host, C=self.C,
                                            n=self.n, cache=self.cache,
                                            kernels=self.shape.kernels,
                                            mesh=self.mesh)
                else:
                    raise ValueError(
                        f"unknown candidate source {kind!r}: use one of "
                        f"{CD.CANDIDATE_SOURCES}")
                self._sources[kind] = src
        return src

    def topm_fn(self, B: int):
        """Fused single-dispatch ``prune='topm'`` plan (`plans.make_topm_fn`).
        Keyed on ``prune_m`` — the program's static survivor width."""
        return self.cache.get(
            self._key("topm", B, (self.shape.prune_m,)),
            lambda: PL.make_topm_fn(self.mesh, self.C, self.n,
                                    self.shape_for(B), batch=B,
                                    with_prep=self._use_prep))

    def prune_rungs(self) -> List[int]:
        """The fixed survivor-capacity ladder ``prune_base · 2^i``
        (device-aligned, strictly below the full index width). Rungs under
        ``k_max`` are skipped — `plans.prune_rung` targets
        ``max(survivors, k_max)``, so a dispatch can never pick one."""
        ndev = int(self.mesh.devices.size)
        rungs: List[int] = []
        r = max(int(self.shape.prune_base), 1)
        while True:
            ra = r + (-r) % ndev
            if ra >= self.C:
                break
            if r >= self.k_max and (not rungs or rungs[-1] != ra):
                rungs.append(ra)
            r *= 2
        return rungs

    def _dummy_queries(self, B: int):
        return (jnp.full((B, self.n), PAD_KEY, jnp.uint32),
                jnp.zeros((B, self.n), jnp.float32),
                jnp.zeros((B, self.n), jnp.float32),
                jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.float32))

    # -- warmup --------------------------------------------------------------
    def warmup(self, cost_reps: int = 2, modes: Sequence[str] = ("off",),
               joinability: bool = False, cost_mode: Optional[str] = None,
               request: Optional[PL.Request] = None):
        """Compile the plans of every requested prune ``mode`` for every
        bucket (zero-row dummy queries) and measure the ``cost_mode``
        plan's dispatch cost, so `plan_batches` can pick buckets from
        observed per-query cost instead of assuming bigger is cheaper.

        ``'off'`` warms the full scan; ``'safe'`` warms the scan (the
        fallback when the survivor set outgrows the ladder), the emit-tables
        probe and every (bucket, rung) pruned plan — the rung set is fixed a
        priori, so survivor-count changes at serve time never compile;
        ``'topm'`` warms the fused plan. Because request semantics are
        traced operands, warming a plan once covers **every** scorer ×
        estimator × k ≤ k_max × α (`CompileCache.misses` stays flat across
        request sweeps — the DESIGN.md §6 contract). Pass
        ``joinability=True`` to also pre-warm the bare `search_joinable`
        probe (``'safe'`` warms a reusable probe either way)."""
        modes = tuple(modes)
        if cost_mode is None:
            cost_mode = modes[0]
        rungs = self.prune_rungs() if "safe" in modes else []
        # cost dispatches run under the *serving* request's semantics (a
        # spearman server must not feed the bucket planner pearson timings
        # — their relative bucket costs differ); compiled programs are
        # request-independent either way
        ops = jnp.asarray(PL.request_operands(
            request if request is not None else PL.Request()))

        def _time(fn):
            ts = []
            for _ in range(max(cost_reps, 1)):
                t0 = time.perf_counter()
                fn()
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        inv = self.shape.candidates == "inverted"
        req0 = request if request is not None else PL.Request()
        for B in self.buckets:
            qa = self._dummy_queries(B)
            prep_args = self._prep_args(B)
            args = qa + (self.shard,) + prep_args
            scan = topm = None
            # the sourced safe/topm dispatches fall back to the full scan
            # when the survivor set outgrows the rung ladder, so an
            # inverted server warms it for those modes too
            if "off" in modes or "safe" in modes or (inv and "topm" in modes):
                scan = self.scan_fn(B)
                jax.block_until_ready(scan(*args, ops))
            if "topm" in modes and not inv:
                topm = self.topm_fn(B)
                jax.block_until_ready(topm(*args, ops))
            if joinability and "safe" not in modes and not inv:
                jax.block_until_ready(self.probe_fn(B)(*args))
            s1 = None
            if "safe" in modes and not inv:
                s1 = self.probe_fn(B, emit_tables=True)
                tabs = jax.block_until_ready(s1(*args))
                tab_args = tuple(tabs[1:]) if self._use_prep else ()
                for M in rungs:
                    idx = jnp.zeros((M,), jnp.int32)
                    ok = jnp.zeros((M,), bool)
                    jax.block_until_ready(self.prune_fn(B, M)(
                        *qa, self.shard, idx, ok, *tab_args, *prep_args,
                        ops))
            if inv and ("safe" in modes or "topm" in modes or joinability):
                # postings probe (current + next window rung) and the
                # table-free pruned plans the sourced dispatches feed
                src = self.source()
                src.warmup(B)
                for M in (self.prune_rungs()
                          if ("safe" in modes or "topm" in modes) else []):
                    idx = jnp.zeros((M,), jnp.int32)
                    ok = jnp.zeros((M,), bool)
                    jax.block_until_ready(self.prune_plain_fn(B, M)(
                        *qa, self.shard, idx, ok, ops))
                if "safe" in modes:
                    # fused device-resident plans (DESIGN.md §11): every
                    # survivor rung (adaptation/overflow retry can land on
                    # any of them) × the current and next window rungs
                    # (segment turnover under mutation can double W — same
                    # ahead-of-need discipline as the probe warmup)
                    for W in (src.W, src.W * 2):
                        for M in self.prune_rungs():
                            jax.block_until_ready(
                                self.inverted_fused_fn(B, M, W)(
                                    *qa, self.shard, src._keys_d,
                                    src._cols_d, ops))
            # measured per-dispatch cost of the default plan: that is what
            # a serve-time dispatch of this server actually costs
            if cost_mode == "topm" and topm is not None:
                self._bucket_cost[B] = _time(
                    lambda: jax.block_until_ready(topm(*args, ops)))
            elif cost_mode == "topm" and inv:
                self._bucket_cost[B] = _time(
                    lambda: self._dispatch_topm_sourced(
                        qa, B, B, prep_args, req0, ops))
            elif cost_mode == "safe" and rungs and inv:
                self._bucket_cost[B] = _time(
                    lambda: self._dispatch_safe(qa, B, B, prep_args, req0,
                                                ops))
            elif cost_mode == "safe" and rungs:
                M0 = rungs[0]
                idx0 = jnp.zeros((M0,), jnp.int32)
                ok0 = jnp.zeros((M0,), bool)
                s2 = self.prune_fn(B, M0)

                def _two_stage():
                    out1 = jax.block_until_ready(s1(*args))
                    np.asarray(out1[0] if self._use_prep else out1)
                    tab_args = tuple(out1[1:]) if self._use_prep else ()
                    jax.block_until_ready(
                        s2(*qa, self.shard, idx0, ok0, *tab_args,
                           *prep_args, ops))

                self._bucket_cost[B] = _time(_two_stage)
            elif scan is not None:
                self._bucket_cost[B] = _time(
                    lambda: jax.block_until_ready(scan(*args, ops)))

    # -- batching ------------------------------------------------------------
    def bucket_for(self, nq: int) -> int:
        """Smallest bucket covering ``nq`` queries (largest if none do)."""
        for b in self.buckets:
            if b >= nq:
                return b
        return self.buckets[-1]

    def plan_batches(self, nq: int) -> List[int]:
        """Cover ``nq`` queries with bucket dispatches of minimal measured
        cost (exact DP over the warmup timings). Before warmup — no costs
        yet — fall back to the legacy greedy max-bucket slicing."""
        if not self._bucket_cost or nq <= 0:
            bmax = self.buckets[-1]
            full, tail = divmod(nq, bmax)
            return [bmax] * full + ([self.bucket_for(tail)] if tail else [])
        costs = tuple(sorted(self._bucket_cost.items()))
        return list(_plan_cover(nq, self.buckets, costs))

    # -- dispatch ------------------------------------------------------------
    def _stage(self, name: str, req: Optional[PL.Request] = None,
               rows: int = 0):
        """Count one invocation of stage ``name`` — and the ``rows``
        candidate rows it hands to ``req``'s estimator — under
        ``_tel_lock`` (a racy ``+=`` under concurrent dispatches silently
        loses updates) and return its span, ``with self._stage("fused"):
        ...``."""
        with self._tel_lock:
            self._stage_n[name] = self._stage_n.get(name, 0) + 1
            if rows:
                est = req.estimator
                self._scored_n[est] = self._scored_n.get(est, 0) + rows
        return TR.span(_STAGE_SPANS[name])

    def _finish_ranked(self, out, req: PL.Request):
        """Block on a rank-stage output ``(s, g, r, m, tiles)``, count its
        rank-kernel pair tiles under ``req``'s estimator (the ``i32[2]``
        comes back after the same wait as the strips: no sync of its own)
        and, under the host combine, merge the concatenated per-device
        local top-ks ``[.., D·kk]`` into the global ``[.., k_max]``
        (`plans.combine_local_topk`) — the only cross-shard step of a
        host-combine dispatch."""
        with TR.span("serve.wait"):
            *out, tiles = jax.block_until_ready(out)
            live, total = (int(t) for t in np.asarray(tiles))
            if total:
                with self._tel_lock:
                    _add_pairs(self._tiles_n, {req.estimator: (live, total)})
            if not self._host_combine:
                return tuple(np.asarray(o) for o in out)
        with self._stage("combine"):
            return PL.combine_local_topk(*out, self.k_max)

    def _launch_lock(self):
        """`_MESH_DISPATCH_LOCK` on a sharded mesh, a no-op otherwise."""
        return (_MESH_DISPATCH_LOCK if self._serialize
                else contextlib.nullcontext())

    def _dispatch(self, qa, nq: int, req: PL.Request, ops,
                  B: Optional[int] = None):
        """Run one ≤bucket slice under ``req``'s semantics: pad to the
        bucket, dispatch the plan its prune mode selects, slice back.
        Telemetry counts a two-stage plan as one dispatch. Sharded
        dispatches hold the process-wide launch lock end to end."""
        with self._launch_lock():
            return self._dispatch_inner(qa, nq, req, ops, B)

    def _dispatch_inner(self, qa, nq: int, req: PL.Request, ops,
                        B: Optional[int] = None):
        B = self.bucket_for(nq) if B is None else B
        pad = B - nq
        if pad:
            qa = tuple(jnp.concatenate(
                [a, jnp.broadcast_to(a[nq - 1:nq], (pad,) + a.shape[1:])])
                for a in qa)
        prep_args = self._prep_args(B)
        t0 = time.perf_counter()
        if req.prune == "topm":
            if self.source().kind != "scan":
                out = self._dispatch_topm_sourced(qa, nq, B, prep_args, req,
                                                  ops)
            else:
                ndev = int(self.mesh.devices.size)
                rows = B * ndev * PL.topm_width(self.shape, self.C // ndev)
                with self._stage("topm", req, rows):
                    out = self.topm_fn(B)(*qa, self.shard, *prep_args, ops)
                    s, g, r, m = self._finish_ranked(out, req)
                g = np.where(np.isfinite(s), g, -1).astype(np.int32)
                out = (s, g, r, m)
        elif req.prune == "safe":
            out = self._dispatch_safe(qa, nq, B, prep_args, req, ops)
        else:
            with self._stage("scan", req, B * self.C):
                out = self.scan_fn(B)(*qa, self.shard, *prep_args, ops)
                out = self._finish_ranked(out, req)
        dt = time.perf_counter() - t0
        with self._tel_lock:
            self.dispatch_log.append((B, nq, dt))
            self._total_queries += nq
            self._total_dispatches += 1
            self._total_s += dt
        return tuple(o[:nq] for o in out)

    def _dispatch_safe(self, qa, nq: int, B: int, prep_args, req, ops):
        """One two-stage dispatch (DESIGN.md §5): stage-1 hit counts from
        the configured candidate source → host filter → ladder rung →
        gather-compacted stage-2 scoring; falls back to the (already
        compiled) full-scan plan when the survivor set would not fit a rung
        below the full index width. Either way, −inf rows get id −1.

        The scan source keeps the historical fused path verbatim: its
        emit-tables probe shares the binary-search/membership state with
        the pruned plan. A non-scan source dispatches the device-resident
        fused plan (`_dispatch_safe_fused` — one launch, no [B, C]
        materialisation, DESIGN.md §11); flipping ``fused_safe`` off
        exposes the legacy two-dispatch path (source hit counts → host
        select → table-free pruned plan) — same survivors (hit counts are
        exact and source-independent), scores equal to ulp-level
        reassociation."""
        if self.source().kind != "scan":
            if self.fused_safe:
                return self._dispatch_safe_fused(qa, nq, B, prep_args, req,
                                                 ops)
            with self._stage("stage1"):
                hits_np = self.source().hit_counts(qa, B)[:nq]
            return self._prune_and_score(qa, B, prep_args, req, ops,
                                         hits_np=hits_np, tab_args=None)
        with self._stage("stage1"):
            out1 = self.probe_fn(B, emit_tables=True)(*qa, self.shard,
                                                      *prep_args)
            with TR.span("serve.wait"):
                out1 = jax.block_until_ready(out1)
        hits, tab_args = ((out1[0], tuple(out1[1:])) if self._use_prep
                          else (out1, ()))
        # selection sees only the real rows: bucket-padding copies must not
        # inflate the survivor set
        hits_np = np.asarray(hits)[:nq]
        return self._prune_and_score(qa, B, prep_args, req, ops,
                                     hits_np=hits_np, tab_args=tab_args)

    def _dispatch_safe_fused(self, qa, nq: int, B: int, prep_args, req, ops):
        """Device-resident ``safe`` dispatch through the inverted source
        (DESIGN.md §11): ONE compiled launch chains postings probe → merge
        → survivor select → gather → score → rank (`plans.make_inverted_fn`)
        — no host [B, C] scatter, no mid-query sync, no O(C) tail.

        The survivor count is data-dependent but the dispatch shape is not:
        the plan reports the exact union size ``n_surv`` alongside the
        ranked output, and the executor adapts. It dispatches at the last
        sufficient rung (``_fused_rung``, seeded at the base rung); on
        overflow (``n_surv > M`` — the emitted survivors are then the M
        smallest ids, not a superset) it re-dispatches once at the exact
        covering rung — guaranteed sufficient, ``n_surv`` is M-independent
        — or falls back to the already-warmed full scan when the union
        outgrows the ladder, exactly like the host-selected path. The rung
        path is a deterministic function of the query history, so replayed
        sequences (the D1-vs-D8 test tier) take identical dispatches.

        Bucket-padding rows are broadcast copies of the last real row, so
        they duplicate its eligible ids and leave the survivor union — and
        ``n_surv`` — unchanged."""
        rungs = self.prune_rungs()
        if not rungs:
            # no rung beats the full scan — the host-selected path would
            # fall back for every survivor count; dispatch the scan direct
            with self._stage("scan", req, B * self.C):
                out = self.scan_fn(B)(*qa, self.shard, *prep_args, ops)
                s, g, r, m = self._finish_ranked(out, req)
            g = np.where(np.isfinite(s), g, -1).astype(np.int32)
            return s, g, r, m
        src = self.source()
        with self._res_lock:
            M = self._fused_rung if self._fused_rung in rungs else rungs[0]
        ndev = int(self.mesh.devices.size)
        for _ in range(2):
            with self._stage("fused", req, B * M):
                out = self.inverted_fused_fn(B, M, src.W)(
                    *qa, self.shard, src._keys_d, src._cols_d, ops)
                s, g, r, m = self._finish_ranked(out[:5], req)
                with TR.span("serve.wait"):
                    n = int(np.asarray(out[5]))  # replicated union size
            need = PL.prune_rung(max(n, self.k_max), self.shape.prune_base,
                                 self.C, ndev)
            if n <= M:
                with self._res_lock:
                    self._fused_rung = need if need is not None else M
                g = np.where(np.isfinite(s), g, -1).astype(np.int32)
                return s, g, r, m
            if need is None:
                break               # union outgrew the ladder → full scan
            with self._res_lock:
                self._fused_rung = M = need
        with self._stage("scan", req, B * self.C):
            out = self.scan_fn(B)(*qa, self.shard, *prep_args, ops)
            s, g, r, m = self._finish_ranked(out, req)
        g = np.where(np.isfinite(s), g, -1).astype(np.int32)
        return s, g, r, m

    def _prune_and_score(self, qa, B: int, prep_args, req, ops, *,
                         hits_np, tab_args):
        """Shared stage-2 tail of the safe dispatch: survivor selection,
        rung choice, pruned (or fallback full-scan) scoring.
        ``tab_args=None`` selects the table-free pruned plan."""
        with self._stage("select"):
            surv = PL.select_survivors(hits_np, prune="safe",
                                       min_sample=req.min_sample)
            ndev = int(self.mesh.devices.size)
            rung = PL.prune_rung(max(len(surv), self.k_max),
                                 self.shape.prune_base, self.C, ndev)
        if rung is None:
            with self._stage("scan", req, B * self.C):
                out = self.scan_fn(B)(*qa, self.shard, *prep_args, ops)
                s, g, r, m = self._finish_ranked(out, req)
            # same id convention as the pruned dispatch below: −inf → −1
            g = np.where(np.isfinite(s), g, -1).astype(np.int32)
            return s, g, r, m
        idx = np.zeros((rung,), np.int32)
        idx[:len(surv)] = surv
        valid = np.arange(rung) < len(surv)
        with self._stage("stage2", req, B * rung):
            if tab_args is None:
                out = self.prune_plain_fn(B, rung)(*qa, self.shard,
                                                   jnp.asarray(idx),
                                                   jnp.asarray(valid), ops)
            else:
                out = self.prune_fn(B, rung)(*qa, self.shard,
                                             jnp.asarray(idx),
                                             jnp.asarray(valid), *tab_args,
                                             *prep_args, ops)
            s, g, r, m = self._finish_ranked(out, req)
        # stage-2 gids are already index-space; −inf rows (pruned / empty)
        # get id −1 so they can never alias a real column
        g = np.where(np.isfinite(s), g, -1).astype(np.int32)
        return s, g, r, m

    def _dispatch_topm_sourced(self, qa, nq: int, B: int, prep_args, req,
                               ops):
        """``prune='topm'`` through a non-scan candidate source: per-row
        top-M survivor selection on the source's hit counts (host), then
        the table-free pruned plan — the fused single-dispatch plan is a
        full scan by construction, which is exactly what the inverted
        source exists to avoid. Falls back to the full scan when the
        survivor union outgrows the rung ladder. (The fused device-resident
        select is safe-only: its union semantics cannot express per-row
        top-M truncation, so ``topm`` keeps the two-stage shape.)"""
        with self._stage("stage1"):
            hits_np = self.source().hit_counts(qa, B)[:nq]
        with self._stage("select"):
            surv = PL.select_survivors(hits_np, prune="topm",
                                       min_sample=req.min_sample,
                                       prune_m=self.shape.prune_m)
            ndev = int(self.mesh.devices.size)
            rung = PL.prune_rung(max(len(surv), self.k_max),
                                 self.shape.prune_base, self.C, ndev)
        if rung is None:
            with self._stage("scan", req, B * self.C):
                out = self.scan_fn(B)(*qa, self.shard, *prep_args, ops)
                s, g, r, m = self._finish_ranked(out, req)
            g = np.where(np.isfinite(s), g, -1).astype(np.int32)
            return s, g, r, m
        idx = np.zeros((rung,), np.int32)
        idx[:len(surv)] = surv
        valid = np.arange(rung) < len(surv)
        with self._stage("stage2", req, B * rung):
            out = self.prune_plain_fn(B, rung)(*qa, self.shard,
                                               jnp.asarray(idx),
                                               jnp.asarray(valid), ops)
            s, g, r, m = self._finish_ranked(out, req)
        g = np.where(np.isfinite(s), g, -1).astype(np.int32)
        return s, g, r, m

    def query_batch(self, sketches: CorrelationSketch, req: PL.Request):
        """Serve a batch of query sketches (leading [NQ] axis) under one
        request's semantics → ``[NQ, min(req.k, k_max)]`` results.

        The batch is covered by the bucket plan of `plan_batches` (measured
        per-dispatch costs after `warmup()`; greedy max-bucket before). Only
        the real queries' rows are returned, in request order.
        """
        qa = query_arrays(sketches)
        nq = int(qa[0].shape[0])
        k_ret = min(int(req.k), self.k_max)
        if nq == 0:
            empty = lambda dt: jnp.zeros((0, k_ret), dt)
            return (empty(jnp.float32), empty(jnp.int32),
                    empty(jnp.float32), empty(jnp.float32))
        ops = jnp.asarray(PL.request_operands(req))
        outs = []
        s = 0
        for B in self.plan_batches(nq):
            e = min(s + B, nq)
            outs.append(self._dispatch(tuple(a[s:e] for a in qa), e - s,
                                       req, ops, B=B))
            s = e
        out = tuple(jnp.concatenate(parts) for parts in zip(*outs))
        if k_ret < self.k_max:   # request k is a host-side slice (§6)
            out = tuple(o[:, :k_ret] for o in out)
        return out

    # -- joinability (stage 1 as a first-class workload) ---------------------
    def key_minima(self) -> KeyMinima:
        """Lazily computed per-candidate KMV key-minima layout of the
        resident shard (`repro.engine.index.key_minima`), plus the
        index-constant D̂_C estimates (cached — not recomputed per query)."""
        with self._res_lock:
            if self._minima is None:
                self._minima = key_minima(self.shard)
                self._minima_dc = CT.distinct_from_minima(
                    self._minima.count, self._minima.tau, self.n)
            return self._minima

    def stage1_hits(self, sketches: CorrelationSketch) -> np.ndarray:
        """Exact per-candidate sketch-intersection sizes ``[NQ, C]`` for a
        batch of query sketches — the configured candidate source
        (`ShapePolicy.candidates`), bucketed like `query_batch` but with no
        scoring stage. The scan source reuses an already-warmed emit-tables
        probe (its extra outputs are dropped) instead of compiling a lean
        twin; the inverted source dispatches its postings probe."""
        qa = query_arrays(sketches)
        nq = int(qa[0].shape[0])
        if nq == 0:
            return np.zeros((0, self.C), np.float32)
        rows = []
        s = 0
        while s < nq:
            B = self.bucket_for(min(nq - s, self.buckets[-1]))
            e = min(s + B, nq)
            part = tuple(a[s:e] for a in qa)
            if e - s < B:
                part = tuple(jnp.concatenate(
                    [a, jnp.broadcast_to(a[-1:], (B - (e - s),) + a.shape[1:])])
                    for a in part)
            with self._launch_lock(), self._stage("stage1"):
                hc = self.source().hit_counts(part, B)
            rows.append(hc[:e - s])
            s = e
        return np.concatenate(rows, axis=0)

    def search_joinable_sketches(self, sketches: CorrelationSketch, *,
                                 k: int, metric: str = "containment",
                                 alpha: float = 0.05) -> JoinabilityResult:
        """Top-k *joinability* search over pre-built query sketches.

        The pure stage-1 workload (paper §2/Defn. 3 first clause: "tables
        joinable with T_Q on K_Q"): per-candidate hit counts from the
        containment scan, turned into `repro.core.containment` estimates
        with §2.1 Hoeffding CIs, ranked by ``metric`` (one of
        ``JOIN_METRICS``; ties → lower column id). Candidates with zero key
        overlap never appear; short rows pad with id −1.
        """
        if metric not in JOIN_METRICS:
            raise ValueError(f"unknown joinability metric {metric!r}: "
                             f"use one of {JOIN_METRICS}")
        k = int(k)
        hits = self.stage1_hits(sketches)
        nq = hits.shape[0]
        minima = self.key_minima()
        q_kh = np.asarray(sketches.key_hash)
        q_mask = np.asarray(sketches.mask)
        out = {f: np.zeros((nq, k), np.float32)
               for f in JoinabilityResult._FIELDS}
        out["ids"] = np.full((nq, k), -1, np.int32)
        for i in range(nq):
            est = CT.joinability_estimates(
                hits[i], CT.query_minima(q_kh[i], q_mask[i]),
                minima.count, minima.tau, self.n,
                cand_distinct=self._minima_dc, alpha=alpha)
            score = np.asarray(getattr(est, metric), np.float32)
            ok = est.hits > 0
            order = np.lexsort((np.arange(score.shape[0]),
                                np.where(ok, -score, np.inf)))[:k]
            order = order[ok[order]]
            kk = order.shape[0]
            out["ids"][i, :kk] = order
            out["score"][i, :kk] = score[order]
            for f in ("hits", "containment", "ci_lo", "ci_hi", "jaccard",
                      "join_size"):
                out[f][i, :kk] = np.asarray(getattr(est, f), np.float32)[order]
        return JoinabilityResult(**out)

    # -- telemetry -----------------------------------------------------------
    def stage_stats(self) -> Dict[str, int]:
        """Consistent copy of the per-stage counts ``{stage: count}``."""
        with self._tel_lock:
            return dict(self._stage_n)

    def scored_rows(self) -> Dict[str, int]:
        """Consistent copy of the scored candidate rows ``{estimator:
        rows}``."""
        with self._tel_lock:
            return dict(self._scored_n)

    def rank_tiles(self) -> Dict[str, List[int]]:
        """Consistent copy of the rank kernel's pair tiles ``{estimator:
        [live, total]}``."""
        with self._tel_lock:
            return {est: list(v) for est, v in self._tiles_n.items()}

    def throughput(self) -> dict:
        """Latency/throughput numbers: lifetime totals for queries/qps,
        percentiles over the bounded recent-dispatch window, and the
        per-stage counts (``stages[name] = {count}`` over `_STAGE_NAMES`;
        ``device_dispatches`` sums the device-launch stages — the counter
        the single-dispatch CI gate reads; ``scored_rows`` the candidate
        rows handed to stage-2 scoring, by estimator; ``rank_tiles`` the
        `rank_moments` pair tiles computed and covered, by estimator). The
        totals and the log window are read under the telemetry lock, so
        concurrent dispatches can't tear the percentiles."""
        with self._tel_lock:
            queries = self._total_queries
            dispatches = self._total_dispatches
            total_s = self._total_s
            log = list(self.dispatch_log)
            stage_n = dict(self._stage_n)
            scored = dict(self._scored_n)
            tiles = {est: list(v) for est, v in self._tiles_n.items()}
        stages = {name: dict(count=n) for name, n in sorted(stage_n.items())}
        devd = sum(stage_n.get(name, 0) for name in _DEVICE_STAGES)
        if not queries:
            return dict(queries=0, dispatches=0, total_s=0.0, qps=0.0,
                        dispatch_p50_ms=0.0, dispatch_p90_ms=0.0,
                        dispatch_p99_ms=0.0, per_query_ms=0.0,
                        stages=stages, device_dispatches=devd,
                        scored_rows=scored, rank_tiles=tiles)
        lat_ms = np.array([t * 1e3 for _, _, t in log])
        return dict(
            queries=queries, dispatches=dispatches,
            total_s=total_s,
            qps=queries / max(total_s, 1e-12),
            dispatch_p50_ms=float(np.percentile(lat_ms, 50)),
            dispatch_p90_ms=float(np.percentile(lat_ms, 90)),
            dispatch_p99_ms=float(np.percentile(lat_ms, 99)),
            per_query_ms=1e3 * total_s / max(queries, 1),
            stages=stages, device_dispatches=devd, scored_rows=scored,
            rank_tiles=tiles)


@dataclasses.dataclass(frozen=True)
class _SegEntry:
    """One segment of a published segment-map snapshot. Frozen: `refresh()`
    never mutates a live entry in place (a concurrent dispatch may be
    reading it) — a segment whose global-id ``base`` moved is republished
    as a *new* entry sharing the old executor."""
    sid: int
    version: int
    base: int            # global-id offset (cumulative used slots)
    used: int
    capacity: int        # device-padded column count (the compile-key shape)
    exec: _SegmentExec


def _is_live(source) -> bool:
    from repro.engine import lifecycle as LC
    return isinstance(source, LC.LiveIndex)


class Server:
    """The unified serving facade (DESIGN.md §6): one class, every index
    flavour, per-request query semantics.

    ``source`` may be a `repro.engine.lifecycle.LiveIndex` (served across
    its segments with `refresh()` picking up mutations), a
    `repro.engine.index.SketchIndex` (placed on the mesh and served as a
    single-segment live index) or an already-placed `IndexShard`.

    ``policy`` is the compile-relevant `repro.engine.plans.ShapePolicy`
    (or a legacy `QueryConfig`, which is split via `plans.split_config`);
    ``request`` is the *default* `plans.Request` — every serving method
    accepts a per-call ``request=`` override, and because request semantics
    are traced operands / host-side slices, heterogeneous requests share
    the warmed programs: after `warmup()` a sweep over every scorer ×
    estimator × k ≤ k_max × prune mode compiles nothing.

    Results combine across segments deterministically (score desc, global
    id asc; −inf rows get id −1) into ``[NQ, request.k]`` numpy arrays with
    ids indexing `self.names`.
    """

    def __init__(self, mesh, source, policy=None, *,
                 request: Optional[PL.Request] = None,
                 buckets: Sequence[int] = (1, 8, 32),
                 batch_rows: Optional[int] = None,
                 cache: Optional[CompileCache] = None,
                 index: Optional[SketchIndex] = None, prep=None):
        self.mesh = mesh
        if isinstance(policy, Q.QueryConfig):
            shape, req0 = PL.split_config(policy)
            request = request if request is not None else req0
        elif policy is None:
            shape = PL.ShapePolicy()
        else:
            shape = policy
        # resolve the mesh-dependent fields up front: `self.shape` then
        # reports the concrete shard count / rank combine the segment
        # executors will serve with (DESIGN.md §10)
        shape = PL.resolve_shape(shape, mesh)
        self.shape = shape
        self.request = request if request is not None else PL.Request()
        if self.request.prune not in PL.PRUNE_MODES:  # constructor-time, as
            raise ValueError(                        # the old servers did
                f"unknown prune mode {self.request.prune!r}: "
                f"use one of {PL.PRUNE_MODES}")
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self._batch_rows = batch_rows
        self.cache = cache if cache is not None else CompileCache()
        self._entries: Dict[int, _SegEntry] = {}
        self._order: List[int] = []
        self.names: List[str] = []
        #: the published segment-map snapshot — an immutable tuple of frozen
        #: `_SegEntry`s in dispatch order. Dispatch paths read it **once**
        #: per call and never touch `_entries`/`_order` directly, so a
        #: concurrent `refresh()` (which builds a full replacement and swaps
        #: the reference) can never tear a scan mid-iteration, and every
        #: result is consistent with exactly one index version (global-id
        #: bases included).
        self._view: Tuple[_SegEntry, ...] = ()
        self._seen_version = -1
        #: serialises refresh() (snapshot + republish); dispatches never
        #: take it — they read the already-published view
        self._refresh_lock = threading.RLock()
        #: guards the logical request counters below
        self._stats_lock = threading.Lock()
        #: the attached `repro.engine.scheduler.AsyncScheduler` (if any) —
        #: its queue-depth / deadline-miss counters join `throughput()`
        self._scheduler = None
        #: measured bucket costs survive segment turnover per capacity class
        self._cap_costs: Dict[int, Dict[int, float]] = {}
        #: logical request telemetry (a query counts once, however many
        #: segments it fans out to) + dispatches of retired segment execs
        self._q_total = 0
        self._q_seconds = 0.0
        self._retired = dict(dispatches=0)
        #: per-stage counts, scored rows and rank tiles of retired segment
        #: executors (folded in by `refresh()` so `throughput()` stays
        #: lifetime-accurate)
        self._retired_stage_n: Dict[str, int] = {}
        self._retired_scored: Dict[str, int] = {}
        self._retired_tiles: Dict[str, List[int]] = {}

        if _is_live(source):
            self._live = source
            self.n = source.n
            self.refresh()
        else:
            self._live = None
            if isinstance(source, SketchIndex):
                index = index if index is not None else source
                shard = shard_for_mesh(source, mesh)
            else:
                shard = source      # an IndexShard the caller already placed
            self.n = shard.sketch_size
            ex = _SegmentExec(mesh, shard, shape, buckets=self.buckets,
                              prep=prep, index=index, batch_rows=batch_rows,
                              cache=self.cache)
            used = len(index.names) if index is not None else ex.C
            self._entries[0] = _SegEntry(sid=0, version=0, base=0, used=used,
                                         capacity=ex.C, exec=ex)
            self._order = [0]
            self.names = list(index.names) if index is not None else []
            self._view = (self._entries[0],)

    # -- segment sync --------------------------------------------------------
    def _seg_candidates(self, capacity: int) -> str:
        """Resolve the candidate source a segment of host ``capacity``
        columns will serve with — against the *device-padded* count, so it
        matches the resolution its `_SegmentExec` performs on construction
        (``candidates='auto'`` picks per segment, DESIGN.md §7/§11)."""
        ndev = int(self.mesh.devices.size)
        return PL.resolve_candidates(self.shape.candidates,
                                     capacity + (-capacity) % ndev)

    @property
    def _exec(self) -> _SegmentExec:
        """The single static executor (static sources only)."""
        assert self._live is None and len(self._order) == 1
        return self._entries[self._order[0]].exec

    def _make_entry(self, sid: int, version: int, base: int, used: int,
                    host_shard, postings=None) -> _SegEntry:
        shard = place_shard(host_shard, self.mesh)
        ex = _SegmentExec(self.mesh, shard, self.shape, buckets=self.buckets,
                          batch_rows=self._batch_rows, cache=self.cache,
                          postings=postings)
        ex._bucket_cost = dict(self._cap_costs.get(ex.C, {}))
        return _SegEntry(sid=sid, version=version, base=base,
                         used=used, capacity=ex.C, exec=ex)

    def refresh(self) -> None:
        """Sync with a live index: device-place new/changed segments, drop
        removed ones, rebuild the global-id catalog. A no-op for static
        sources, and free when nothing moved (lock-free version fast-path —
        in particular, queries don't stall on the index lock while a
        compaction is folding). The index lock is held only to snapshot
        consistent host-side views of the changed segments (a concurrent
        append could otherwise produce a torn read); device placement and
        executor construction happen after it is released, so writers are
        never blocked on device transfers.

        Concurrency: refreshes serialise on ``_refresh_lock``; dispatches
        never take it. The replacement segment map is built on the side —
        retained entries whose global-id ``base`` moved are *republished*
        (frozen entries sharing the old executor), never mutated — and the
        new `_view` tuple is swapped in as one reference assignment, so a
        concurrent `query_batch` sees either the old snapshot or the new
        one, complete with matching bases, and never a mixture."""
        if self._live is None or self._live.version == self._seen_version:
            return
        with TR.span("serve.refresh"), self._refresh_lock:
            if self._live.version == self._seen_version:
                return  # another thread refreshed while we waited
            with self._live._lock:
                ver = self._live.version
                snaps = []
                for seg in self._live._segs:
                    old = self._entries.get(seg.sid)
                    fresh = old is None or old.version != seg.version
                    # candidate source per segment: 'auto' resolves by the
                    # segment's own (device-padded) capacity, exactly as
                    # its executor will on construction
                    inv = self._seg_candidates(seg.capacity) == "inverted"
                    if fresh and inv:
                        # materialise the segment's postings under the lock
                        # so the snapshot carries the incrementally
                        # maintained layout (write/tombstone keep it in
                        # sync from then on)
                        seg.postings()
                    snaps.append((seg.sid, seg.version, seg.used,
                                  list(seg.names[:seg.used]),
                                  seg.host_snapshot() if fresh else None,
                                  inv))
            entries: Dict[int, _SegEntry] = {}
            order: List[int] = []
            names: List[str] = []
            base = 0
            for sid, version, used, seg_names, snap, inv in snaps:
                if snap is None:
                    old = self._entries[sid]
                    entries[sid] = (old if old.base == base else
                                    dataclasses.replace(old, base=base))
                else:
                    entries[sid] = self._make_entry(
                        sid, version, base, used, snap.to_index_shard(),
                        postings=snap.postings() if inv else None)
                order.append(sid)
                names.extend(seg_names)
                base += used
            # retired-executor accounting: an exec is retired when no new
            # entry references it (entry identity can change on a pure
            # base shift while the exec — and its telemetry — lives on)
            kept = {id(e.exec) for e in entries.values()}
            gone = [old.exec for old in self._entries.values()
                    if id(old.exec) not in kept]
            with self._stats_lock:
                self._retired["dispatches"] += sum(
                    ex._total_dispatches for ex in gone)
                for ex in gone:
                    _add_counts(self._retired_stage_n, ex.stage_stats())
                    _add_counts(self._retired_scored, ex.scored_rows())
                    _add_pairs(self._retired_tiles, ex.rank_tiles())
            self._entries = entries
            self._order = order
            self.names = names
            self._view = tuple(entries[sid] for sid in order)
            self._seen_version = ver

    # -- warmup --------------------------------------------------------------
    def warmup(self, cost_reps: int = 2, include_ladder: bool = True,
               joinability: bool = False,
               modes: Optional[Sequence[str]] = None) -> None:
        """Compile the serving plans for every resident segment shape and
        measure dispatch costs (kept per capacity class so live-segment
        turnover doesn't lose them).

        ``modes`` defaults to **every** prune mode — after this warmup any
        request (scorer, estimator, k ≤ k_max, prune mode, α) dispatches
        with zero compiles (the DESIGN.md §6 contract; the deprecated
        server aliases pass their config's single mode instead, preserving
        the historical warmup cost). ``include_ladder`` (live sources)
        additionally pre-warms the upcoming capacity-ladder shapes — the
        delta rung and the rung a `compact()` would land on — so the first
        mutation after warmup serves without a compile. ``joinability``
        pre-warms the bare `search_joinable` probe."""
        modes = tuple(modes) if modes is not None else PL.PRUNE_MODES
        cost_mode = self.request.prune if self.request.prune in modes \
            else modes[0]
        warmed = set()
        for e in self._view:
            e.exec.warmup(cost_reps=cost_reps, modes=modes,
                          joinability=joinability, cost_mode=cost_mode,
                          request=self.request)
            self._cap_costs[e.exec.C] = dict(e.exec._bucket_cost)
            warmed.add(e.exec.C)
        if self._live is not None and include_ladder:
            from repro.engine import lifecycle as LC
            ndev = int(self.mesh.devices.size)
            ahead = {self._live.delta_cap,
                     LC.ladder_rung(self._live.live_columns(),
                                    self._live.delta_cap)}
            for cap in sorted(ahead):
                if cap + (-cap) % ndev in warmed:
                    continue
                empty = LC.Segment.empty(-1, cap, self.n, self._live.agg)
                entry = self._make_entry(
                    -1, 0, 0, 0, empty.to_index_shard(),
                    postings=(empty.postings()
                              if self._seg_candidates(cap) == "inverted"
                              else None))
                entry.exec.warmup(cost_reps=cost_reps, modes=modes,
                                  joinability=joinability,
                                  cost_mode=cost_mode,
                                  request=self.request)
                self._cap_costs[entry.exec.C] = dict(entry.exec._bucket_cost)
                warmed.add(entry.exec.C)

    # -- queries -------------------------------------------------------------
    def plan_batches(self, nq: int) -> List[int]:
        """Measured-cost bucket cover for ``nq`` queries (the DP over the
        `warmup()` timings). For a static source this is the single
        executor's plan; for a live source it is the first segment's —
        every segment plans independently at dispatch time."""
        view = self._view
        if not view:
            return []
        return view[0].exec.plan_batches(nq)

    def query_batch(self, sketches: CorrelationSketch, *,
                    request: Optional[PL.Request] = None,
                    refresh: bool = True):
        """Serve a batch of query sketches (leading [NQ] axis) against every
        segment → combined ``[NQ, k]`` (scores, global ids, r, m) numpy
        arrays, global ids indexing `self.names` (-1 for empty tail slots).
        ``request`` overrides the server's default semantics for this call
        only — no compiles, whatever it asks for (post-warmup).
        """
        req = request if request is not None else self.request
        if req.k > self.shape.k_max:
            # k beyond the policy width would come back as fabricated
            # −inf/−1 tail rows indistinguishable from "no more matches" —
            # refuse instead (segments *smaller* than k still pad
            # legitimately: other segments fill the global top-k)
            raise ValueError(
                f"request k={req.k} exceeds ShapePolicy.k_max="
                f"{self.shape.k_max}; raise k_max (a compile-time width) "
                "or lower k")
        with TR.span("serve.query"):
            if refresh:
                self.refresh()
            t_start = time.perf_counter()
            out = self._query_view(sketches, req)
            with self._stats_lock:
                self._q_total += len(out[0])
                self._q_seconds += time.perf_counter() - t_start
            return out

    def _query_view(self, sketches: CorrelationSketch, req: PL.Request):
        """`query_batch` on one atomic read of the published segment map:
        every per-segment dispatch below (and the global-id bases) comes
        from this single snapshot, however many refreshes land
        concurrently."""
        view = self._view
        k = int(req.k)
        nq = int(jax.tree.leaves(sketches)[0].shape[0])
        empty = (np.full((nq, k), -np.inf, np.float32),
                 np.full((nq, k), -1, np.int32),
                 np.zeros((nq, k), np.float32), np.zeros((nq, k), np.float32))
        parts = []
        for e in view:
            if e.used == 0 or nq == 0:
                continue
            s, g, r, m = e.exec.query_batch(sketches, req)
            with TR.span("serve.wait"):
                parts.append((np.asarray(s), np.asarray(g) + e.base,
                              np.asarray(r), np.asarray(m)))
        if not parts:
            return empty
        # deterministic combine: score desc, global id asc as tiebreak
        with TR.span("serve.merge"):
            s = np.concatenate([p[0] for p in parts], axis=1)
            g = np.concatenate([p[1] for p in parts], axis=1)
            r = np.concatenate([p[2] for p in parts], axis=1)
            m = np.concatenate([p[3] for p in parts], axis=1)
            out = empty
            pick = np.lexsort((g, -s), axis=1)[:, :k]
            take = lambda a: np.take_along_axis(a, pick, axis=1)
            s, g, r, m = take(s), take(g), take(r), take(m)
            kk = s.shape[1]
            out[0][:, :kk] = s
            out[1][:, :kk] = np.where(np.isfinite(s), g, -1)
            out[2][:, :kk] = np.where(np.isfinite(s), r, 0.0)
            out[3][:, :kk] = np.where(np.isfinite(s), m, 0.0)
        return out

    def query_columns(self, keys_list, values_list, *, chunk: int = 8192,
                      request: Optional[PL.Request] = None,
                      refresh: bool = True):
        """Convenience: raw query columns → sketches → combined top-k."""
        sks = build_query_sketches(keys_list, values_list, n=self.n,
                                   chunk=chunk)
        return self.query_batch(sks, request=request, refresh=refresh)

    # -- joinability search --------------------------------------------------
    def stage1_hits(self, sketches: CorrelationSketch, *,
                    refresh: bool = True) -> np.ndarray:
        """Exact per-candidate sketch-intersection sizes ``[NQ, C_global]``
        across every segment, sliced to the used slots so the candidate
        axis is exactly the global id space of `self.names`."""
        if refresh:
            self.refresh()
        view = self._view
        parts = [e.exec.stage1_hits(sketches)[:, :e.used] for e in view]
        return (np.concatenate(parts, axis=1) if parts
                else np.zeros((0, 0), np.float32))

    def search_joinable_sketches(self, sketches: CorrelationSketch, *,
                                 k: Optional[int] = None,
                                 metric: str = "containment",
                                 request: Optional[PL.Request] = None,
                                 refresh: bool = True) -> JoinabilityResult:
        """Top-k joinability search across every live segment (DESIGN.md §5).

        Fans the stage-1 containment scan out per segment (each segment
        executor ranks its own candidates — the global top-k is contained in
        the union of per-segment top-ks), shifts segment-local ids into the
        global catalog (`self.names`), and combines deterministically:
        metric desc, global id asc. Tombstoned and unused slots have zero
        stored minima, so they can never surface.
        """
        if metric not in JOIN_METRICS:
            raise ValueError(f"unknown joinability metric {metric!r}: "
                             f"use one of {JOIN_METRICS}")
        req = request if request is not None else self.request
        if refresh:
            self.refresh()
        k = int(k or req.k)
        nq = int(jax.tree.leaves(sketches)[0].shape[0])
        fields = JoinabilityResult._FIELDS
        empty = {f: np.zeros((nq, k), np.float32) for f in fields}
        empty["ids"] = np.full((nq, k), -1, np.int32)
        parts = []
        for e in self._view:
            if e.used == 0:
                continue
            res = e.exec.search_joinable_sketches(sketches, k=k,
                                                  metric=metric,
                                                  alpha=req.alpha)
            ids = np.where(res.ids >= 0, res.ids + e.base, -1)
            parts.append(dataclasses.replace(res, ids=ids.astype(np.int32)))
        if not parts or nq == 0:
            return JoinabilityResult(**{f: empty[f][:nq] for f in fields})
        # every per-segment result is k wide, so the concatenation holds
        # ≥ k columns whenever any part exists — the [:, :k] slice below is
        # always full width
        cat = {f: np.concatenate([getattr(p, f) for p in parts], axis=1)
               for f in fields}
        ok = cat["ids"] >= 0
        pick = np.lexsort((np.where(ok, cat["ids"], np.iinfo(np.int32).max),
                           np.where(ok, -cat["score"], np.inf)), axis=1)[:, :k]
        take = lambda a: np.take_along_axis(a, pick, axis=1)
        valid = take(ok)
        out = {}
        for f in fields:
            taken = take(cat[f])
            out[f] = (np.where(valid, taken, -1).astype(np.int32)
                      if f == "ids" else np.where(valid, taken, 0.0))
        return JoinabilityResult(**out)

    def search_joinable(self, keys_list, *, k: Optional[int] = None,
                        metric: str = "containment", chunk: int = 8192,
                        request: Optional[PL.Request] = None,
                        refresh: bool = True) -> JoinabilityResult:
        """Top-k joinable columns for raw query *key* columns (no values
        needed — joinability is a property of the key sets alone), across
        all segments — global ids index `self.names`."""
        values = [np.zeros((len(kz),), np.float32) for kz in keys_list]
        sks = build_query_sketches(keys_list, values, n=self.n, chunk=chunk)
        return self.search_joinable_sketches(sks, k=k, metric=metric,
                                             request=request,
                                             refresh=refresh)

    # -- telemetry -----------------------------------------------------------
    def throughput(self) -> dict:
        """Lifetime serving telemetry. For live sources ``queries``/``qps``
        count *logical* requests (one per query, however many segments it
        fanned out to) and ``dispatches`` the underlying per-segment plan
        dispatches; static sources report the single executor's
        dispatch-level numbers (including latency percentiles). When an
        `repro.engine.scheduler.AsyncScheduler` is attached, its admission
        telemetry (``queue_depth``, ``deadline_misses``, ...) joins the
        dict."""
        if self._live is None:
            out = self._exec.throughput()
        else:
            view = self._view
            with self._stats_lock:
                q_total = self._q_total
                q_seconds = self._q_seconds
                retired = self._retired["dispatches"]
                stage_n = dict(self._retired_stage_n)
                scored = dict(self._retired_scored)
                tiles = {est: list(v)
                         for est, v in self._retired_tiles.items()}
            # per-stage counts, scored rows and rank tiles across live +
            # retired segment executors (DESIGN.md §11): every view entry
            # owns a distinct exec, so the sum is double-count-free
            for e in view:
                _add_counts(stage_n, e.exec.stage_stats())
                _add_counts(scored, e.exec.scored_rows())
                _add_pairs(tiles, e.exec.rank_tiles())
            stages = {name: dict(count=n)
                      for name, n in sorted(stage_n.items())}
            out = dict(queries=q_total,
                       dispatches=retired
                       + sum(e.exec._total_dispatches for e in view),
                       total_s=q_seconds,
                       qps=q_total / max(q_seconds, 1e-12),
                       compiles=self.cache.misses,
                       segments=len(view),
                       stages=stages,
                       device_dispatches=sum(stage_n.get(name, 0)
                                             for name in _DEVICE_STAGES),
                       scored_rows=scored, rank_tiles=tiles)
        sched = self._scheduler
        if sched is not None:
            out.update(sched.queue_stats())
        return out


# ----------------------------------------------------------------------------
# deprecated alias: the historical single-index server API
# ----------------------------------------------------------------------------

class QueryServer(Server):
    """Deprecated alias of `Server` for a static, already-placed
    `IndexShard` — kept so existing call sites (and their exact output
    conventions) survive the plan/executor refactor.

    Differences from the unified facade, preserved for back-compat:
    ``query_batch`` returns the executor's raw per-program output (no
    cross-segment combine, no −inf → −1 rewrite on the full-scan path) and
    ``warmup`` compiles only the configured ``qcfg.prune`` plan. New code
    should construct `Server` directly.
    """

    def __init__(self, mesh, shard: IndexShard, qcfg,
                 buckets: Sequence[int] = (1, 8, 32), prep=None,
                 index: Optional[SketchIndex] = None,
                 batch_rows: Optional[int] = None,
                 cache: Optional[CompileCache] = None):
        warnings.warn(
            "repro.engine.serve.QueryServer is deprecated; use "
            "repro.engine.serve.Server (one facade for static and live "
            "indexes, per-request semantics — DESIGN.md §6)",
            DeprecationWarning, stacklevel=2)
        super().__init__(mesh, shard, qcfg, buckets=buckets,
                         batch_rows=batch_rows, cache=cache, index=index,
                         prep=prep)
        self.qcfg = qcfg

    # -- legacy surface, delegated to the single executor --------------------
    @property
    def shard(self) -> IndexShard:
        return self._exec.shard

    @property
    def C(self) -> int:
        return self._exec.C

    @property
    def batch_rows(self) -> int:
        return self._exec.batch_rows

    @property
    def dispatch_log(self):
        return self._exec.dispatch_log

    @property
    def _bucket_cost(self):
        return self._exec._bucket_cost

    @_bucket_cost.setter
    def _bucket_cost(self, value):
        self._exec._bucket_cost = value

    @property
    def _total_dispatches(self) -> int:
        return self._exec._total_dispatches

    def qcfg_for(self, B: int):
        """Bucket-B query config (legacy view of `_SegmentExec.shape_for`)."""
        chunk = self._exec.chunk_for(B)
        if chunk == self.qcfg.score_chunk:
            return self.qcfg
        return dataclasses.replace(self.qcfg, score_chunk=chunk)

    def prep(self, B: Optional[int] = None):
        return self._exec.prep(B)

    def query_fn(self, B: int):
        return self._exec.scan_fn(B)

    def stage1_fn(self, B: int, emit_tables: bool = False):
        return self._exec.probe_fn(B, emit_tables=emit_tables)

    def stage2_fn(self, B: int, M: int):
        return self._exec.prune_fn(B, M)

    def topm_fn(self, B: int):
        return self._exec.topm_fn(B)

    def prune_rungs(self) -> List[int]:
        return self._exec.prune_rungs()

    def bucket_for(self, nq: int) -> int:
        return self._exec.bucket_for(nq)

    def warmup(self, cost_reps: int = 2, joinability: bool = False,
               modes: Optional[Sequence[str]] = None) -> None:
        super().warmup(cost_reps=cost_reps, joinability=joinability,
                       modes=modes if modes is not None
                       else (self.request.prune,))

    def query_batch(self, sketches: CorrelationSketch, *,
                    request: Optional[PL.Request] = None):
        """Legacy output convention: the raw program results — jnp arrays
        for the full scan (gids of −inf rows left as the program produced
        them), numpy with −1 ids on the pruned paths."""
        return self._exec.query_batch(
            sketches, request if request is not None else self.request)

    def query_columns(self, keys_list, values_list, *, chunk: int = 8192,
                      request: Optional[PL.Request] = None):
        sks = build_query_sketches(keys_list, values_list, n=self.n,
                                   chunk=chunk)
        return self.query_batch(sks, request=request)

    def stage1_hits(self, sketches: CorrelationSketch) -> np.ndarray:
        return self._exec.stage1_hits(sketches)

    def key_minima(self) -> KeyMinima:
        return self._exec.key_minima()

    def search_joinable_sketches(self, sketches: CorrelationSketch, *,
                                 k: Optional[int] = None,
                                 metric: str = "containment"
                                 ) -> JoinabilityResult:
        return self._exec.search_joinable_sketches(
            sketches, k=int(k or self.request.k), metric=metric,
            alpha=self.request.alpha)

    def search_joinable(self, keys_list, *, k: Optional[int] = None,
                        metric: str = "containment", chunk: int = 8192
                        ) -> JoinabilityResult:
        values = [np.zeros((len(kz),), np.float32) for kz in keys_list]
        sks = build_query_sketches(keys_list, values, n=self.n, chunk=chunk)
        return self.search_joinable_sketches(sks, k=k, metric=metric)
