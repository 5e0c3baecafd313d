"""Pluggable stage-1 candidate generation (DESIGN.md §7).

Stage 1 of two-stage retrieval (DESIGN.md §5) answers one question per
query: *which columns share keys with it, and how many* — the exact
sketch-intersection hit counts that drive `prune='safe'` eligibility,
``topm`` selection and the `search_joinable` workload. This module makes
that stage a first-class pluggable layer behind one small interface:

  * `ScanSource` — the existing containment scan over every resident
    column (`plans.make_probe_fn` through the segment executor's compile
    cache), extracted verbatim: dispatches the very same compiled probe
    programs as before, so its hit counts are bit-identical to the
    pre-refactor path (pinned in tests). O(C) per query.
  * `InvertedSource` — the QCR-style inverted key index
    (`repro.engine.index.Postings`): hashed key values map to the columns
    containing them, so candidate generation is one ``searchsorted`` per
    query key plus a fixed-width window gather and a device-side
    postings-merge (`repro.kernels.ops.postings_merge`) —
    O(n_q · (W + log E)), independent of the corpus size. Postings array
    shapes ride the segment capacity ladder and the gather window its own
    ``2^i`` ladder, so index mutation causes zero recompiles (warmed one
    rung ahead).

Both sources return the *same exact counts* (each stored (key, column)
pair is counted at most once, and query keys are distinct within a
sketch), so the provably-top-k-preserving ``prune='safe'`` guarantee
(DESIGN.md §5) carries over to the inverted source unchanged — property-
tested in `tests/test_candidates.py`. Select with
``ShapePolicy(candidates="scan" | "inverted")``.
"""
from __future__ import annotations

from typing import Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sketch import PAD_KEY
from repro.engine import trace as TR
from repro.engine.index import Postings, placement
from repro.engine.plans import _postings_window_candidates
from repro.kernels import ops as K
from repro.kernels.ops import KernelConfig

#: the candidate-source vocabulary of `plans.ShapePolicy.candidates`
CANDIDATE_SOURCES = ("scan", "inverted")

#: base rung of the gather-window ladder ``WINDOW_BASE · 2^i`` — the window
#: only ever takes these widths, so run-length growth under mutation almost
#: never meets an uncompiled program (warmup compiles one rung ahead)
WINDOW_BASE = 8


def window_rung(max_run: int, base: int = WINDOW_BASE) -> int:
    """Smallest window on the fixed ladder ``base · 2^i`` covering the
    longest equal-key postings run (same shape-quantisation idea as
    `lifecycle.ladder_rung`)."""
    w = int(base)
    while w < max_run:
        w *= 2
    return w


@runtime_checkable
class CandidateSource(Protocol):
    """Stage-1 candidate generation: per-query candidate sets as exact
    intersection hit counts.

    ``hit_counts`` takes the standard query tuple ``qa = (q_kh, q_val,
    q_mask, q_cmin, q_cmax)`` already padded to bucket ``B`` and returns
    host ``f32 [B, C]`` counts — ``hits[b, c]`` is the exact size of the
    stored-key intersection between query ``b`` and column ``c`` (the
    sketch-join sample size ``m``; zero for non-candidates). Implementations
    must agree on these counts exactly — the `prune='safe'` eligibility
    filter (DESIGN.md §5) reads them as ground truth.
    """
    kind: str

    def hit_counts(self, qa, B: int) -> np.ndarray: ...

    def warmup(self, B: int) -> None: ...


class ScanSource:
    """The containment scan as a candidate source — the pre-refactor
    stage-1 path, verbatim: every dispatch goes through the owning segment
    executor's warmed probe plans (`serve._SegmentExec.probe_fn`), reusing
    an already-compiled emit-tables variant when one is resident rather
    than compiling a lean twin (the historical `stage1_hits` behaviour, so
    hit counts — and compile counts — are bit-identical to before)."""

    kind = "scan"

    def __init__(self, ex):
        self._ex = ex   # a serve._SegmentExec (duck-typed to avoid a cycle)

    def warmup(self, B: int) -> None:
        ex = self._ex
        qa = ex._dummy_queries(B)
        jax.block_until_ready(
            ex.probe_fn(B)(*qa, ex.shard, *ex._prep_args(B)))

    def hit_counts(self, qa, B: int) -> np.ndarray:
        ex = self._ex
        emit = ex._use_prep and ex._key("probe", B, (True,)) in ex.cache
        out = ex.probe_fn(B, emit_tables=emit)(*qa, ex.shard,
                                               *ex._prep_args(B))
        hits = out[0] if isinstance(out, tuple) else out
        with TR.span("serve.wait"):
            return np.asarray(jax.block_until_ready(hits))


def make_postings_probe_fn(E: int, W: int, batch: int, n: int,
                           cfg: KernelConfig, mesh):
    """Build the compiled inverted-probe program for one (E, W, B, n)
    shape: per query key, ``searchsorted`` into the key-sorted postings,
    gather a W-wide window, match, and merge the matched column ids into
    per-column counts on device (`ops.postings_merge`). Returns sparse
    ``(cols i32[B, n·W], counts f32[B, n·W])`` — corpus-size-independent;
    the host scatters into dense ``[B, C]`` rows by id.

    Queries and postings are replicated, and every device of ``mesh`` runs
    the whole probe (DESIGN.md §10). The program is a `shard_map` over
    replicated specs because the compiler cannot partition a Pallas
    kernel across a mesh on its own."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def probe(q_kh, q_mask, keys, cols):
        cand = _postings_window_candidates(q_kh, q_mask, keys, cols, E, W)
        return K.postings_merge(cand, cfg)

    return jax.jit(shard_map(probe, mesh=mesh, in_specs=(P(),) * 4,
                             out_specs=(P(), P()), check_vma=False))


def dense_hit_counts(cols: np.ndarray, counts: np.ndarray,
                     C: int) -> np.ndarray:
    """Scatter sparse merged postings output into dense ``f32 [B, C]`` hit
    rows. Each live id occupies exactly one slot per row (the
    `postings_merge` contract), so plain assignment is exact.

    Since the fused device-resident path (DESIGN.md §11) this O(C)
    materialisation is off the serving hot path: `prune='safe'` queries run
    probe → select → score in one dispatch (`plans.make_inverted_fn`) and
    never build a dense row. It survives as the **test oracle** for that
    path (`tests/test_fused_inverted.py`) and as the dense backend of
    `hit_counts` — the `stage1_hits` / `search_joinable` / ``topm``
    workloads, which want all-candidate counts by definition."""
    B = cols.shape[0]
    hits = np.zeros((B, C), np.float32)
    b, s = np.nonzero(cols >= 0)
    hits[b, cols[b, s]] = counts[b, s]
    return hits


class InvertedSource:
    """QCR-style inverted key index as a candidate source (DESIGN.md §7).

    Holds one segment's `Postings` (host layout + device copies). The
    probe program is cached in the shared `CompileCache` keyed on
    ``(B, E, W, n, kernels, mesh)`` — E is fixed by the segment's ladder capacity
    and W by the window ladder, so segment turnover under mutation reuses
    warmed programs. ``warmup`` compiles the current window rung *and the
    next one*, covering run-length growth between refreshes.
    """

    kind = "inverted"

    def __init__(self, postings: Postings, *, C: int, n: int, cache, mesh,
                 kernels: KernelConfig):
        self.C = int(C)
        self.n = int(n)
        self.E = postings.E
        self.W = window_rung(postings.max_run())
        self.cache = cache
        self.cfg = kernels
        self._mesh = mesh
        # the probe reads every posting on every device (DESIGN.md §10), so
        # the planes are placed replicated on the mesh once, not re-sent
        # from one device on every dispatch
        rep = placement(mesh, replicated=True)
        self._keys_d = jax.device_put(postings.keys, rep)
        self._cols_d = jax.device_put(postings.cols, rep)

    def _probe_fn(self, B: int, W: int):
        return self.cache.get(
            ("inv-probe", B, self.E, W, self.n, self.cfg, self._mesh),
            lambda: make_postings_probe_fn(self.E, W, B, self.n, self.cfg,
                                           self._mesh))

    def warmup(self, B: int) -> None:
        qk = jnp.full((B, self.n), PAD_KEY, jnp.uint32)
        qm = jnp.zeros((B, self.n), jnp.float32)
        for W in (self.W, self.W * 2):
            jax.block_until_ready(
                self._probe_fn(B, W)(qk, qm, self._keys_d, self._cols_d))

    def hit_counts(self, qa, B: int) -> np.ndarray:
        out = self._probe_fn(B, self.W)(qa[0], qa[2], self._keys_d,
                                        self._cols_d)
        with TR.span("serve.wait"):
            cols, counts = (np.asarray(a) for a in jax.block_until_ready(out))
        return dense_hit_counts(cols, counts, self.C)
