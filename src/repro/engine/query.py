"""Legacy facade of the distributed query engine (pre-plan/executor API).

The engine's real serving core lives in `repro.engine.plans` (DESIGN.md §6):
one compiled pipeline program per (batch, index shape, `ShapePolicy`), with
per-request semantics — estimator, scorer, α, eligibility floor — entering
as traced operands and k as a host-side slice of the static ``k_max`` rank
stage. This module keeps the original API surface alive on top of it:

  * `QueryConfig` — the historical all-in-one config. New code should use
    the split pair `plans.ShapePolicy` (compile-relevant) +
    `plans.Request` (per-request); `plans.split_config` converts.
  * `make_query_fn` / `make_stage1_fn` / `make_pruned_query_fn` /
    `make_topm_query_fn` — **deprecated** thin wrappers that build the
    corresponding plan and bind the request operands derived from the
    `QueryConfig`. Results are produced by the very same compiled programs
    the unified `repro.engine.serve.Server` dispatches, so old and new APIs
    are bit-identical by construction.
  * `score_shard` / `_scores_from_stats` — statically-specialised stage
    entry points kept for tests and host-side tooling; the scorer math is
    single-sourced in `repro.core.scoring` via `plans.score_stats`.

Shared data structures (`PreppedShard`), host-side helpers
(`select_survivors`, `prune_rung`) and the probe-table primitives are
re-exported from `repro.engine.plans` so existing imports keep working.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax.numpy as jnp

from repro.engine import plans as PL
from repro.engine.index import IndexShard
from repro.kernels.ops import KernelConfig

# re-exported plan/executor primitives (canonical home: repro.engine.plans)
from repro.engine.plans import (  # noqa: F401
    _PAD_KEY, PreppedShard, make_prep_fn, prune_rung,
    _block_bits, _block_hittab, _block_vtab, _prep_block, _use_bits,
    _w_from_bits)


@dataclasses.dataclass(frozen=True)
class QueryConfig:
    """Knobs of the distributed query program (paper Defn. 3 + DESIGN.md §5).

    Historically both the compile key and the request: ``k``/``estimator``/
    ``scorer``/``alpha``/``min_sample``/``prune`` mirror the paper's query
    model (§4: top-k, the §5.3 estimators, the §4.4 scorers, the §4.3
    confidence level and the m ≥ 3 eligibility floor), the rest is engine
    shape policy. The plan/executor core (DESIGN.md §6) splits the two
    concerns — `repro.engine.plans.split_config` maps this onto a
    (`ShapePolicy`, `Request`) pair; prefer those for new code.
    """
    k: int = 10
    estimator: str = "pearson"      # pearson | spearman | rin | qn
    scorer: str = "s4"              # s1 | s2 | s4  (s3 = bootstrap: host path)
    alpha: float = 0.05
    min_sample: int = 3
    kernels: KernelConfig = KernelConfig()
    #: candidates scored per inner step; bounds the (chunk × n_q × n) match
    #: tensor on the XLA path (the Pallas kernel tiles the same way in VMEM)
    score_chunk: int = 512
    #: XLA-path intersect: "sortmerge" (O(C·n·log n), no n² tensor — §Perf E2)
    #: or "eqmatrix" (the kernel-shaped reference formulation)
    intersect: str = "sortmerge"
    #: two-stage retrieval (DESIGN.md §5): "off" = the classic full scan
    #: (bit-identical to pre-prune behaviour); "safe" = drop candidates whose
    #: *exact* stage-1 intersection is below ``min_sample``; "topm" = keep
    #: the ``prune_m`` best stage-1 candidates per query
    prune: str = "off"              # off | safe | topm
    #: "topm" survivor budget per query (union across a batch)
    prune_m: int = 128
    #: base rung of the compacted-shard capacity ladder ``prune_base · 2^i``
    prune_base: int = 64


def _static_scorer(qcfg: QueryConfig) -> str:
    # the historical scoring tail treated every scorer outside {s1, s2} as
    # s4; the static entry points keep that leniency
    return qcfg.scorer if qcfg.scorer in ("s1", "s2") else "s4"


def _split(qcfg: QueryConfig):
    """(ShapePolicy, operand vector) for the deprecated builders below
    (`split_config` already applies the historical scorer/estimator
    leniency)."""
    shape, req = PL.split_config(qcfg)
    return shape, jnp.asarray(PL.request_operands(req))


def _deprecated(name: str, replacement: str):
    warnings.warn(
        f"repro.engine.query.{name} is deprecated; use "
        f"repro.engine.plans.{replacement} (per-request semantics ride in "
        "as traced operands — see DESIGN.md §6)",
        DeprecationWarning, stacklevel=3)


# ----------------------------------------------------------------------------
# statically-specialised stage entry points (host tooling + tests)
# ----------------------------------------------------------------------------

def score_shard(q_kh, q_val, q_mask, q_cmin, q_cmax, shard: IndexShard,
                qcfg: QueryConfig, axis_names=None,
                prep: Optional[PreppedShard] = None):
    """Score every candidate in a shard (§4: estimator → §4.3 CI → §4.4
    scorer); returns (scores, r, m, ci_len).

    Statically specialised on the `QueryConfig` (the compiled serving paths
    instead trace the request operands — `repro.engine.plans`). Accepts a
    single query (``q_kh: [n_q]``) or a batch (``q_kh: [B, n_q]``); the s4
    normalisation is per query row (a ``[B]`` pmin/pmax across shards when
    ``axis_names`` is given).
    """
    from repro.core.bounds import hoeffding_eligibility_floor
    shape = PL.resolve_kernels(PL.split_config(qcfg)[0])
    r, m, ci_len, _ = PL._shard_stats(q_kh, q_val, q_mask, q_cmin, q_cmax,
                                      shard, shape, qcfg.estimator,
                                      qcfg.alpha, prep=prep)
    s = PL.score_stats(r, m, ci_len, _static_scorer(qcfg),
                       float(hoeffding_eligibility_floor(qcfg.min_sample)),
                       axis_names=axis_names)
    return s, r, m, ci_len


def _scores_from_stats(r, m, ci_len, qcfg: QueryConfig, axis_names=None):
    """Deprecated: the scoring tail now lives in `repro.engine.plans.
    score_stats`, with the §4.4 formulas single-sourced in
    `repro.core.scoring` (se_z_factor / ci_h_factor_from_bounds)."""
    from repro.core.bounds import hoeffding_eligibility_floor
    return PL.score_stats(r, m, ci_len, _static_scorer(qcfg),
                          float(hoeffding_eligibility_floor(qcfg.min_sample)),
                          axis_names=axis_names)


def select_survivors(hits, qcfg: QueryConfig):
    """Host-side stage-1 → stage-2 candidate selection (DESIGN.md §5);
    see `repro.engine.plans.select_survivors` (the canonical home)."""
    return PL.select_survivors(hits, prune=qcfg.prune,
                               min_sample=qcfg.min_sample,
                               prune_m=qcfg.prune_m)


# ----------------------------------------------------------------------------
# deprecated program builders (thin wrappers over the plan executor)
# ----------------------------------------------------------------------------

def make_query_fn(mesh, C_total: int, n: int, qcfg: QueryConfig,
                  batch: Optional[int] = None, with_prep: bool = False):
    """Deprecated: build the full-scan program for one `QueryConfig`.

    A thin wrapper over `repro.engine.plans.make_scan_fn` that binds the
    config's request operands — the returned callable keeps the historical
    signature ``fn(q_kh, q_val, q_mask, q_cmin, q_cmax, shard[, prep])``
    and is bit-identical to the plan program the unified server dispatches
    (it *is* that program, with the operand vector pre-bound).
    """
    _deprecated("make_query_fn", "make_scan_fn")
    shape, ops = _split(qcfg)
    fn = PL.make_scan_fn(mesh, C_total, n, shape, batch=batch,
                         with_prep=with_prep)
    return lambda *args: fn(*args, ops)[:4]   # the historical outputs


def make_stage1_fn(mesh, C_total: int, n: int, qcfg: QueryConfig,
                   batch: Optional[int] = None, with_prep: bool = False,
                   emit_tables: bool = False):
    """Deprecated: build the stage-1 containment-scan program; a thin
    wrapper over `repro.engine.plans.make_probe_fn` (which is request-
    independent, so nothing needs binding)."""
    _deprecated("make_stage1_fn", "make_probe_fn")
    shape, _ = _split(qcfg)
    return PL.make_probe_fn(mesh, C_total, n, shape, batch=batch,
                            with_prep=with_prep, emit_tables=emit_tables)


def make_pruned_query_fn(mesh, C_total: int, n: int, qcfg: QueryConfig,
                         M: int, batch: Optional[int] = None,
                         with_prep: bool = False):
    """Deprecated: build the stage-2 pruned-scoring program for ladder rung
    ``M``; a thin wrapper over `repro.engine.plans.make_pruned_fn` with the
    config's request operands pre-bound."""
    _deprecated("make_pruned_query_fn", "make_pruned_fn")
    shape, ops = _split(qcfg)
    fn = PL.make_pruned_fn(mesh, C_total, n, shape, M, batch=batch,
                           with_prep=with_prep)
    return lambda *args: fn(*args, ops)[:4]   # the historical outputs


def make_topm_query_fn(mesh, C_total: int, n: int, qcfg: QueryConfig,
                       batch: int, with_prep: bool = False):
    """Deprecated: build the fused ``prune='topm'`` program; a thin wrapper
    over `repro.engine.plans.make_topm_fn` with the config's request
    operands pre-bound."""
    _deprecated("make_topm_query_fn", "make_topm_fn")
    shape, ops = _split(qcfg)
    fn = PL.make_topm_fn(mesh, C_total, n, shape, batch=batch,
                         with_prep=with_prep)
    return lambda *args: fn(*args, ops)[:4]   # the historical outputs


def query(index_shard: IndexShard, query_sketch, mesh, qcfg: QueryConfig):
    """Convenience one-shot query (paper Defn. 3; compiles per index
    shape — serving layers cache programs instead, DESIGN.md §4/§6)."""
    from repro.engine.index import query_arrays
    qa = query_arrays(query_sketch)
    shape, ops = _split(qcfg)
    fn = PL.make_scan_fn(mesh, index_shard.num_columns,
                         index_shard.sketch_size, shape)
    return fn(*qa, index_shard, ops)[:4]
