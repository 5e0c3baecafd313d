"""Jitted public wrappers for the Pallas kernels.

Dispatch policy: ``KernelConfig("auto")`` (the engine's default) follows
the platform — the Pallas kernels on a TPU, the pure-jnp references
(`ref.py`) elsewhere — and is pinned from the mesh's devices by
`repro.engine.plans.resolve_kernels` before any kernel is called; a direct
call with no config runs the reference. ``backend="interpret"`` executes
the actual kernel bodies through the Pallas interpreter for validation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import containment as _ct
from repro.kernels import flash_attention as _fa
from repro.kernels import hash_build as _hb
from repro.kernels import postings as _pm
from repro.kernels import rank_transform as _rt
from repro.kernels import ref as _ref
from repro.kernels import sketch_join as _sj

Backend = Literal["auto", "xla", "pallas", "interpret"]


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    #: "auto" follows the platform the program runs on (`resolve`): the
    #: Pallas kernels on a TPU, their `ref.py` XLA twins elsewhere. "xla"
    #: and "pallas" pin a path (explicit "xla" is the on-chip oracle);
    #: "interpret" runs the kernel bodies in the Pallas interpreter and is
    #: only ever a test's explicit choice.
    backend: Backend = "auto"

    def resolve(self, platform: str) -> "KernelConfig":
        """Pin ``"auto"`` for the devices' ``platform`` ("tpu", "cpu",
        ...); concrete backends pass through. The only place the choice
        is made: the engine calls it through `plans.resolve_kernels`."""
        if self.backend != "auto":
            return self
        return KernelConfig("pallas" if platform == "tpu" else "xla")

    @property
    def interpret(self) -> bool:
        return self.backend == "interpret"

    @property
    def use_pallas(self) -> bool:
        if self.backend == "auto":
            raise ValueError("KernelConfig('auto') is unresolved: pin it to "
                             "a platform first (KernelConfig.resolve)")
        return self.backend in ("pallas", "interpret")


#: what a direct kernel call runs when given no config: the XLA reference
XLA = KernelConfig("xla")


def sketch_join_moments(q_kh, q_val, q_mask, c_kh, c_val, c_mask,
                        cfg: KernelConfig = XLA):
    if cfg.use_pallas:
        return _sj.sketch_join_moments(q_kh, q_val, q_mask.astype(jnp.float32),
                                       c_kh, c_val, c_mask.astype(jnp.float32),
                                       interpret=cfg.interpret)
    return _ref.sketch_join_moments(q_kh, q_val, q_mask, c_kh, c_val, c_mask)


def sketch_join_moments_batched(q_kh, q_val, q_mask, c_kh, c_val, c_mask,
                                cfg: KernelConfig = XLA):
    """Batched-query join: q_* carry a leading [B] axis, candidates shared.

    The Pallas kernel is single-query; batching goes through its vmap rule
    (one grid launch per row). The XLA path uses the batched reference
    oracle, which is bit-identical per row to the single-query call.
    """
    if cfg.use_pallas:
        return jax.vmap(
            lambda a, b, c: _sj.sketch_join_moments(
                a, b, c.astype(jnp.float32), c_kh, c_val,
                c_mask.astype(jnp.float32), interpret=cfg.interpret))(
                    q_kh, q_val, q_mask)
    return _ref.sketch_join_moments_batched(q_kh, q_val, q_mask, c_kh, c_val, c_mask)


def containment_hits(q_kh, q_mask, c_kh, c_mask,
                     cfg: KernelConfig = XLA):
    """Stage-1 joinability intersect (DESIGN.md §5): exact per-candidate
    key-intersection counts, no value traffic. Pallas on TPU, eq-matrix
    reference on XLA (the engine's sortmerge stage-1 path bypasses this
    wrapper — see `repro.engine.plans.make_probe_fn`)."""
    if cfg.use_pallas:
        return _ct.containment_hits(q_kh, q_mask.astype(jnp.float32),
                                    c_kh, c_mask.astype(jnp.float32),
                                    interpret=cfg.interpret)
    return _ref.containment_hits(q_kh, q_mask, c_kh, c_mask)


def containment_hits_batched(q_kh, q_mask, c_kh, c_mask,
                             cfg: KernelConfig = XLA):
    """Batched stage-1 intersect: q_* carry a leading [B] axis → hits [B, C].
    Pallas batches through its vmap rule (one grid launch per row)."""
    if cfg.use_pallas:
        return jax.vmap(
            lambda a, b: _ct.containment_hits(
                a, b.astype(jnp.float32), c_kh, c_mask.astype(jnp.float32),
                interpret=cfg.interpret))(q_kh, q_mask)
    return _ref.containment_hits_batched(q_kh, q_mask, c_kh, c_mask)


def postings_merge(cand, cfg: KernelConfig = XLA):
    """Dedup-count of gathered postings windows (DESIGN.md §7): merge each
    row of candidate column ids into (cols, counts) with every live id in
    exactly one slot. Slot order is backend-defined (set-equal outputs —
    see `repro.kernels.ref.postings_merge`); consumers scatter by id."""
    if cfg.use_pallas:
        return _pm.postings_merge(cand, interpret=cfg.interpret)
    return _ref.postings_merge(cand)


def postings_select(cols, counts, floor, M: int,
                    cfg: KernelConfig = XLA):
    """Device-resident survivor select over merged postings rows
    (DESIGN.md §11): the union of column ids whose exact hit count clears
    the traced eligibility ``floor``, emitted ascending and zero-padded to
    the static rung ``M``. Returns ``(surv i32[M], valid bool[M],
    n_surv i32[])`` — ``n_surv`` counts *all* eligible ids, so
    ``n_surv > M`` flags a rung overflow (the emitted survivors are then
    incomplete and the caller must re-dispatch on a covering rung)."""
    if cfg.use_pallas:
        return _pm.postings_select(cols, counts, floor, M,
                                   interpret=cfg.interpret)
    return _ref.postings_select(cols, counts, floor, M)


def rank_transform(x, mask, cfg: KernelConfig = XLA):
    if cfg.use_pallas:
        return _rt.rank_transform(x, mask, interpret=cfg.interpret)
    return _ref.rank_transform(x, mask.astype(jnp.float32))


def rank_moments(a, b, mask, kind: str = "spearman",
                 cfg: KernelConfig = XLA):
    """Fused rank transform + moment reduction: a, b, mask f32[..., n] →
    f32[..., 6] sufficient statistics for `pearson_from_moments`
    (``kind="rin"`` rankit-transforms the ranks in the epilogue). The hot
    path of the spearman/rin estimators — the [.., n] rank arrays never
    materialise outside the kernel (DESIGN.md §8)."""
    if cfg.use_pallas:
        lead, n = a.shape[:-1], a.shape[-1]
        flat = lambda x: x.reshape(-1, n)
        out = _rt.rank_moments(flat(a), flat(b),
                               flat(mask.astype(jnp.float32)),
                               kind=kind, interpret=cfg.interpret)
        return out.reshape(*lead, 6)
    return _ref.rank_moments(a, b, mask, kind=kind)


def rank_tiles(mask, cfg: KernelConfig = XLA):
    """``i32[2]`` = [pair tiles the Pallas `rank_moments` computes for this
    mask, pair tiles its grid covers] (the occupancy skip's telemetry,
    DESIGN.md §8); zeros on the XLA path, which has no tiles."""
    if cfg.use_pallas:
        return _rt.rank_tiles(mask.reshape(-1, mask.shape[-1]))
    return jnp.zeros((2,), jnp.int32)


def qn_correlation(a, b, mask, cfg: KernelConfig = XLA):
    """Qn robust correlation per row: a, b, mask f32[..., n] → f32[...].
    Pallas bisects the pairwise-difference bit space in VMEM; the XLA path
    sorts once and bisects with searchsorted counts (`ref.qn_correlation`)."""
    if cfg.use_pallas:
        lead, n = a.shape[:-1], a.shape[-1]
        flat = lambda x: x.reshape(-1, n)
        out = _rt.qn_correlation(flat(a), flat(b),
                                 flat(mask.astype(jnp.float32)),
                                 interpret=cfg.interpret)
        return out.reshape(lead)
    return _ref.qn_correlation(a, b, mask)


def hash_build(keys, cfg: KernelConfig = XLA):
    if cfg.use_pallas:
        return _hb.hash_build(keys, interpret=cfg.interpret)
    return _ref.hash_build(keys)


def flash_attention(q, k, v, *, causal=True, window=0,
                    cfg: KernelConfig = XLA):
    if cfg.use_pallas:
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   interpret=cfg.interpret)
    return _ref.flash_attention(q, k, v, causal=causal, window=window)


# moment → statistics helpers shared by engine and benchmarks
pearson_from_moments = _ref.pearson_from_moments
hoeffding_from_moments = _ref.hoeffding_from_moments
