"""Pallas TPU kernels for the rank-based estimators (paper §5.3).

Spearman's ρ, the RIN transform and the Qn robust correlation all start
from O(n²) pairwise comparisons over the sketch-join sample. Sorting is
hostile to the TPU's vector unit, so everything here uses the branch-free
pairwise formulation

    rank_i = #{j valid : x_j < x_i} + (#{j valid : x_j == x_i} + 1) / 2

which is block compares + reductions — pure VPU work with perfectly regular
shape. n is the sketch size (≤ 1024), so n² stays tiny; the win is batching
thousands of rows per launch.

Three kernels:

``rank_transform``
    The original standalone rank transform (kept as the ref/fallback while
    the fused kernel is the hot path): ranks land in HBM, the caller reduces
    them. Grid ``(R // block_r, n // block_n)`` with reduction-grid
    revisiting over the column blocks.

``rank_moments``
    The fused hot path: per row-block, ranks for ``a`` and ``b`` accumulate
    in VMEM scratch across the j-tiles of the pair plane and are folded into
    the six sufficient statistics ``[m, Σrₐ, Σr_b, Σrₐ², Σr_b², Σrₐr_b]``
    in the finalize step — the ``[R, n]`` rank arrays never touch HBM, and
    the output is 6 floats/row instead of n. Pair tiles in which no row of
    the block carries weight are skipped (an occupancy word per row block,
    scalar-prefetched): they would add exactly 0, so the output is the
    same bits, and a full scan over a lake in which few columns join the
    query computes almost none of them. ``kind="rin"`` applies the rankit
    epilogue Φ⁻¹((r − ½)/m) in-register between ranking and the moment
    reduction (`_rankit`: a polynomial erfinv with Python-float
    coefficients — a kernel may capture no array constants, which rules
    out ``jax.scipy.special.ndtri``).

``qn_correlation``
    The Shevlyakov–Oja robust correlation: four Qn scale estimates per row,
    each the kq-th smallest pairwise |difference|. Instead of sorting the n²
    differences, the kernel finds the exact order statistic by bisecting the
    int32 bit space of non-negative float32 (bit patterns of finite f32 ≥ 0
    are monotone in value): 31 count-reductions over the [n, n] pairwise
    differences, recomputed tile by tile, no sort, no gather.

Both pairwise kernels keep one sublane group of rows per block and tile
the pair plane in lane-aligned ``[rows, i, j]`` tiles of at most
`repro.kernels.tiling.TILE_BYTES`, so they compile for the chip at every
documented sketch size (n ≤ 1024).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import TILE_BYTES, lane_block, pack_columns

_VMEM_BUDGET = 4 * 1024 * 1024  # soft cap for the resident compare tensor


def _fit_blocks(block_r: int, block_n: int, n: int,
                budget: int = _VMEM_BUDGET) -> tuple:
    """Shrink ``(block_r, block_n)`` until the [block_r, n, block_n] compare
    tensor fits the VMEM budget.

    Rows shrink first (halving just lengthens the row grid); only when
    ``block_r == 1`` still busts the budget does ``block_n`` shrink — to the
    largest divisor of n not exceeding half the current block, so the column
    grid keeps tiling n exactly. Both dims are accounted for, so a caller
    passing an explicit ``block_n`` can no longer blow past the budget with
    ``block_r`` already at 1.
    """
    def footprint(br, bn):
        return br * n * bn * 4
    while block_r > 1 and footprint(block_r, block_n) > budget:
        block_r //= 2
    while block_n > 1 and footprint(block_r, block_n) > budget:
        nxt = block_n // 2
        while nxt > 1 and n % nxt:
            nxt -= 1
        block_n = max(nxt, 1)
    return block_r, block_n


def _pad_rows(arrs, R: int, block_r: int):
    """Zero-pad the leading axis of each [R, n] array to a block_r multiple.

    Padded rows carry mask == 0, so they produce all-zero moments (and are
    sliced off by the caller)."""
    Rp = -(-R // block_r) * block_r
    if Rp == R:
        return arrs, Rp
    pad = [(0, Rp - R), (0, 0)]
    return [jnp.pad(x, pad) for x in arrs], Rp


# ----------------------------------------------------------------------------
# rank_transform — standalone ranks (ref/fallback path)
# ----------------------------------------------------------------------------

def _kernel(x_ref, xs_ref, ms_ref, rank_ref):
    jblk = pl.program_id(1)

    xi = x_ref[...]    # [Br, n]  — the rows whose ranks we produce
    xj = xs_ref[...]   # [Br, Bn] — column block of the same rows
    mj = ms_ref[...]   # [Br, Bn]

    lt = (xj[:, None, :] < xi[:, :, None]).astype(jnp.float32)   # [Br, n, Bn]
    eq = (xj[:, None, :] == xi[:, :, None]).astype(jnp.float32)
    less = jnp.einsum("rib,rb->ri", lt, mj, preferred_element_type=jnp.float32)
    equal = jnp.einsum("rib,rb->ri", eq, mj, preferred_element_type=jnp.float32)

    @pl.when(jblk == 0)
    def _init():
        rank_ref[...] = jnp.zeros(rank_ref.shape, rank_ref.dtype)

    rank_ref[...] += less + equal * 0.5

    @pl.when(jblk == pl.num_programs(1) - 1)
    def _finalize():
        rank_ref[...] += 0.5  # the (+1)/2 term


@functools.partial(jax.jit, static_argnames=("block_r", "block_n", "interpret"))
def rank_transform(x, mask, *, block_r: int = 8, block_n: int = 0,
                   interpret: bool = False):
    """See :func:`repro.kernels.ref.rank_transform` for semantics."""
    R, n = x.shape
    if block_n <= 0:
        block_n = n
    block_r, block_n = _fit_blocks(block_r, block_n, n)
    assert R % block_r == 0 and n % block_n == 0, (R, n, block_r, block_n)
    mask = mask.astype(jnp.float32)

    grid = (R // block_r, n // block_n)
    ranks = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, n), lambda r, j: (r, 0)),
            pl.BlockSpec((block_r, block_n), lambda r, j: (r, j)),
            pl.BlockSpec((block_r, block_n), lambda r, j: (r, j)),
        ],
        out_specs=pl.BlockSpec((block_r, n), lambda r, j: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, n), jnp.float32),
        interpret=interpret,
    )(x, x, mask)
    return ranks * mask


# ----------------------------------------------------------------------------
# rank_moments — fused rank → sufficient-statistics kernel (the hot path)
# ----------------------------------------------------------------------------

def _horner(coeffs, x):
    acc = coeffs[0]
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


#: single-precision erfinv coefficients (M. Giles, "Approximating the erfinv
#: function", GPU Computing Gems, 2011 — the ones XLA's f32 ErfInv uses):
#: central branch in w − 2.5, tail branch in √w − 3, w = −log(1 − x²)
_ERFINV_CENTRAL = (2.81022636e-08, 3.43273939e-07, -3.5233877e-06,
                   -4.39150654e-06, 0.00021858087, -0.00125372503,
                   -0.00417768164, 0.246640727, 1.50140941)
_ERFINV_TAIL = (-0.000200214257, 0.000100950558, 0.00134934322,
                -0.00367342844, 0.00573950773, -0.0076224613,
                0.00943887047, 1.00167406, 2.83297682)


def _rankit(r, m):
    """Φ⁻¹(clip((r − ½)/m, 1e-6, 1 − 1e-6)) in f32, in-kernel, as
    √2·erfinv(2q − 1). The kernel may capture no array constants, which
    rules out ``jax.scipy.special.ndtri``; the coefficients are Python
    floats. q and 1 − q are both formed from the rank, so neither tail
    suffers the cancellation of ``1 - q``, and 1 − x² = 4·q·(1 − q)."""
    q = jnp.clip((r - 0.5) / m, 1e-6, 1.0 - 1e-6)
    qc = jnp.clip((m - r + 0.5) / m, 1e-6, 1.0 - 1e-6)
    w = -jnp.log(4.0 * q * qc)
    central = _horner(_ERFINV_CENTRAL, w - 2.5)
    tail = _horner(_ERFINV_TAIL, jnp.sqrt(w) - 3.0)
    return 1.4142135623730951 * jnp.where(w < 5.0, central, tail) * (q - qc)


def _moments_kernel(kind, bi, bj, occ_ref, a_ref, b_ref, w_ref, out_ref,
                    ra_ref, rb_ref):
    n = w_ref.shape[-1]
    ti, tj = n // bi, n // bj
    word = occ_ref[pl.program_id(0)]
    # bit t < ti: some row of the block has a live slot in i-tile t; bit
    # ti + t: the same for j-tile t (`_occupancy`)
    live = lambda bit: ((word >> bit) & 1) != 0

    def counts(xi, xj, wj):
        # Σ_j w_j·[x_j < x_i] + ½·Σ_j w_j·[x_j == x_i], this j-tile
        lt = jnp.where(xj[:, None, :] < xi[:, :, None], wj[:, None, :], 0.0)
        eq = jnp.where(xj[:, None, :] == xi[:, :, None], wj[:, None, :], 0.0)
        return jnp.sum(lt + 0.5 * eq, axis=-1)          # [Br, Bi]

    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    if kind == "rin":
        msafe = jnp.maximum(jnp.sum(w_ref[...], axis=-1, keepdims=True), 1.0)

    for i in range(ti):
        isl = slice(i * bi, (i + 1) * bi)

        @pl.when(live(i))
        def _slot_tile():
            ra_ref[...] = jnp.zeros(ra_ref.shape, ra_ref.dtype)
            rb_ref[...] = jnp.zeros(rb_ref.shape, rb_ref.dtype)
            for j in range(tj):
                jsl = slice(j * bj, (j + 1) * bj)

                @pl.when(live(ti + j))
                def _pair_tile():
                    wj = w_ref[:, jsl]
                    ra_ref[...] += counts(a_ref[:, isl], a_ref[:, jsl], wj)
                    rb_ref[...] += counts(b_ref[:, isl], b_ref[:, jsl], wj)

            w = w_ref[:, isl]                           # [Br, Bi]
            ra = (ra_ref[...] + 0.5) * w                # masked average ranks
            rb = (rb_ref[...] + 0.5) * w
            if kind == "rin":
                ra = jnp.where(w > 0, _rankit(ra, msafe), 0.0)
                rb = jnp.where(w > 0, _rankit(rb, msafe), 0.0)
            s = lambda x: jnp.sum(x, -1, keepdims=True)
            out_ref[...] += pack_columns(
                out_ref.shape,
                [s(w), s(ra), s(rb), s(ra * ra), s(rb * rb), s(ra * rb)])


def pair_blocks(block_r: int, n: int, block_n: int = 0) -> tuple:
    """(i, j) tile of the ``[block_r, i, j]`` pairwise plane: an explicit
    ``block_n`` is the j-block as given, else both are lane-aligned
    divisors of n holding the tile to `TILE_BYTES`."""
    bj = block_n if block_n > 0 else lane_block(n, 512)
    bi = lane_block(n, max(TILE_BYTES // (4 * block_r * bj), 1))
    return bi, bj


def _occupancy(w, block_r: int, bi: int, bj: int):
    """One int32 word per ``block_r``-row block of ``w`` (rows a multiple of
    ``block_r``): bit t set when some row of the block has a nonzero weight
    in i-tile t (width ``bi``), bit ``n/bi + t`` the same for j-tile t
    (width ``bj``). A pair tile adds exactly 0 to every count unless both
    of its bits are set."""
    Rp, n = w.shape
    ti, tj = n // bi, n // bj
    assert ti + tj <= 31, (n, bi, bj)
    rows = jnp.any(w.reshape(Rp // block_r, block_r, n) != 0, axis=1)
    tiles = jnp.concatenate([rows.reshape(-1, ti, bi).any(-1),
                             rows.reshape(-1, tj, bj).any(-1)], axis=-1)
    return jnp.sum(tiles.astype(jnp.int32)
                   << jnp.arange(ti + tj, dtype=jnp.int32), axis=-1)


def rank_tiles(mask, *, block_r: int = 8, block_n: int = 0):
    """``i32[2]`` = [pair tiles `rank_moments` computes for this mask,
    pair tiles its grid covers]: the telemetry of the occupancy skip, from
    the same words the kernel reads (inside one jitted program XLA shares
    the reduce with the kernel's)."""
    R, n = mask.shape
    bi, bj = pair_blocks(block_r, n, block_n)
    (w,), Rp = _pad_rows([mask.astype(jnp.float32)], R, block_r)
    occ = _occupancy(w, block_r, bi, bj)
    ti = n // bi
    popcount = jax.lax.population_count
    live = jnp.sum(popcount(occ & ((1 << ti) - 1)) * popcount(occ >> ti))
    total = (Rp // block_r) * ti * (n // bj)
    return jnp.stack([live, jnp.int32(total)])


@functools.partial(jax.jit,
                   static_argnames=("kind", "block_r", "block_n", "interpret"))
def rank_moments(a, b, mask, *, kind: str = "spearman", block_r: int = 8,
                 block_n: int = 0, interpret: bool = False):
    """Fused masked rank transform + moment reduction per row.

    a, b: f32[R, n], mask: f32[R, n] → f32[R, 6] =
    ``[m, Σrₐ, Σr_b, Σrₐ², Σr_b², Σrₐr_b]`` (feed `pearson_from_moments`).
    ``kind="rin"`` replaces ranks by the rankit transform before reducing.
    Semantics: :func:`repro.kernels.ref.rank_moments`.

    Grid ``(R / block_r,)``: one step holds a ``[block_r, n]`` row block of
    a, b and the mask in VMEM and walks its ``[block_r, Bi, Bj]`` pair
    tiles in a static loop. The ranks of one ``[block_r, Bi]`` slot tile
    accumulate in VMEM scratch over the j-tiles and are folded into the
    row block's moments after the last one; only the [Br, 6] moment block
    is written back, so HBM output traffic is O(R), not O(R·n).

    **Occupancy skip.** A scalar-prefetched word per row block
    (`_occupancy`, one XLA reduce over the mask) marks the i- and j-tiles
    in which some row of the block has a nonzero weight. A pair tile whose
    j-slots all weigh 0 adds exactly +0.0 to every count, and an i-tile
    whose slots all weigh 0 is multiplied down to 0 by the finalize, so
    both are skipped (`pl.when`) and the output is bit-identical to
    computing them. Where few slots join — a full scan over a lake in which
    few columns share the query's keys — almost every tile is skipped;
    dense masks pay only the reduce.
    """
    R, n = a.shape
    bi, bj = pair_blocks(block_r, n, block_n)
    assert n % bj == 0 and n % bi == 0, (n, bi, bj)
    w = mask.astype(jnp.float32)
    (a, b, w), Rp = _pad_rows([a, b, w], R, block_r)
    occ = _occupancy(w, block_r, bi, bj)

    row = pl.BlockSpec((block_r, n), lambda r, occ: (r, 0))
    out = pl.pallas_call(
        functools.partial(_moments_kernel, kind, bi, bj),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Rp // block_r,),
            in_specs=[row, row, row],
            out_specs=pl.BlockSpec((block_r, 6), lambda r, occ: (r, 0)),
            scratch_shapes=[pltpu.VMEM((block_r, bi), jnp.float32),
                            pltpu.VMEM((block_r, bi), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Rp, 6), jnp.float32),
        interpret=interpret,
    )(occ, a, b, w)
    return out[:R]


# ----------------------------------------------------------------------------
# qn_correlation — Shevlyakov–Oja robust correlation, sort-free
# ----------------------------------------------------------------------------

_MAX_FINITE_BITS = int(np.float32(np.finfo(np.float32).max).view(np.int32))
_BIG = 3.4e38


def _qn_kernel(bi, bj, a_ref, b_ref, w_ref, out_ref):
    a = a_ref[...]                                      # [Br, n]
    b = b_ref[...]
    w = w_ref[...]
    n = a.shape[-1]
    m = jnp.sum(w, axis=-1, keepdims=True)              # [Br, 1], exact ints
    h = jnp.floor(m * 0.5) + 1.0
    kq = jnp.maximum(h * (h - 1.0) * 0.5, 1.0)          # [Br, 1]
    # (i, j) tiles of the pair plane that hold some pair i < j
    tiles = [(i0, j0) for i0 in range(0, n, bi) for j0 in range(0, n, bj)
             if j0 + bj - 1 > i0]

    def pair_count(x, t):
        # #{valid pairs i < j : |x_i − x_j| ≤ t}, one [Br, bi, bj] tile at
        # a time so the plane never has to fit VMEM
        total = jnp.zeros(t.shape, jnp.float32)
        cut = lambda v, s0, size: jax.lax.slice_in_dim(v, s0, s0 + size,
                                                       axis=1)
        for i0, j0 in tiles:
            row = i0 + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0)
            col = j0 + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1)
            xi, xj = cut(x, i0, bi), cut(x, j0, bj)
            wi, wj = cut(w, i0, bi), cut(w, j0, bj)
            pw = (wi[:, :, None] * wj[:, None, :]
                  * (col > row).astype(jnp.float32)[None])
            d = jnp.abs(xi[:, :, None] - xj[:, None, :])
            hit = jnp.where(d <= t[:, :, None], pw, 0.0)
            total += jnp.sum(jnp.sum(hit, axis=-1), axis=-1, keepdims=True)
        return total

    def qn_scale(x):
        # kq-th smallest valid pairwise |difference|, found by bisecting the
        # int32 bit space of non-negative f32 (bits are monotone in value):
        # count(d ≤ t) is a step function that only increases at realised
        # difference values, so the minimal t with count ≥ kq IS the order
        # statistic — exactly, in 31 compare-reduce passes, no sort. Counts
        # stay < 2²⁴ (n² ≤ 1M), so the f32 accumulation is exact.
        def body(_, lohi):
            lo, hi = lohi
            mid = lo + (hi - lo) // 2
            t = jax.lax.bitcast_convert_type(mid, jnp.float32)  # [Br, 1]
            hit = pair_count(x, t) >= kq
            return jnp.where(hit, lo, mid + 1), jnp.where(hit, mid, hi)

        lo = jnp.zeros(kq.shape, jnp.int32)
        hi = jnp.full(kq.shape, _MAX_FINITE_BITS, jnp.int32)
        _, hi = jax.lax.fori_loop(0, 31, body, (lo, hi))
        kth = jax.lax.bitcast_convert_type(hi, jnp.float32)
        # kq exceeding the valid pair count leaves hi at max-finite ≥ big → 0
        # (2.21914: asymptotic consistency constant for N(0,1))
        return 2.21914 * jnp.where(kth >= _BIG, 0.0, kth)

    sa = qn_scale(a)
    sb = qn_scale(b)
    ok = (sa > 1e-12) & (sb > 1e-12)
    az = a / jnp.where(ok, sa, 1.0)
    bz = b / jnp.where(ok, sb, 1.0)
    inv_sqrt2 = float(1.0 / np.sqrt(2.0))
    qu = qn_scale((az + bz) * inv_sqrt2)
    qv = qn_scale((az - bz) * inv_sqrt2)
    num = qu * qu - qv * qv
    den = qu * qu + qv * qv
    r = jnp.where(den > 1e-12, num / jnp.where(den > 1e-12, den, 1.0), 0.0)
    out_ref[...] = jnp.clip(jnp.where(ok, r, 0.0), -1.0, 1.0)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def qn_correlation(a, b, mask, *, block_r: int = 8, interpret: bool = False):
    """Per-row Qn robust correlation. a, b: f32[R, n], mask → f32[R].

    Semantics: :func:`repro.core.estimators.qn_correlation` (same constants,
    same degenerate-case handling). Rows stay at ``block_r`` per block; the
    pair plane is counted in `pair_blocks` tiles, recomputed on every
    bisection pass instead of held resident.
    """
    R, n = a.shape
    bi, bj = pair_blocks(block_r, n)
    w = mask.astype(jnp.float32)
    (a, b, w), Rp = _pad_rows([a, b, w], R, block_r)

    spec = pl.BlockSpec((block_r, n), lambda r: (r, 0))
    out = pl.pallas_call(
        functools.partial(_qn_kernel, bi, bj),
        grid=(Rp // block_r,),
        in_specs=[spec, spec, spec],
        out_specs=pl.BlockSpec((block_r, 1), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        interpret=interpret,
    )(a, b, w)
    return out[:R, 0]
