"""The fused rank→moments kernel and the Qn kernel vs their oracles.

Satellite coverage for the fused rank pipeline (DESIGN.md §8): the
interpret-mode Pallas `rank_moments` must reproduce the unfused reference
(`ref.rank_transform` ranks reduced to moments in f64) on adversarial
tie/mask patterns, the Qn bisection kernel must match the sort-based
`core.estimators.qn_correlation`, and the `_fit_blocks` VMEM budget must
account for *both* block dims (the pre-fix loop only shrank ``block_r``,
so explicit ``block_n`` callers could exceed the budget with block_r
already at 1).
"""
import numpy as np
import jax.numpy as jnp
import pytest
import scipy.special

from repro.core import estimators as E
from repro.kernels import ops, ref
from repro.kernels import rank_transform as RT
from repro.kernels.ops import KernelConfig

INTERP = KernelConfig("interpret")


def _adversarial(rng, R=9, n=32):
    """Rows covering the degenerate shapes that break naive rank code."""
    a = rng.normal(size=(R, n)).astype(np.float32)
    b = (rng.normal(size=(R, n)) + 0.4 * a).astype(np.float32)
    mask = (rng.random((R, n)) < 0.75).astype(np.float32)
    a[0], b[0] = 1.0, -2.0               # all ties on both sides
    mask[1] = 0.0                        # all-masked row (m = 0)
    mask[2] = 0.0
    mask[2, n // 2] = 1.0                # single survivor (m = 1)
    a[3, : n // 2] = 0.5                 # heavy tie block
    b[4] = b[4, 0]                       # ties on one side only
    mask[5] = 1.0                        # fully dense row
    return a, b, mask


def _moments_f64(ra, rb, w):
    """The six sufficient statistics accumulated in float64."""
    ra, rb, w = (np.asarray(x, np.float64) for x in (ra, rb, w))
    return np.stack([w.sum(-1), (ra * w).sum(-1), (rb * w).sum(-1),
                     (ra * ra * w).sum(-1), (rb * rb * w).sum(-1),
                     (ra * rb * w).sum(-1)], -1)


def test_fit_blocks_accounts_for_both_dims():
    budget = 4 * 1024 * 1024
    # rows shrink first; at the default there is nothing to do
    assert RT._fit_blocks(8, 128, 128, budget) == (8, 128)
    # big n: rows hit 1, and the column dim must now shrink too — the
    # pre-fix loop returned (1, 4096) here, a 64 MB resident block
    br, bn = RT._fit_blocks(8, 4096, 4096, budget)
    assert br * 4096 * bn * 4 <= budget
    assert 4096 % bn == 0
    # explicit block_n stays divisor-aligned even for non-power-of-two n
    br, bn = RT._fit_blocks(1, 96, 96, 96 * 96 * 4 // 2)
    assert 96 % bn == 0 and 1 * 96 * bn * 4 <= 96 * 96 * 4 // 2
    # budget larger than the tensor: untouched
    assert RT._fit_blocks(4, 16, 16, budget) == (4, 16)


@pytest.mark.parametrize("R,n,block_n", [(9, 32, 0), (16, 64, 16), (6, 128, 0)])
def test_rank_moments_matches_unfused_f64_reference(rng, R, n, block_n):
    """Interpret-mode fused kernel == ref ranks + f64 moment accumulation
    on adversarial tie/mask patterns (all-ties, all-masked, single
    survivor). block_n < n exercises the reduction-grid revisiting path
    with the VMEM scratch accumulators."""
    a, b, mask = _adversarial(rng, R=R, n=n)
    aj, bj, mj = (jnp.asarray(x) for x in (a, b, mask))
    got = np.asarray(RT.rank_moments(aj, bj, mj, block_n=block_n,
                                     interpret=True))
    ra = np.asarray(ref.rank_transform(aj, mj))
    rb = np.asarray(ref.rank_transform(bj, mj))
    want = _moments_f64(ra, rb, mask)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # and the XLA production path agrees with the same oracle
    got_ref = np.asarray(ref.rank_moments(aj, bj, mj))
    np.testing.assert_allclose(got_ref, want, rtol=1e-6, atol=1e-6)


def _rin_f64(a, b, mask):
    """f64 rankit of the reference ranks, reduced to the six moments."""
    aj, bj, mj = (jnp.asarray(x) for x in (a, b, mask))
    ra = np.asarray(ref.rank_transform(aj, mj), np.float64)
    rb = np.asarray(ref.rank_transform(bj, mj), np.float64)
    w = np.asarray(mask, np.float64)
    msafe = np.maximum(w.sum(-1, keepdims=True), 1.0)
    ta = np.where(w > 0, scipy.special.ndtri(
        np.clip((ra - 0.5) / msafe, 1e-6, 1 - 1e-6)), 0.0)
    tb = np.where(w > 0, scipy.special.ndtri(
        np.clip((rb - 0.5) / msafe, 1e-6, 1 - 1e-6)), 0.0)
    return _moments_f64(ta, tb, w)


def test_rank_moments_rin_epilogue(rng):
    """kind='rin' applies the rankit transform in-register; the result must
    match an f64 rankit applied to the reference ranks."""
    a, b, mask = _adversarial(rng)
    aj, bj, mj = (jnp.asarray(x) for x in (a, b, mask))
    got = np.asarray(RT.rank_moments(aj, bj, mj, kind="rin", interpret=True))
    want = _rin_f64(a, b, mask)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    got_ref = np.asarray(ref.rank_moments(aj, bj, mj, kind="rin"))
    np.testing.assert_allclose(got_ref, want, rtol=2e-5, atol=2e-5)


def _sparse(rng, R, n, pattern):
    """Masks of a full scan over a lake where few slots join: whole 8-row
    blocks with no weight beside the patterns the occupancy skip must still
    compute exactly."""
    a = (np.round(rng.normal(size=(R, n)) * 4) / 4).astype(np.float32)
    b = (rng.normal(size=(R, n)) + 0.4 * a).astype(np.float32)
    mask = np.zeros((R, n), np.float32)
    if pattern == "empty_blocks":        # block 1 live, its neighbours empty
        mask[8:16] = rng.random((8, n)) < 0.75
    elif pattern == "one_slot":          # one live slot a block: one i-tile,
        for r0 in range(0, R, 8):        # one j-tile
            if (r0 // 8) % 2 == 0:
                mask[rng.integers(r0, min(r0 + 8, R)), rng.integers(0, n)] = 1
    else:                                # "dense_beside_empty"
        mask[8:16] = 1.0
        mask[R - 1, : n // 3] = 1.0      # and a partial tail block
    return a, b, mask


@pytest.mark.parametrize("kind", ["spearman", "rin"])
@pytest.mark.parametrize("pattern",
                         ["empty_blocks", "one_slot", "dense_beside_empty"])
@pytest.mark.parametrize("R,n,block_n", [(21, 256, 64), (19, 1024, 0)])
def test_rank_moments_occupancy_skip_matches_f64_reference(rng, R, n,
                                                           block_n, pattern,
                                                           kind):
    """The occupancy skip is exact: with whole empty 8-row blocks, blocks
    whose one live slot sits in one i- and one j-tile, and a dense block
    beside empty ones, at R not a multiple of 8 and tilings with several
    j-tiles (n = 256, block_n = 64) or several of both (n = 1024: 8 × 2),
    the kernel matches the f64 unfused oracle, rows without weight come
    back as exact 0.0, and `ops.rank_moments` over a 3-D lead shape is the
    same kernel."""
    a, b, mask = _sparse(rng, R, n, pattern)
    aj, bj, mj = (jnp.asarray(x) for x in (a, b, mask))
    moments = lambda a, b, m: np.asarray(RT.rank_moments(
        jnp.asarray(a), jnp.asarray(b), jnp.asarray(m), kind=kind,
        block_n=block_n, interpret=True))
    got = moments(a, b, mask)
    if kind == "spearman":
        want = _moments_f64(np.asarray(ref.rank_transform(aj, mj)),
                            np.asarray(ref.rank_transform(bj, mj)), mask)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:
        # the rin sums Σ Φ⁻¹(·) cancel towards 0, so their f32 error is
        # absolute and grows with the slots summed: the rin test's 2e-5
        # per 32 slots
        np.testing.assert_allclose(got, _rin_f64(a, b, mask),
                                   rtol=2e-5, atol=2e-5 * n / 32)
    # skipping is exact: the same rows, seven to a block beside dense rows
    # so that every pair tile is computed, give the same bits
    pos = np.arange(R) // 7 * 8 + np.arange(R) % 7
    a8, b8 = rng.normal(size=(2, pos[-1] // 8 * 8 + 8, n)).astype(np.float32)
    m8 = np.ones(a8.shape, np.float32)
    a8[pos], b8[pos], m8[pos] = a, b, mask
    np.testing.assert_array_equal(moments(a8, b8, m8)[pos].view(np.int32),
                                  got.view(np.int32))
    empty = mask.sum(-1) == 0
    assert empty.any()
    assert np.all(got[empty].view(np.int32) == 0)      # +0.0, bit for bit
    if block_n == 0:
        got3 = np.asarray(ops.rank_moments(aj[None], bj[None], mj[None],
                                           kind, INTERP))
        assert got3.shape == (1, R, 6)
        np.testing.assert_array_equal(got3[0], got)


def test_rank_tiles_counts_the_occupancy(rng):
    """`rank_tiles` counts the pair tiles the kernel computes: one per
    block with a single live slot, i-tiles × j-tiles for a dense block,
    none for an empty one, over the grid's (padded) total."""
    n = 1024                                 # 8 i-tiles × 2 j-tiles a block
    mask = np.zeros((35, n), np.float32)     # 5 blocks, the last one padded
    mask[3, 700] = 1.0                       # block 0: one tile
    mask[8:16] = 1.0                         # block 1: all 16
    mask[34, 0] = 1.0                        # block 4: one tile
    got = np.asarray(RT.rank_tiles(jnp.asarray(mask)))
    np.testing.assert_array_equal(got, [1 + 16 + 1, 5 * 16])
    np.testing.assert_array_equal(
        np.asarray(ops.rank_tiles(jnp.asarray(mask[None]), INTERP)), got)
    np.testing.assert_array_equal(
        np.asarray(ops.rank_tiles(jnp.asarray(mask))), [0, 0])


def test_rank_moments_feeds_pearson_to_spearman(rng):
    """pearson_from_moments over the fused moments == the host spearman/rin
    estimators — the end-to-end contract `plans._score_block` relies on."""
    a, b, mask = _adversarial(rng)
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    mj = jnp.asarray(mask)
    mb = jnp.asarray(mask > 0)
    r_sp = np.asarray(ref.pearson_from_moments(
        RT.rank_moments(aj, bj, mj, interpret=True)))
    np.testing.assert_allclose(r_sp, np.asarray(E.spearman(aj, bj, mb)),
                               rtol=2e-5, atol=2e-5)
    r_rin = np.asarray(ref.pearson_from_moments(
        RT.rank_moments(aj, bj, mj, kind="rin", interpret=True)))
    np.testing.assert_allclose(r_rin, np.asarray(E.rin(aj, bj, mb)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("R,n", [(9, 32), (6, 64)])
def test_qn_kernel_matches_estimators(rng, R, n):
    """The bit-space bisection kernel == the sort-based host Qn, including
    the degenerate rows (zero valid pairs → scale 0 → r 0)."""
    a, b, mask = _adversarial(rng, R=R, n=n)
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    got = np.asarray(RT.qn_correlation(aj, bj, jnp.asarray(mask),
                                       interpret=True))
    want = np.asarray(E.qn_correlation(aj, bj, jnp.asarray(mask > 0)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # XLA searchsorted-bisection path: same values up to probe rounding
    got_ref = np.asarray(ref.qn_correlation(aj, bj, jnp.asarray(mask)))
    np.testing.assert_allclose(got_ref, want, rtol=5e-5, atol=5e-5)


def test_ops_dispatch_and_leading_dims(rng):
    """The ops-layer dispatchers route both backends through the same
    semantics, for flat and batched leading dims."""
    a, b, mask = _adversarial(rng, R=12, n=32)
    a3 = jnp.asarray(a.reshape(3, 4, 32))
    b3 = jnp.asarray(b.reshape(3, 4, 32))
    m3 = jnp.asarray(mask.reshape(3, 4, 32))
    for kind in ("spearman", "rin"):
        got_i = np.asarray(ops.rank_moments(a3, b3, m3, kind, INTERP))
        got_x = np.asarray(ops.rank_moments(a3, b3, m3, kind))
        assert got_i.shape == got_x.shape == (3, 4, 6)
        np.testing.assert_allclose(got_i, got_x, rtol=2e-5, atol=2e-5)
    q_i = np.asarray(ops.qn_correlation(a3, b3, m3, INTERP))
    q_x = np.asarray(ops.qn_correlation(a3, b3, m3))
    assert q_i.shape == q_x.shape == (3, 4)
    np.testing.assert_allclose(q_i, q_x, rtol=5e-5, atol=5e-5)
    with pytest.raises(ValueError):
        ref.rank_moments(a3, b3, m3, kind="kendall")


# ---------------------------------------------------------------------------
# full-width XLA fast paths (DESIGN.md §8: bitonic sort + batched search)
# ---------------------------------------------------------------------------

def test_sorted_row_primitives_match_references(rng):
    """`_bitonic_sort_rows` == `jnp.sort` bit-for-bit (with +inf padding
    lanes) and `_searchsorted_rows` == row-vmapped `jnp.searchsorted` on
    tie-heavy probes, both sides — the primitives every full-width path
    leans on."""
    x = np.round(rng.normal(size=(32, 128)) * 8).astype(np.float32) / 8
    x[3] = np.inf                        # all-padding row survives the net
    xs_ref = np.sort(x, axis=-1)
    np.testing.assert_array_equal(
        np.asarray(ref._bitonic_sort_rows(jnp.asarray(x))), xs_ref)
    # non-power-of-two widths go through the +inf pad
    y = x[:, :100]
    padded = np.asarray(ref._bitonic_sort_rows(
        ref._pad_pow2_rows(jnp.asarray(y), jnp.inf)))
    np.testing.assert_array_equal(padded[:, :100], np.sort(y, axis=-1))
    assert padded.shape[-1] == 128 and np.all(np.isinf(padded[:, 100:]))
    probe = np.round(rng.normal(size=(32, 128)) * 8).astype(np.float32) / 8
    xs = jnp.asarray(xs_ref)
    for side in ("left", "right"):
        got = np.asarray(ref._searchsorted_rows(xs, jnp.asarray(probe), side))
        want = np.stack([np.searchsorted(xs_ref[i], probe[i], side=side)
                         for i in range(32)])
        np.testing.assert_array_equal(got, want)


def test_rank_sorted_path_bit_identical_to_pairwise(rng):
    """At n ≥ `_RANK_SORTED_MIN_N` ranks come from sort + two binary
    searches; the midrank ``(left + right + 1)/2`` must equal the pairwise
    ``Σ lt + ½·Σ eq + ½`` formula **bit-for-bit** (exact integers and
    halves in f32), so the threshold is invisible to every caller."""
    n = ref._RANK_SORTED_MIN_N + 64      # 256: pow2, above threshold
    a, b, mask = _adversarial(rng, R=16, n=n)
    aj, mj = jnp.asarray(a), jnp.asarray(mask)
    got = np.asarray(ref._ranks_sorted(aj, mj))
    lt = np.where(a[:, None, :] < a[:, :, None], mask[:, None, :], 0.0)
    eq = np.where(a[:, None, :] == a[:, :, None], mask[:, None, :], 0.0)
    want = (np.sum(lt + 0.5 * eq, axis=-1) + 0.5) * mask
    np.testing.assert_array_equal(got, want.astype(np.float32))
    # and the fused rank_moments above threshold still matches the f64
    # oracle (spearman + rin epilogues)
    bj = jnp.asarray(b)
    ra = np.asarray(ref.rank_transform(aj, mj))
    rb = np.asarray(ref.rank_transform(bj, mj))
    np.testing.assert_allclose(
        np.asarray(ref.rank_moments(aj, bj, mj)),
        _moments_f64(ra, rb, mask), rtol=1e-6, atol=1e-6)


def test_qn_full_width_matches_oracle_above_threshold(rng):
    """The bitonic-sorted Qn bisection at full width (n = 256, non-pow2
    n = 200) still matches the host estimator on adversarial rows."""
    for n in (200, 256):
        a, b, mask = _adversarial(rng, R=9, n=n)
        aj, bj = jnp.asarray(a), jnp.asarray(b)
        got = np.asarray(ref.qn_correlation(aj, bj, jnp.asarray(mask)))
        want = np.asarray(E.qn_correlation(aj, bj, jnp.asarray(mask > 0)))
        np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-5)
