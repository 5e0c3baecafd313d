"""Plan/executor architecture (DESIGN.md §6): config split, traced request
semantics, scoring parity, the compile-count contract and the deprecated
shims.

The load-bearing assertions:

  * the unified scoring tail (`plans.score_stats`, routed through
    `repro.core.scoring`) is **bit-identical** to the pre-refactor s1/s2/s4
    formulas, both statically specialised and with traced operands;
  * the ``prune='off'`` plan is bit-identical to the statically-specialised
    scan (the PR 1 batched engine semantics), for every fast scorer × both
    estimators;
  * ``safe``/``topm`` requests keep the PR 4 superset/ulp-equality
    contracts against the full scan;
  * after `Server.warmup()` a request sweep over every scorer × estimator ×
    k ≤ k_max × prune mode × α triggers **zero** compiles
    (`CompileCache.misses` flat) — one compiled program per (bucket, index
    shape) serves them all;
  * the legacy builders and both server class names survive as deprecated
    wrappers over the plan executor.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import scoring as SC
from repro.data.pipeline import Table
from repro.engine import index as IX
from repro.engine import plans as PL
from repro.engine import query as Q
from repro.engine import serve as SV
from repro.kernels.ops import KernelConfig

N_SKETCH = 32
#: one compile cache for the whole module: servers share programs, so the
#: parameterised tests pay each (shape, bucket) compile exactly once
CACHE = SV.CompileCache()


def _corpus(rng, n_tables=12, key_space=2000, rows=800):
    tables = []
    for i in range(n_tables):
        m = int(rng.integers(64, rows))
        if i % 4 == 3:  # disjoint universe → never joinable with the queries
            keys = rng.choice(key_space, size=m, replace=False).astype(
                np.uint32) + np.uint32(1 << 20)
        else:
            keys = rng.choice(key_space, size=m, replace=False).astype(
                np.uint32)
        tables.append(Table(keys=keys,
                            values=rng.standard_normal(m).astype(np.float32),
                            name=f"t{i}"))
    return tables


def _queries(rng, nq=4, key_space=2000, rows=700):
    out = []
    for _ in range(nq):
        m = int(rng.integers(64, rows))
        keys = rng.choice(key_space, size=m, replace=False).astype(np.uint32)
        out.append((keys, rng.standard_normal(m).astype(np.float32)))
    return out


def _setup(rng, shape, request=None, n_tables=12, buckets=(4,)):
    tables = _corpus(rng, n_tables=n_tables)
    idx = IX.build_index(tables, n=N_SKETCH, pad_to=n_tables)
    mesh = jax.make_mesh((1,), ("shard",))
    srv = SV.Server(mesh, idx, shape, request=request, buckets=buckets,
                    cache=CACHE)
    return mesh, idx, srv


def _sketches(rng, nq=4):
    queries = _queries(rng, nq=nq)
    return SV.build_query_sketches([k for k, _ in queries],
                                   [v for _, v in queries], n=N_SKETCH)


# ---------------------------------------------------------------------------
# config split
# ---------------------------------------------------------------------------

def test_split_config_partitions_the_legacy_config():
    qcfg = Q.QueryConfig(k=7, estimator="spearman", scorer="s2", alpha=0.1,
                         min_sample=5, score_chunk=33, intersect="eqmatrix",
                         prune="safe", prune_m=17, prune_base=8)
    shape, req = PL.split_config(qcfg)
    # compile-relevant → ShapePolicy
    assert (shape.k_max, shape.score_chunk, shape.intersect,
            shape.prune_m, shape.prune_base) == (7, 33, "eqmatrix", 17, 8)
    # per-request semantics → Request
    assert (req.k, req.estimator, req.scorer, req.prune, req.alpha,
            req.min_sample) == (7, "spearman", "s2", "safe", 0.1, 5)
    # shapes are hashable compile keys; requests never enter them
    assert hash(shape) == hash(dataclasses.replace(shape))
    ops = PL.request_operands(req)
    assert ops.shape == (4,) and ops.dtype == np.float32
    np.testing.assert_allclose(ops, [1.0, 1.0, 0.1, 5.0], rtol=1e-6)


def test_resolve_shape_kernels_follow_the_platform():
    """The default kernel backend is resolved from the mesh's devices: the
    XLA twins on a CPU mesh (the Pallas kernels on a TPU one are pinned by
    `test_tpu_compile.py`); explicit choices stay as given."""
    mesh = jax.make_mesh((1,), ("shard",))
    assert PL.ShapePolicy().kernels.backend == "auto"
    assert PL.resolve_shape(PL.ShapePolicy(), mesh).kernels.backend == "xla"
    for pinned in ("xla", "pallas", "interpret"):
        shape = PL.ShapePolicy(kernels=KernelConfig(pinned))
        assert PL.resolve_shape(shape, mesh).kernels.backend == pinned
    assert KernelConfig().resolve("tpu").backend == "pallas"
    assert KernelConfig().resolve("cpu").backend == "xla"
    # the choice is made once, by resolution: an unresolved backend never
    # reaches a kernel dispatch
    with pytest.raises(ValueError):
        KernelConfig().use_pallas
    with pytest.raises(ValueError):
        PL.uses_sortmerge(PL.ShapePolicy())
    assert PL.resolve_kernels(PL.ShapePolicy()).kernels.backend == "xla"


def test_request_operands_validate_vocabulary():
    with pytest.raises(ValueError):
        PL.request_operands(PL.Request(estimator="kendall"))
    with pytest.raises(ValueError):
        PL.request_operands(PL.Request(scorer="s3"))
    with pytest.raises(ValueError):
        PL.request_operands(PL.Request(prune="sometimes"))


def test_split_config_keeps_legacy_leniency(rng):
    """The pre-refactor scoring tail served any scorer outside {s1, s2} as
    s4 and any estimator it didn't implement as pearson; configs relying on
    that keep being served through the split (and through the deprecated
    servers), while unknown prune modes still raise at construction. Note
    ``rin``/``qn`` are in-program estimators now, so only genuinely unknown
    names (e.g. kendall) take the pearson fallback."""
    shape, req = PL.split_config(Q.QueryConfig(scorer="s3",
                                               estimator="kendall"))
    assert (req.scorer, req.estimator) == ("s4", "pearson")
    _, req_rin = PL.split_config(Q.QueryConfig(estimator="rin"))
    assert req_rin.estimator == "rin"   # promoted, no longer a fallback
    _, req_qn = PL.split_config(Q.QueryConfig(estimator="qn"))
    assert req_qn.estimator == "qn"
    with pytest.raises(ValueError):
        PL.split_config(Q.QueryConfig(prune="sometimes"))
    # end to end: a legacy server with a lenient config serves (as s4)
    tables = _corpus(rng, n_tables=8)
    idx = IX.build_index(tables, n=N_SKETCH, pad_to=8)
    mesh = jax.make_mesh((1,), ("shard",))
    shard = IX.shard_for_mesh(idx, mesh)
    sks = _sketches(rng, nq=2)
    with pytest.warns(DeprecationWarning):
        srv3 = SV.QueryServer(mesh, shard, Q.QueryConfig(k=3, scorer="s3"),
                              buckets=(2,), index=idx, cache=CACHE)
        srv4 = SV.QueryServer(mesh, shard, Q.QueryConfig(k=3, scorer="s4"),
                              buckets=(2,), index=idx, cache=CACHE)
    for got, want in zip(srv3.query_batch(sks), srv4.query_batch(sks)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError):
        SV.Server(mesh, idx, request=PL.Request(prune="nope"), cache=CACHE)


# ---------------------------------------------------------------------------
# §4.4 scoring parity: the old engine formulas, the unified tail and
# core.scoring must agree bit for bit (satellite: scoring single-source)
# ---------------------------------------------------------------------------

def _legacy_scores(r, m, ci_len, scorer, min_sample, axis_names=None):
    """The pre-refactor `engine.query._scores_from_stats` body, verbatim —
    the parallel s2/s4 implementation this PR deleted. Kept here (only) to
    pin the unified path bit-identical to it."""
    eligible = m >= min_sample
    if scorer == "s1":
        s = jnp.abs(r)
    elif scorer == "s2":
        se_z = 1.0 - 1.0 / jnp.sqrt(jnp.maximum(m, 4.0) - 3.0)
        s = jnp.abs(r) * se_z
    else:  # s4
        big = jnp.float32(3.4e38)
        lmin = jnp.min(jnp.where(eligible, ci_len, big), axis=-1)
        lmax = jnp.max(jnp.where(eligible, ci_len, -big), axis=-1)
        rng = jnp.maximum(lmax - lmin, 1e-12)
        f = jnp.clip(1.0 - (jnp.minimum(ci_len, lmax[..., None])
                            - lmin[..., None]) / rng[..., None], 0.0, 1.0)
        s = jnp.abs(r) * f
    return jnp.where(eligible, s, -jnp.inf)


@pytest.mark.parametrize("scorer", ["s1", "s2", "s4"])
def test_score_stats_bit_identical_to_legacy_formulas(rng, scorer):
    B, C = 3, 40
    r = jnp.asarray(rng.uniform(-1, 1, size=(B, C)).astype(np.float32))
    m = jnp.asarray(rng.integers(0, 30, size=(B, C)).astype(np.float32))
    ci_len = jnp.asarray((10.0 ** rng.uniform(-3, 6, size=(B, C))).astype(
        np.float32))
    want = np.asarray(_legacy_scores(r, m, ci_len, scorer, 3))
    # statically specialised tail (what `query.score_shard` runs)
    got_static = np.asarray(PL.score_stats(r, m, ci_len, scorer, 3.0))
    np.testing.assert_array_equal(got_static, want)
    # traced-operand tail (what the compiled plans run)
    ops = jnp.asarray(PL.request_operands(PL.Request(scorer=scorer)))
    got_traced = np.asarray(jax.jit(
        lambda rr, mm, cc, oo: PL.score_stats(rr, mm, cc, oo[1], oo[3]))(
            r, m, ci_len, ops))
    np.testing.assert_array_equal(got_traced, want)
    # and the §4.4 factors really come from core.scoring
    if scorer == "s2":
        np.testing.assert_array_equal(
            np.asarray(SC.se_z_factor(m)),
            np.asarray(1.0 - 1.0 / jnp.sqrt(jnp.maximum(m, 4.0) - 3.0)))
    if scorer == "s4":
        eligible = m >= 3.0
        lmin, lmax = SC.ci_h_bounds(ci_len, eligible)
        f_core = SC.ci_h_factor_from_bounds(ci_len, lmin[..., None],
                                            lmax[..., None])
        fin = np.isfinite(want)
        np.testing.assert_array_equal(
            np.asarray(jnp.abs(r) * f_core)[fin], want[fin])


def test_core_ci_h_factor_unchanged_by_refactor(rng):
    """`core.scoring.ci_h_factor` (the host-side scorer) must still match
    its documented formula after being rerouted through the shared bounds
    helpers."""
    ci_len = jnp.asarray((10.0 ** rng.uniform(-3, 3, size=(5, 16))).astype(
        np.float32))
    eligible = jnp.asarray(rng.random((5, 16)) < 0.7)
    got = np.asarray(SC.ci_h_factor(ci_len, eligible))
    big = jnp.float32(3.4e38)
    lmin = jnp.min(jnp.where(eligible, ci_len, big), -1, keepdims=True)
    lmax = jnp.max(jnp.where(eligible, ci_len, -big), -1, keepdims=True)
    rng_ = jnp.maximum(lmax - lmin, 1e-12)
    f = 1.0 - (jnp.minimum(ci_len, lmax) - lmin) / rng_
    want = np.asarray(jnp.where(eligible, jnp.clip(f, 0.0, 1.0), 0.0))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# plan parity: traced-operand programs vs the statically-specialised stages
# ---------------------------------------------------------------------------

def _static_scan_fn(mesh, shape, req):
    """A compiled scan with the request semantics bound *statically* — the
    exact program structure of the PR 1 batched engine, built from the same
    stage functions. The traced-operand plan must match it bit for bit."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    axes = tuple(mesh.axis_names)
    sizes = PL._axis_sizes(mesh, axes)
    shape = PL.resolve_kernels(shape, mesh)     # as every plan builder does

    def local(q_kh, q_val, q_mask, q_cmin, q_cmax, sh):
        r, m, ci, _ = PL._shard_stats(q_kh, q_val, q_mask, q_cmin, q_cmax,
                                      sh, shape, req.estimator, req.alpha)
        s = PL.score_stats(r, m, ci, req.scorer, float(req.min_sample),
                           axis_names=axes)
        Cl = s.shape[-1]
        lin = PL._linear_device_index(axes, sizes)
        gids = jnp.arange(Cl, dtype=jnp.int32) + lin.astype(jnp.int32) * Cl
        return PL._topk_gathered(s, r, m, gids, shape.k_max, axes)

    fn = shard_map(local, mesh=mesh,
                   in_specs=PL._QUERY_SPECS + (PL._shard_specs(axes),),
                   out_specs=(P(), P(), P(), P()), check_vma=False)
    return jax.jit(fn)


@pytest.mark.parametrize("estimator", ["pearson", "spearman", "rin", "qn"])
@pytest.mark.parametrize("scorer", ["s1", "s2", "s4"])
def test_scan_plan_bit_identical_to_static_scan(rng, scorer, estimator):
    """The one-compiled-program scan (traced estimator/scorer/α/floor) must
    be byte-for-byte the statically specialised compiled scan — the PR 1
    batched engine semantics — for every fast scorer under pearson, the
    default estimator (traced selectors are `lax.switch`/bitwise `where`,
    so the chosen branch's floats are untouched). The rank/qn branches are
    separate called computations whose fused reductions may fuse
    differently → ulp-equal, the same contract the pruned paths carry."""
    qcfg = Q.QueryConfig(k=5, scorer=scorer, estimator=estimator,
                         score_chunk=5)     # non-divisible → padded scan
    shape, req = PL.split_config(qcfg)
    mesh, idx, srv = _setup(rng, shape, request=req)
    shard = srv._exec.shard
    sks = _sketches(rng, nq=4)
    fn = PL.make_scan_fn(mesh, shard.num_columns, N_SKETCH, shape, batch=4)
    ops = jnp.asarray(PL.request_operands(req))
    got = fn(*IX.query_arrays(sks), shard, ops)
    want = _static_scan_fn(mesh, shape, req)(*IX.query_arrays(sks), shard)
    if estimator == "pearson":
        for g_, w_ in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))
    else:
        _superset_with_equal_scores(want, got)
        _superset_with_equal_scores(got, want)
    # and the server's dispatch is that very program (prune='off' serving
    # is bit-identical to the PR 1 batched scan) — prep-backed, like the
    # server's own dispatch
    prep = IX.precompute_prep(idx, mesh, shard, shape)
    fnp = PL.make_scan_fn(mesh, shard.num_columns, N_SKETCH, shape, batch=4,
                          with_prep=True)
    got_p = fnp(*IX.query_arrays(sks), shard, prep, ops)
    out = srv.query_batch(sks)
    fin = np.isfinite(out[0])
    np.testing.assert_array_equal(out[0][fin], np.asarray(got_p[0])[fin])


def _superset_with_equal_scores(full, pruned, tol=2e-5):
    """Every finite full-scan top-k column must appear in the pruned top-k
    with the same score (ulp-tolerant; ties at the k-th boundary may swap —
    see tests/test_two_stage.py for the rationale)."""
    s0, g0 = np.asarray(full[0]), np.asarray(full[1])
    s1, g1 = np.asarray(pruned[0]), np.asarray(pruned[1])
    for i in range(s0.shape[0]):
        fin = np.isfinite(s0[i])
        kth = np.min(s1[i][np.isfinite(s1[i])], initial=np.inf)
        for gid, sc in zip(g0[i][fin], s0[i][fin]):
            j = np.nonzero(g1[i] == gid)[0]
            if j.size == 0:
                assert abs(sc - kth) <= tol * max(1.0, abs(sc)), (
                    f"query {i}: column {gid} (score {sc}) dropped")
                continue
            np.testing.assert_allclose(s1[i][j[0]], sc, rtol=tol, atol=tol)


@pytest.mark.parametrize("estimator", ["pearson", "spearman", "rin", "qn"])
@pytest.mark.parametrize("scorer", ["s1", "s2", "s4"])
def test_safe_and_topm_requests_match_full_scan(rng, scorer, estimator):
    """Per-request prune modes on one warmed server: 'safe' and 'topm'
    (with a covering prune_m) keep the PR 4 contracts against the same
    server's full scan — across scorers × estimators."""
    shape = PL.ShapePolicy(k_max=5, prune_base=4, prune_m=12)
    mesh, idx, srv = _setup(rng, shape)
    sks = _sketches(rng, nq=4)
    req = PL.Request(k=5, scorer=scorer, estimator=estimator)
    full = srv.query_batch(sks, request=req)
    safe = srv.query_batch(sks, request=dataclasses.replace(req,
                                                            prune="safe"))
    topm = srv.query_batch(sks, request=dataclasses.replace(req,
                                                            prune="topm"))
    _superset_with_equal_scores(full, safe)
    _superset_with_equal_scores(full, topm)


@pytest.mark.parametrize("backend_shape", [
    PL.ShapePolicy(k_max=5, prune_base=4, prune_m=12, intersect="eqmatrix",
                   score_chunk=8),
])
def test_safe_and_topm_on_generic_backend(rng, backend_shape):
    """The prep-free intersect backends run the generic gather paths; the
    same superset contract must hold there."""
    mesh, idx, srv = _setup(rng, backend_shape)
    sks = _sketches(rng, nq=4)
    full = srv.query_batch(sks, request=PL.Request(k=5))
    safe = srv.query_batch(sks, request=PL.Request(k=5, prune="safe"))
    topm = srv.query_batch(sks, request=PL.Request(k=5, prune="topm"))
    _superset_with_equal_scores(full, safe)
    _superset_with_equal_scores(full, topm)


def _f64_estimator(name, a, b, wb):
    """Float64 host reference of the §5.3 rank estimators over one aligned
    (query, candidate) pair — deliberately independent of the jnp code."""
    import scipy.special
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    m = int(wb.sum())

    def ranks(x):
        xv = x[wb]
        r = np.zeros_like(x)
        for i in np.nonzero(wb)[0]:
            r[i] = (xv < x[i]).sum() + ((xv == x[i]).sum() + 1) / 2.0
        return r

    def pear(u, v):
        if m < 2:
            return 0.0
        u, v = u[wb], v[wb]
        mu, mv = u.mean(), v.mean()
        cov = (u * v).mean() - mu * mv
        du = max((u * u).mean() - mu * mu, 0.0)
        dv = max((v * v).mean() - mv * mv, 0.0)
        den = np.sqrt(du) * np.sqrt(dv)
        return cov / den if den > 1e-12 else 0.0

    if name == "spearman":
        return pear(ranks(a), ranks(b))
    if name == "rin":
        msafe = max(m, 1)
        ta = scipy.special.ndtri(np.clip((ranks(a) - 0.5) / msafe,
                                         1e-6, 1 - 1e-6))
        tb = scipy.special.ndtri(np.clip((ranks(b) - 0.5) / msafe,
                                         1e-6, 1 - 1e-6))
        return pear(np.where(wb, ta, 0.0), np.where(wb, tb, 0.0))
    assert name == "qn"

    def qn_scale(x):
        xv = x[wb]
        d = np.abs(xv[:, None] - xv[None, :])[np.triu_indices(m, k=1)]
        h = m // 2 + 1
        kq = max(h * (h - 1) // 2, 1)
        if kq > d.size:
            return 0.0
        return 2.21914 * np.sort(d)[kq - 1]

    sa, sb = qn_scale(a), qn_scale(b)
    if sa <= 1e-12 or sb <= 1e-12:
        return 0.0
    az, bz = a / sa, b / sb
    s2 = 1.0 / np.sqrt(2.0)
    qu, qv = qn_scale((az + bz) * s2), qn_scale((az - bz) * s2)
    den = qu * qu + qv * qv
    r = (qu * qu - qv * qv) / den if den > 1e-12 else 0.0
    return float(np.clip(r, -1.0, 1.0))


@pytest.mark.parametrize("estimator", ["spearman", "rin", "qn"])
def test_rank_estimators_match_f64_references_across_plans(rng, estimator):
    """Property test for the fused rank pipeline (DESIGN.md §8): plan-level
    spearman/rin/qn scores — through every scorer × prune mode on one
    warmed server — agree with independent float64 host references within
    ulp-scale tolerance. The reference realigns each (query, candidate)
    sketch pair by key on the host and scores it with numpy/scipy f64
    implementations of the §5.3 estimators, then pushes (r, m, ci) through
    the same §4.4 scoring tail."""
    from repro.kernels import ref as KREF
    shape = PL.ShapePolicy(k_max=5, prune_base=4, prune_m=12)
    mesh, idx, srv = _setup(rng, shape)
    sks = _sketches(rng, nq=3)
    qa = IX.query_arrays(sks)
    shard = srv._exec.shard
    B, C, n = qa[0].shape[0], shard.key_hash.shape[0], N_SKETCH

    r64 = np.zeros((B, C))
    mom = np.zeros((B, C, 6), np.float32)
    for qi in range(B):
        q_kh = np.asarray(qa[0][qi])
        q_val = np.asarray(qa[1][qi])
        q_mask = np.asarray(qa[2][qi]) > 0
        for ci in range(C):
            lut = {k: v for k, v, mk in zip(np.asarray(shard.key_hash[ci]),
                                            np.asarray(shard.values[ci]),
                                            np.asarray(shard.mask[ci]))
                   if mk > 0}
            a = np.zeros(n, np.float32)
            b = np.zeros(n, np.float32)
            wb = np.zeros(n, bool)
            for s in range(n):
                if q_mask[s] and q_kh[s] in lut:
                    a[s], b[s], wb[s] = q_val[s], lut[q_kh[s]], True
            r64[qi, ci] = _f64_estimator(estimator, a, b, wb)
            w = wb.astype(np.float32)
            mom[qi, ci] = [w.sum(), (a * w).sum(), (b * w).sum(),
                           (a * a * w).sum(), (b * b * w).sum(),
                           (a * b * w).sum()]
    c_lo = np.minimum(np.asarray(qa[3])[:, None], np.asarray(shard.col_min))
    c_hi = np.maximum(np.asarray(qa[4])[:, None], np.asarray(shard.col_max))
    lo, hi = KREF.hoeffding_from_moments(jnp.asarray(mom), c_lo, c_hi)
    ci_len = jnp.asarray(hi) - jnp.asarray(lo)
    m = jnp.asarray(mom[..., 0])

    tol = 5e-5 if estimator == "qn" else 2e-5
    for scorer in PL.FAST_SCORERS:
        want = np.asarray(PL.score_stats(
            jnp.asarray(r64.astype(np.float32)), m, ci_len, scorer, 3.0))
        for prune in PL.PRUNE_MODES:
            out = srv.query_batch(sks, request=PL.Request(
                k=5, estimator=estimator, scorer=scorer, prune=prune))
            scores, gids = np.asarray(out[0]), np.asarray(out[1])
            for qi in range(B):
                fin = np.isfinite(scores[qi])
                for sc, gid in zip(scores[qi][fin], gids[qi][fin]):
                    np.testing.assert_allclose(
                        sc, want[qi, gid], rtol=tol, atol=tol,
                        err_msg=f"{estimator}/{scorer}/{prune} q{qi} "
                                f"col{gid}")


def test_request_k_is_a_slice_of_kmax(rng):
    """Any k ≤ k_max is the prefix of the k_max ranking — a host-side
    slice, not a different program; k > k_max is refused (the tail would
    be fabricated −inf rows indistinguishable from 'no more matches')."""
    shape = PL.ShapePolicy(k_max=8)
    mesh, idx, srv = _setup(rng, shape)
    sks = _sketches(rng, nq=3)
    big = srv.query_batch(sks, request=PL.Request(k=8))
    for k in (1, 3, 8):
        small = srv.query_batch(sks, request=PL.Request(k=k))
        for got, want in zip(small, big):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want)[:, :k])
    with pytest.raises(ValueError):
        srv.query_batch(sks, request=PL.Request(k=9))


# ---------------------------------------------------------------------------
# the compile-count contract (acceptance criterion)
# ---------------------------------------------------------------------------

def test_request_sweep_zero_compiles_after_warmup(rng):
    """One compiled program per (bucket, index shape) serves all 3 fast
    scorers × both estimators × any k ≤ k_max × all prune modes × α —
    asserted via the CompileCache miss counter across a full sweep."""
    shape = PL.ShapePolicy(k_max=5, prune_base=4, prune_m=8)
    cache = SV.CompileCache()
    tables = _corpus(rng, n_tables=12)
    idx = IX.build_index(tables, n=N_SKETCH, pad_to=12)
    mesh = jax.make_mesh((1,), ("shard",))
    srv = SV.Server(mesh, idx, shape, buckets=(2,), cache=cache)
    srv.warmup()        # default: every prune mode's plans
    misses = cache.misses
    assert misses > 0
    sks = _sketches(rng, nq=3)
    outs = {}
    for scorer in PL.FAST_SCORERS:
        for estimator in PL.ESTIMATORS:
            for prune in PL.PRUNE_MODES:
                for k in (1, 4, 5):
                    req = PL.Request(k=k, scorer=scorer, estimator=estimator,
                                     prune=prune, alpha=0.07, min_sample=4)
                    out = srv.query_batch(sks, request=req)
                    assert out[0].shape == (3, k)
                    outs[(scorer, estimator, prune, k)] = out
    assert cache.misses == misses, \
        "request semantics must never touch the compile cache"
    # sanity: the sweep actually exercised different semantics
    s_s1 = outs[("s1", "pearson", "off", 5)][0]
    s_s4 = outs[("s4", "pearson", "off", 5)][0]
    assert not np.array_equal(s_s1, s_s4)


def test_live_server_request_sweep_zero_compiles(rng):
    """The same contract across a mutating index: segment ladder shapes ×
    request sweep, still zero post-warmup compiles."""
    from repro.data.pipeline import multi_column_group
    from repro.engine import lifecycle as LC
    rngg = np.random.default_rng(int(rng.integers(1 << 30)))
    groups = [multi_column_group(rngg, n_cols=2, n_max=600, key_space=1 << 11,
                                 name=f"g{i}") for i in range(4)]
    live = LC.LiveIndex(n=N_SKETCH, delta_cap=4)
    live.append(groups[:3])
    mesh = jax.make_mesh((1,), ("shard",))
    cache = SV.CompileCache()
    srv = SV.Server(mesh, live, PL.ShapePolicy(k_max=4, prune_base=2),
                    buckets=(2,), cache=cache)
    live.compact()
    srv.refresh()
    srv.warmup()
    misses = cache.misses
    qk = [groups[1].keys[:300], groups[2].keys[:200]]
    qv = [groups[1].values[0][:300], groups[2].values[0][:200]]
    for prune in PL.PRUNE_MODES:
        for scorer in ("s1", "s4"):
            out = srv.query_columns(qk, qv, request=PL.Request(
                k=4, scorer=scorer, prune=prune))
            assert out[0].shape == (2, 4)
    live.append(groups[3:])     # delta rung was pre-warmed by the ladder
    srv.query_columns(qk, qv, request=PL.Request(k=2, estimator="spearman"))
    assert cache.misses == misses


# ---------------------------------------------------------------------------
# deprecated shims (satellite: back-compat)
# ---------------------------------------------------------------------------

def test_legacy_builders_are_deprecated_wrappers(rng):
    """Every legacy builder imports, warns, and produces results through
    the plan executor (bit-identical to the new API by construction)."""
    qcfg = Q.QueryConfig(k=3, scorer="s4", prune_base=4)
    tables = _corpus(rng, n_tables=8)
    idx = IX.build_index(tables, n=N_SKETCH, pad_to=8)
    mesh = jax.make_mesh((1,), ("shard",))
    shard = IX.shard_for_mesh(idx, mesh)
    sks = _sketches(rng, nq=2)
    qa = IX.query_arrays(sks)
    shape, req = PL.split_config(qcfg)
    ops = jnp.asarray(PL.request_operands(req))

    with pytest.warns(DeprecationWarning):
        qfn = Q.make_query_fn(mesh, 8, N_SKETCH, qcfg, batch=2)
    want = PL.make_scan_fn(mesh, 8, N_SKETCH, shape, batch=2)(
        *qa, shard, ops)
    for got, ref in zip(qfn(*qa, shard), want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    with pytest.warns(DeprecationWarning):
        s1fn = Q.make_stage1_fn(mesh, 8, N_SKETCH, qcfg, batch=2)
    hits = np.asarray(s1fn(*qa, shard))
    assert hits.shape == (2, 8) and (hits >= 0).all()

    surv = Q.select_survivors(hits, dataclasses.replace(qcfg, prune="safe"))
    rung = Q.prune_rung(max(len(surv), qcfg.k), qcfg.prune_base, 8, 1)
    assert rung is None or rung >= qcfg.k
    M = rung if rung is not None else 4
    idx_v = np.zeros((M,), np.int32)
    idx_v[:min(len(surv), M)] = surv[:M]
    valid = np.arange(M) < len(surv)
    with pytest.warns(DeprecationWarning):
        pfn = Q.make_pruned_query_fn(mesh, 8, N_SKETCH, qcfg, M, batch=2)
    s_p, g_p, _, _ = pfn(*qa, shard, jnp.asarray(idx_v), jnp.asarray(valid))
    assert s_p.shape == (2, qcfg.k)

    with pytest.warns(DeprecationWarning):
        tfn = Q.make_topm_query_fn(mesh, 8, N_SKETCH, qcfg, batch=2)
    s_t, g_t, _, _ = tfn(*qa, shard)
    assert s_t.shape == (2, qcfg.k)

    # the deleted scoring tail survives as a wrapper over the unified one
    r = jnp.asarray(rng.uniform(-1, 1, size=(8,)).astype(np.float32))
    m = jnp.asarray(rng.integers(0, 9, size=(8,)).astype(np.float32))
    ci = jnp.asarray(rng.uniform(0.1, 5.0, size=(8,)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(Q._scores_from_stats(r, m, ci, qcfg)),
        np.asarray(PL.score_stats(r, m, ci, "s4", 3.0)))


def test_server_classes_are_deprecated_aliases(rng):
    """`QueryServer` and `LiveQueryServer` survive only as deprecated
    aliases of the unified `Server`."""
    from repro.engine import lifecycle as LC
    assert issubclass(SV.QueryServer, SV.Server)
    assert issubclass(LC.LiveQueryServer, SV.Server)

    tables = _corpus(rng, n_tables=8)
    idx = IX.build_index(tables, n=N_SKETCH, pad_to=8)
    mesh = jax.make_mesh((1,), ("shard",))
    shard = IX.shard_for_mesh(idx, mesh)
    qcfg = Q.QueryConfig(k=3)
    with pytest.warns(DeprecationWarning):
        legacy = SV.QueryServer(mesh, shard, qcfg, buckets=(2,), index=idx,
                                cache=CACHE)
    srv = SV.Server(mesh, idx, qcfg, buckets=(2,), cache=CACHE)
    sks = _sketches(rng, nq=2)
    s_l, g_l, r_l, m_l = (np.asarray(o) for o in legacy.query_batch(sks))
    s_u, g_u, r_u, m_u = srv.query_batch(sks)
    # same results through both facades (the unified one normalises −inf
    # rows to id −1 and re-sorts ties deterministically)
    fin = np.isfinite(s_u)
    np.testing.assert_array_equal(s_l[fin], s_u[fin])
    np.testing.assert_array_equal(g_l[fin], g_u[fin])
    np.testing.assert_array_equal(g_u[~fin],
                                  np.full_like(g_u[~fin], -1))

    from repro.data.pipeline import multi_column_group
    rngg = np.random.default_rng(0)
    live = LC.LiveIndex(n=N_SKETCH, delta_cap=4)
    live.append([multi_column_group(rngg, n_cols=2, n_max=600, name="g0")])
    with pytest.warns(DeprecationWarning):
        lsrv = LC.LiveQueryServer(mesh, live, qcfg, buckets=(1,))
    out = lsrv.query_columns([live.segments()[0].kh[0][:8].astype(np.uint32)],
                             [np.zeros(8, np.float32)])
    assert out[0].shape == (1, 3)
