"""End-to-end behaviour tests for the paper's system.

Full pipeline: synthetic table collection → sketch index → batched top-k
join-correlation queries → ranking quality vs ground truth (the paper's
Table 1 setup in miniature), plus the training-side augmentation loop.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import build_sketch
from repro.core.sketch import Agg
from repro.data.pipeline import Table, joined_truth, sbn_pair, skewed_pair
from repro.engine import index as IX
from repro.engine import plans as PL
from repro.engine import query as Q


def _corpus_with_truth(rng, n_pairs=24, n_rows=4000):
    """Query column + candidates with KNOWN after-join correlations."""
    kk = rng.choice(1 << 30, size=n_rows, replace=False).astype(np.uint32)
    x = rng.standard_normal(n_rows).astype(np.float32)
    query_t = Table(keys=kk, values=x, name="q")
    tables, true_r = [], []
    for i in range(n_pairs):
        r = float(rng.uniform(-1, 1))
        keep = rng.random(n_rows) < rng.uniform(0.3, 1.0)
        y = (r * x + np.sqrt(max(1 - r * r, 0)) * rng.standard_normal(n_rows)).astype(np.float32)
        tables.append(Table(keys=kk[keep], values=y[keep], name=f"c{i}"))
        true_r.append(float(np.corrcoef(x[keep], y[keep])[0, 1]))
    return query_t, tables, np.array(true_r)


def test_end_to_end_query_quality(rng):
    qt, tables, true_r = _corpus_with_truth(rng)
    idx = IX.build_index(tables, n=256, pad_to=24)
    mesh = jax.make_mesh((1,), ("shard",))
    shard = IX.shard_for_mesh(idx, mesh)
    qsk = build_sketch(jnp.asarray(qt.keys), jnp.asarray(qt.values), n=256)
    s, g, r, m = Q.query(shard, qsk, mesh, Q.QueryConfig(k=24, scorer="s4"))
    g = np.asarray(g)
    r = np.asarray(r)
    # estimates close to truth for every returned candidate
    err = np.abs(r - true_r[g])
    assert np.median(err) < 0.1, np.median(err)
    # the top hit should be among the truly most-correlated columns
    assert abs(true_r[g[0]]) >= np.sort(np.abs(true_r))[-5]


def test_estimates_match_full_join(rng):
    """Sketch estimate vs correlation computed on the *fully joined* table,
    with repeated keys and mean aggregation (Fig. 1/2 semantics)."""
    tx, ty, r_target, c = sbn_pair(rng, n_max=20000)
    # introduce repeated keys in y
    rep = rng.integers(0, len(ty.keys), size=len(ty.keys) // 3)
    ty_keys = np.concatenate([ty.keys, ty.keys[rep]])
    ty_vals = np.concatenate([ty.values, ty.values[rep] + 0.1]).astype(np.float32)
    ty2 = Table(keys=ty_keys, values=ty_vals)
    sx = build_sketch(jnp.asarray(tx.keys), jnp.asarray(tx.values), n=256, agg=Agg.MEAN)
    sy = build_sketch(jnp.asarray(ty2.keys), jnp.asarray(ty2.values), n=256, agg=Agg.MEAN)
    from repro.core.join import sketch_join
    from repro.core import estimators as E
    sj = sketch_join(sx, sy)
    est = float(E.pearson(sj.a, sj.b, sj.mask))
    xj, yj = joined_truth(tx, ty2, agg="mean")
    truth = float(np.corrcoef(xj, yj)[0, 1])
    assert abs(est - truth) < 0.2, (est, truth, int(sj.m))


def test_augmentation_improves_model(rng):
    """The paper's motivating loop: discover a correlated feature via
    join-correlation query, join it in, and show a regression model improves
    (Example 2 of the paper, miniaturised)."""
    n = 2000
    kk = rng.choice(1 << 30, size=n, replace=False).astype(np.uint32)
    latent = rng.standard_normal(n).astype(np.float32)
    target = latent + 0.3 * rng.standard_normal(n).astype(np.float32)
    tables = [Table(keys=kk, values=(latent + 0.2 * rng.standard_normal(n)).astype(np.float32),
                    name="driver")]
    for i in range(15):
        _, ty, _, _ = sbn_pair(rng, n_max=n)
        tables.append(Table(keys=ty.keys, values=ty.values, name=f"noise{i}"))
    idx = IX.build_index(tables, n=128, pad_to=16)
    mesh = jax.make_mesh((1,), ("shard",))
    shard = IX.shard_for_mesh(idx, mesh)
    qsk = build_sketch(jnp.asarray(kk), jnp.asarray(target), n=128)
    s, g, r, m = Q.query(shard, qsk, mesh, Q.QueryConfig(k=1))
    assert int(g[0]) == 0  # found the driver
    feat = tables[int(g[0])]
    common, xi, yi = np.intersect1d(kk, feat.keys, return_indices=True)
    X0 = np.ones((len(common), 1), np.float32)                 # intercept only
    X1 = np.stack([np.ones(len(common)), feat.values[yi]], 1)  # + discovered feature
    yt = target[xi]

    def mse(X):
        w = np.linalg.lstsq(X, yt, rcond=None)[0]
        return float(np.mean((X @ w - yt) ** 2))

    assert mse(X1) < 0.5 * mse(X0)  # augmentation halves the error


def test_batched_query_serving(rng):
    """Many queries against one index (the §5.5 serving loop) stay accurate."""
    qt, tables, true_r = _corpus_with_truth(rng, n_pairs=16)
    idx = IX.build_index(tables, n=128, pad_to=16)
    mesh = jax.make_mesh((1,), ("shard",))
    shard = IX.shard_for_mesh(idx, mesh)
    shape, req = PL.split_config(Q.QueryConfig(k=4))
    ops = jnp.asarray(PL.request_operands(req))
    sfn = PL.make_scan_fn(mesh, shard.num_columns, 128, shape)
    qfn = lambda *args: sfn(*args, ops)
    for _ in range(3):
        qsk = build_sketch(jnp.asarray(qt.keys), jnp.asarray(qt.values), n=128)
        s, g, r, m, _ = qfn(*IX.query_arrays(qsk), shard)
        assert np.isfinite(np.asarray(s)[np.asarray(m) >= 3]).all()
