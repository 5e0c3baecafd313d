"""Batched multi-query engine: exact equivalence with the sequential path,
per-row s4 normalisation, bucket padding, and bounded-memory chunking."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import build_sketch
from repro.core.sketch import Agg, build_sketch_streaming
from repro.data.pipeline import Table, sbn_pair
from repro.engine import index as IX
from repro.engine import plans as PL
from repro.engine import query as Q
from repro.engine import serve as SV


def _scan_fn(mesh, C, n, qcfg, batch=None):
    """Full-scan program with the config's request operands bound — the
    plans-layer replacement for the deprecated `Q.make_query_fn`."""
    shape, req = PL.split_config(qcfg)
    ops = jnp.asarray(PL.request_operands(req))
    fn = PL.make_scan_fn(mesh, C, n, shape, batch=batch)
    return lambda *args: fn(*args, ops)[:4]   # the ranked strips


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    tables = []
    for i in range(10):
        _, ty, _, _ = sbn_pair(rng, n_max=3000)
        tables.append(Table(keys=ty.keys, values=ty.values, name=f"t{i}"))
    idx = IX.build_index(tables, n=64, pad_to=10)
    mesh = jax.make_mesh((1,), ("shard",))
    shard = IX.shard_for_mesh(idx, mesh)
    qts = [sbn_pair(rng, n_max=2500)[0] for _ in range(4)]
    qsks = [build_sketch(jnp.asarray(t.keys), jnp.asarray(t.values), n=64)
            for t in qts]
    return mesh, shard, qts, qsks


def _stacked(qsks):
    qa = [IX.query_arrays(sk) for sk in qsks]
    return tuple(jnp.stack([q[j] for q in qa]) for j in range(5))


# score_chunk=4 with C=10 forces the multi-chunk scan *and* the non-divisible
# padded tail, so the equivalence check covers the whole streaming path.
@pytest.mark.parametrize("intersect", ["sortmerge", "eqmatrix"])
@pytest.mark.parametrize("B", [1, 4])
def test_batched_matches_sequential(corpus, B, intersect):
    mesh, shard, _, qsks = corpus
    qcfg = Q.QueryConfig(k=5, scorer="s4", intersect=intersect, score_chunk=4)
    seqfn = _scan_fn(mesh, 10, 64, qcfg)
    bfn = _scan_fn(mesh, 10, 64, qcfg, batch=B)
    for s in range(0, len(qsks), B):
        batch = qsks[s:s + B]
        if len(batch) < B:
            break
        out = bfn(*_stacked(batch), shard)
        assert all(o.shape[:2] == (B, 5) for o in out)
        for bi, sk in enumerate(batch):
            ref = seqfn(*IX.query_arrays(sk), shard)
            for got, want in zip(out, ref):
                np.testing.assert_array_equal(np.asarray(got[bi]),
                                              np.asarray(want))


def test_s4_normalisation_independent_per_query(corpus):
    """A query's s4 scores must not change with its batch companions: the
    CI-length min/max normalisation is per row, not pooled over the batch."""
    mesh, shard, _, qsks = corpus
    qcfg = Q.QueryConfig(k=5, scorer="s4")
    bfn = _scan_fn(mesh, 10, 64, qcfg, batch=2)
    alone = _scan_fn(mesh, 10, 64, qcfg)(*IX.query_arrays(qsks[0]), shard)
    for partner in (1, 2, 3):
        out = bfn(*_stacked([qsks[0], qsks[partner]]), shard)
        for got, want in zip(out, alone):
            np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))


def test_bucket_padding_returns_real_queries(corpus):
    mesh, shard, qts, qsks = corpus
    qcfg = Q.QueryConfig(k=5, scorer="s4")
    srv = SV.QueryServer(mesh, shard, qcfg, buckets=(1, 2, 8))
    out = srv.query_columns([t.keys for t in qts[:3]],
                            [t.values for t in qts[:3]])
    seqfn = _scan_fn(mesh, 10, 64, qcfg)
    assert all(o.shape == (3, 5) for o in out)
    # 3 queries with buckets (1,2,8) → one padded dispatch at B=8
    assert srv.dispatch_log[-1][0] == 8 and srv.dispatch_log[-1][1] == 3
    for i, sk in enumerate(qsks[:3]):
        ref = seqfn(*IX.query_arrays(sk), shard)
        for got, want in zip(out, ref):
            np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


def test_batched_sketch_build_matches_single(corpus):
    """Chunked + vmapped + merged construction == one build_sketch per column
    (the KMV closure property, exercised through the serving layer)."""
    _, _, qts, qsks = corpus
    sks = SV.build_query_sketches([t.keys for t in qts],
                                  [t.values for t in qts], n=64, chunk=1024)
    for i, ref in enumerate(qsks):
        got = jax.tree.map(lambda a, i=i: a[i], sks)
        gm, rm = np.asarray(got.mask), np.asarray(ref.mask)
        np.testing.assert_array_equal(gm, rm)
        np.testing.assert_array_equal(np.asarray(got.key_hash)[gm],
                                      np.asarray(ref.key_hash)[rm])
        np.testing.assert_allclose(np.asarray(got.values())[gm],
                                   np.asarray(ref.values())[rm], rtol=1e-6)
        np.testing.assert_allclose(float(got.col_min), float(ref.col_min))
        np.testing.assert_allclose(float(got.col_max), float(ref.col_max))


def test_batched_sketch_build_ragged_lengths():
    """Queries with very different row counts share one build: only real
    chunks are sketched (ragged layout) and the per-round KMV fold must
    still equal a standalone build for every column."""
    rng = np.random.default_rng(7)
    cols = []
    for ln in (50, 4000, 300, 9000):
        k = rng.integers(0, 3000, size=ln).astype(np.uint32)
        v = rng.normal(size=ln).astype(np.float32)
        cols.append((k, v))
    sks = SV.build_query_sketches([k for k, _ in cols], [v for _, v in cols],
                                  n=64, chunk=1024)
    for i, (k, v) in enumerate(cols):
        ref = build_sketch(jnp.asarray(k), jnp.asarray(v), n=64)
        got = jax.tree.map(lambda a, i=i: a[i], sks)
        gm, rm = np.asarray(got.mask), np.asarray(ref.mask)
        np.testing.assert_array_equal(gm, rm)
        np.testing.assert_array_equal(np.asarray(got.key_hash)[gm],
                                      np.asarray(ref.key_hash)[rm])
        np.testing.assert_allclose(np.asarray(got.values())[gm],
                                   np.asarray(ref.values())[rm], rtol=1e-5)
        np.testing.assert_allclose(float(got.rows), float(ref.rows))


def test_score_chunk_padding_bounds_memory(corpus):
    """Regression (#satellite): C % score_chunk != 0 used to fall back to one
    unchunked O(C·n²) block; now the tail is padded and masked. The chunked
    scan must agree with the single-block result and drop the pad rows."""
    mesh, shard, _, qsks = corpus
    qa = IX.query_arrays(qsks[0])
    whole = Q.QueryConfig(k=5, score_chunk=512)   # C=10 → single block
    chunked = Q.QueryConfig(k=5, score_chunk=4)   # 10 % 4 != 0 → padded scan
    s0, r0, m0, c0 = Q.score_shard(*qa, shard, whole)
    s1, r1, m1, c1 = Q.score_shard(*qa, shard, chunked)
    assert s1.shape == (10,)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(r0), np.asarray(r1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
    # eq-matrix path too: the padded candidates must not produce matches
    eq = Q.QueryConfig(k=5, score_chunk=3, intersect="eqmatrix")
    s2, r2, m2, _ = Q.score_shard(*qa, shard, eq)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s2), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m2))


def _lengths(c, nq, chunk):
    """Row counts whose longest column spans ``c`` chunks: one ragged
    column alone, or three of different chunk counts."""
    if nq == 1:
        return [c * chunk - 17]
    return [c * chunk - 5, chunk // 3, (c + 1) // 2 * chunk]


@pytest.mark.parametrize("nq", [1, 3])
@pytest.mark.parametrize("c", [1, 2, 3, 8])
@pytest.mark.parametrize("agg", list(Agg))
def test_compiled_front_end_equals_streaming_build(agg, c, nq):
    """The one-program build (vmapped chunk sketches + in-program KMV fold)
    equals, leaf for leaf, the per-column streaming build at the same chunk:
    every aggregation (FIRST/LAST read the row order), 1–8 chunks, lone and
    ragged batches, repeated keys across chunks and NaN rows."""
    chunk, n = 256, 32
    rng = np.random.default_rng([c, nq, list(Agg).index(agg)])
    cols = []
    for ln in _lengths(c, nq, chunk):
        k = rng.integers(0, 3 * chunk, size=ln).astype(np.uint32)
        v = rng.normal(size=ln).astype(np.float32)
        v[rng.random(ln) < 0.02] = np.nan
        cols.append((k, v))
    sks = SV.build_query_sketches([k for k, _ in cols], [v for _, v in cols],
                                  n=n, agg=agg, chunk=chunk)
    assert sks.agg == agg and sks.key_hash.shape == (nq, n)
    for i, (k, v) in enumerate(cols):
        ref = build_sketch_streaming(k, v, n=n, agg=agg, chunk=chunk)
        got = jax.tree.map(lambda a, i=i: a[i], sks)
        for name, g, r in zip(ref.__dataclass_fields__, jax.tree.leaves(got),
                              jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                          err_msg=name)


def test_front_end_warm_shape_compiles_nothing():
    """A second call at a warmed shape lowers no JAX program: the front
    end's `programs` count stays flat while `calls` grows, and a new chunk
    count costs exactly one more program."""
    rng = np.random.default_rng(5)

    def call(rows):
        k = rng.integers(0, 5000, size=rows).astype(np.uint32)
        v = rng.normal(size=rows).astype(np.float32)
        return jax.block_until_ready(
            SV.build_query_sketches([k], [v], n=32, chunk=512))

    call(1500)                                       # warm: 3 chunks
    lowerings = []
    hear = lambda event, *a, **kw: lowerings.append(event) \
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration" else None
    jax.monitoring.register_event_duration_secs_listener(hear)
    try:
        before = SV.front_stats()
        call(1400)                                   # same shape, new data
        mid = SV.front_stats()
        assert lowerings == []
        assert mid["calls"] == before["calls"] + 1
        assert mid["programs"] == before["programs"]
        call(2000)                                   # 4 chunks: new shape
        after = SV.front_stats()
        assert after["programs"] == mid["programs"] + 1
        assert after["calls"] == mid["calls"] + 1
        assert len(lowerings) == 1
    finally:
        jax.monitoring.unregister_event_duration_listener(hear)
