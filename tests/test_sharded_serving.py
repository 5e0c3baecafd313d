"""Sharded serving: mesh-distributed index behind the server, with a
cross-shard top-k combine (DESIGN.md §10).

The contract under test: a `Server` on an 8-device mesh returns the same
ids and sample sizes as the same server on a single device, and scores
and r equal to 1e-6 relative (f32 reduction order can move the last ulp
from one process to the next), across every scorer × estimator ×
prune-mode combination, on an uneven corpus (C not divisible by D, pad
columns fully masked).  Each
device ranks its own shard and emits a local top-`k_max`; the host
merges the `[D, k_max]` strips with deterministic tie-breaking (score
desc, then global id asc), which is exactly the total order the
single-device gather combine produces.

Like `test_distributed.py`, the multi-device work shells out to a
subprocess (the fake device count must be set before jax initialises).
One subprocess runs all three checks and prints PASS-A/PASS-B/PASS-C
markers; the pytest functions assert on the cached stdout so the heavy
build cost is paid once.
"""
import functools

from test_distributed import _run

_BODY = """
    from repro.data.pipeline import Table, sbn_pair
    from repro.engine import index as IX
    from repro.engine import plans as PL
    from repro.engine import serve as SV

    C = 13                     # uneven: pads to 16 on 8 devices
    N = 32
    RTOL = 1e-6                # ~8 f32 ulps on scores and r

    def make_servers(tables, shape, cache, buckets=(1, 2)):
        idx = IX.build_index(tables, n=N)
        mesh1 = jax.make_mesh((1,), ("shard",), devices=jax.devices()[:1])
        mesh8 = jax.make_mesh((8,), ("shard",))
        srv1 = SV.Server(mesh1, idx, shape, buckets=buckets, cache=cache)
        srv8 = SV.Server(mesh8, idx, shape, buckets=buckets, cache=cache)
        assert srv1.shape.combine == "gather" and srv1.shape.mesh_shards == 1
        assert srv8.shape.combine == "host" and srv8.shape.mesh_shards == 8
        return srv1, srv8

    def sweep(srv1, srv8, sks, combos, k=4):
        bad = []
        for sc, est, pm in combos:
            req = PL.Request(k=k, scorer=sc, estimator=est, prune=pm)
            o1 = srv1.query_batch(sks, request=req)
            o8 = srv8.query_batch(sks, request=req)
            for name, a, b in zip("sgrm", o1, o8):
                a, b = np.asarray(a), np.asarray(b)
                # ids and m exactly; scores and r to RTOL (XLA:CPU may
                # reorder a plan's f32 slot sums from one process to the
                # next: the pearson 'safe' path drifts by an ulp run to run)
                same = (np.array_equal(a, b) if name in "gm" else
                        np.allclose(a, b, rtol=RTOL, atol=0.0))
                if not same:
                    bad.append((sc, est, pm, name, a, b))
            g = np.asarray(o1[1])
            assert g.max() < C, f"pad column id leaked: {g}"
        return bad

    # ---- A: bit-identity across every scorer x estimator x prune mode ----
    rng = np.random.default_rng(3)
    tables, queries = [], []
    for i in range(C):
        tx, ty, _, _ = sbn_pair(rng, n_max=700)
        tables.append(Table(keys=ty.keys, values=ty.values, name=f"t{i}"))
        if len(queries) < 3:
            queries.append(tx)
    # prune_base=8 keeps the 'safe' rung aligned between 1 and 8 devices;
    # prune_m >= C makes per-shard top-M semantically total.
    shape = PL.ShapePolicy(k_max=4, prune_base=8, prune_m=32, score_chunk=512)
    cache = SV.CompileCache()
    srv1, srv8 = make_servers(tables, shape, cache)
    srv1.warmup(modes=PL.PRUNE_MODES)
    srv8.warmup(modes=PL.PRUNE_MODES)
    misses0 = cache.misses

    sks = SV.build_query_sketches([q.keys for q in queries],
                                  [q.values for q in queries], n=N)
    combos = [(sc, est, pm) for sc in PL.FAST_SCORERS
              for est in PL.ESTIMATORS for pm in PL.PRUNE_MODES]
    bad = sweep(srv1, srv8, sks, combos)
    for sc, est, pm, name, a, b in bad:
        print(f"MISMATCH {sc}/{est}/{pm} [{name}]\\n 1dev: {a}\\n 8dev: {b}")
    assert not bad, f"{len(bad)} sharded-vs-single mismatches"

    # inverted stage-1 source: the postings probe is replicated by design,
    # sharding only stage-2 -- ids and scores must still match exactly.
    # 'safe' runs the fused single-dispatch plan (DESIGN.md S11); its rung
    # path is a deterministic function of the query history and prune_base=8
    # aligns the ladder across 1 and 8 devices, so D1 and D8 take identical
    # dispatches and must stay bit-identical through probe -> select ->
    # gather -> score -> rank.
    shape_inv = PL.ShapePolicy(k_max=4, prune_base=8, prune_m=32,
                               score_chunk=512, candidates="inverted")
    cache_i = SV.CompileCache()
    srv1i, srv8i = make_servers(tables, shape_inv, cache_i)
    srv1i.warmup(modes=("topm", "safe"))
    srv8i.warmup(modes=("topm", "safe"))
    misses_i = cache_i.misses
    bad = sweep(srv1i, srv8i, sks,
                [("s4", est, pm) for est in PL.ESTIMATORS
                 for pm in ("topm", "safe")])
    assert not bad, f"{len(bad)} inverted-source mismatches"
    assert cache_i.misses == misses_i, "fused sweep compiled post-warmup"
    for srv in (srv1i, srv8i):
        stats = [e.exec.stage_stats()
                 for e in srv._entries.values()]
        assert sum(n.get("fused", 0) for n in stats) > 0, \\
            f"fused plan never dispatched (D={srv.shape.mesh_shards})"
    print("PASS-A")

    # ---- B: cross-shard tie-break by global id, ulp-equal scores ----
    # duplicate t0 at positions 2, 7 and 11 (different shards on D=8);
    # querying t0's own column makes all four copies tie at the max score,
    # so the [D, k_max] combine must break the tie by global id.
    dup_tables = list(tables)
    for pos in (2, 7, 11):
        dup_tables[pos] = Table(keys=tables[0].keys, values=tables[0].values,
                                name=f"dup{pos}")
    cache_b = SV.CompileCache()
    srv1b, srv8b = make_servers(dup_tables, shape, cache_b)
    srv1b.warmup(modes=("off", "safe"))
    srv8b.warmup(modes=("off", "safe"))
    qsk = SV.build_query_sketches([tables[0].keys], [tables[0].values], n=N)
    for pm in ("off", "safe"):
        for srv in (srv1b, srv8b):
            req = PL.Request(k=4, prune=pm)
            s, g, r, m = (np.asarray(o)
                          for o in srv.query_batch(qsk, request=req))
            nd = srv.shape.mesh_shards
            assert g[0].tolist() == [0, 2, 7, 11], \\
                f"tie-break order broken (D={nd}, prune={pm}): {g[0]}"
            assert len(set(s[0].tolist())) == 1, \\
                f"duplicated columns not ulp-equal (D={nd}): {s[0]}"
    print("PASS-B")

    # ---- C: zero recompiles after warmup, across the whole sweep ----
    for nq in (1, 2):
        part = jax.tree.map(lambda a: a[:nq], sks)
        for sc, est, pm in combos:
            for k in (1, 4):
                req = PL.Request(k=k, scorer=sc, estimator=est, prune=pm)
                srv1.query_batch(part, request=req)
                srv8.query_batch(part, request=req)
    extra = cache.misses - misses0
    assert extra == 0, f"{extra} steady-state compiles after warmup"
    print("PASS-C")
"""


@functools.lru_cache(maxsize=None)
def _stdout():
    return _run(_BODY)


def test_sharded_bit_identical_all_combos():
    assert "PASS-A" in _stdout()


def test_cross_shard_topk_tie_break_by_global_id():
    assert "PASS-B" in _stdout()


def test_sharded_server_zero_steady_state_compiles():
    assert "PASS-C" in _stdout()
