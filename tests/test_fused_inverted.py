"""Device-resident fused inverted ``safe`` path (DESIGN.md §11).

The load-bearing assertions:

  * `postings_select` (Pallas interpret + XLA ref) implements the exact
    union-of-eligible-ids contract against a brute-force host oracle —
    ascending distinct ids, zero-padded to the static rung, overflow
    (``n_surv > M``) reported but never silently truncated away;
  * the fused probe→select→gather→score→rank plan returns **identical
    survivor sets and ids** to the legacy two-dispatch host-selected path
    (`dense_hit_counts` + `select_survivors` — the retained oracle) across
    scorers × estimators, with scores equal to ulp-level reassociation;
  * the rung-overflow retry adapts `_fused_rung` so steady state is ONE
    device dispatch per query, and the union outgrowing the ladder falls
    back to the (already warmed) full scan with identical results;
  * live mutation (append / delete / compact) through the fused path
    compiles nothing post-warmup — E/W/M all ride fixed ladders;
  * per-stage telemetry surfaces the dispatch mix (`throughput()["stages"]`
    / ``device_dispatches``), and survives segment retirement;
  * ``candidates="auto"`` resolves per segment by corpus size.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from conftest import given, settings, st  # hypothesis or deterministic fallback

from repro.data.pipeline import Table
from repro.engine import candidates as CD
from repro.engine import index as IX
from repro.engine import lifecycle as LC
from repro.engine import plans as PL
from repro.engine import serve as SV
from repro.kernels import ops as K
from repro.kernels import ref
from repro.kernels.ops import KernelConfig

from test_two_stage import _corpus, _queries, _superset_with_equal_scores

N_SKETCH = 32
#: one compile cache for the whole module (same discipline as test_plans)
CACHE = SV.CompileCache()


def _mesh():
    return jax.make_mesh((1,), ("shard",))


def _server(rng, *, n_tables=12, pad_to=None, buckets=(4,), **shape_kw):
    tables = _corpus(rng, n_tables=n_tables)
    idx = IX.build_index(tables, n=N_SKETCH, pad_to=pad_to or n_tables)
    shape_kw.setdefault("prune_base", 4)
    shape_kw.setdefault("candidates", "inverted")
    srv = SV.Server(_mesh(), idx, PL.ShapePolicy(k_max=5, **shape_kw),
                    buckets=buckets, cache=CACHE)
    return idx, srv


def _sketches(rng, nq=4):
    queries = _queries(rng, nq=nq)
    return SV.build_query_sketches([k for k, _ in queries],
                                   [v for _, v in queries], n=N_SKETCH)


def _exec(srv):
    return srv._entries[srv._order[0]].exec


# ---------------------------------------------------------------------------
# kernel: postings_select vs brute force
# ---------------------------------------------------------------------------

def _brute_select(cols, counts, floor, M):
    """Host oracle for the `postings_select` contract: the union across all
    rows of ids whose exact count clears the floor, ascending, padded."""
    elig = (cols >= 0) & (counts >= floor)
    ids = np.unique(cols[elig])
    n_surv = len(ids)
    surv = np.zeros(M, np.int32)
    take = min(n_surv, M)
    surv[:take] = ids[:take]
    valid = np.arange(M) < take
    return surv, valid, n_surv


@pytest.mark.parametrize("B,L,M,floor", [
    (1, 64, 8, 1.0),      # M < distinct ids likely → overflow exercised
    (4, 128, 32, 2.0),
    (7, 192, 64, 1.0),
    (2, 64, 256, 3.0),    # M > N = B·L → pad branch
    (3, 128, 16, 1e9),    # nothing eligible → n_surv == 0, all padding
])
def test_postings_select_ref_vs_interpret_vs_brute(rng, B, L, M, floor):
    cols = rng.integers(0, 40, size=(B, L)).astype(np.int32)
    cols[rng.random((B, L)) < 0.4] = -1
    # merged-row shape: each live id at most once per row (the
    # postings_merge contract postings_select consumes)
    for i in range(B):
        live = cols[i] >= 0
        _, first = np.unique(cols[i][live], return_index=True)
        keep = np.zeros(live.sum(), bool)
        keep[first] = True
        cols[i, np.flatnonzero(live)[~keep]] = -1
    counts = rng.integers(1, 5, size=(B, L)).astype(np.float32)
    counts[cols < 0] = 0.0

    want = _brute_select(cols, counts, floor, M)
    outs = {
        "ref": ref.postings_select(jnp.asarray(cols), jnp.asarray(counts),
                                   jnp.float32(floor), M),
        "interp": K.postings_select(jnp.asarray(cols), jnp.asarray(counts),
                                    jnp.float32(floor), M,
                                    KernelConfig(backend="interpret")),
    }
    for name, (surv, valid, n_surv) in outs.items():
        assert int(n_surv) == want[2], name
        np.testing.assert_array_equal(np.asarray(valid), want[1],
                                      err_msg=name)
        if want[2] <= M:
            np.testing.assert_array_equal(np.asarray(surv), want[0],
                                          err_msg=name)
        else:
            # overflow contract: the emitted survivors are the M smallest
            # eligible ids (still ascending/distinct), flagged by n_surv > M
            np.testing.assert_array_equal(np.asarray(surv), want[0],
                                          err_msg=name)


def test_postings_select_union_across_rows(rng):
    """An id eligible in ANY row survives — per-row counts may straddle the
    floor, the union semantics keep it (that is why the fused select serves
    only 'safe', never per-row top-M)."""
    cols = np.array([[3, 7, -1, -1], [3, 9, -1, -1]], np.int32)
    counts = np.array([[5.0, 1.0, 0, 0], [1.0, 4.0, 0, 0]], np.float32)
    for fn in (lambda: ref.postings_select(jnp.asarray(cols),
                                           jnp.asarray(counts),
                                           jnp.float32(2.0), 4),
               lambda: K.postings_select(jnp.asarray(cols),
                                         jnp.asarray(counts),
                                         jnp.float32(2.0), 4,
                                         KernelConfig(backend="interpret"))):
        surv, valid, n_surv = fn()
        assert int(n_surv) == 2
        np.testing.assert_array_equal(np.asarray(surv), [3, 9, 0, 0])
        np.testing.assert_array_equal(np.asarray(valid),
                                      [True, True, False, False])


def test_postings_select_matches_dense_oracle(rng):
    """End-to-end stage-1 oracle chain: device select over merged postings
    equals host `select_survivors` over the `dense_hit_counts` scatter of
    the same merged output."""
    cand = rng.integers(0, 20, size=(3, 128)).astype(np.int32)
    cand[rng.random((3, 128)) < 0.6] = -1
    mcols, mcnt = ref.postings_merge(jnp.asarray(cand))
    floor = 2.0
    surv, valid, n_surv = ref.postings_select(mcols, mcnt,
                                              jnp.float32(floor), 32)
    hits = CD.dense_hit_counts(np.asarray(mcols), np.asarray(mcnt), 20)
    want = PL.select_survivors(hits, prune="safe", min_sample=int(floor))
    got = np.asarray(surv)[np.asarray(valid)]
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# fused path == host-selected path
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**28),
       scorer=st.sampled_from(["s1", "s2", "s4"]),
       estimator=st.sampled_from(["pearson", "spearman"]))
def test_fused_matches_host_selected_path(seed, scorer, estimator):
    """THE §11 contract: flipping `fused_safe` must not change the answer —
    identical survivor sets (the hit counts are exact and shared), so
    identical ids and m, scores equal to ulp-level reassociation. pad_to=32
    keeps the rung ladder tall enough for genuine fused successes; the
    12-column default in other tests exercises the scan fallback."""
    rng = np.random.default_rng(seed)
    idx, srv = _server(rng, pad_to=32)
    sks = _sketches(rng, nq=4)
    req = PL.Request(k=5, scorer=scorer, estimator=estimator, prune="safe")
    ex = _exec(srv)
    assert ex.fused_safe
    fused = srv.query_batch(sks, request=req)
    ex.fused_safe = False
    try:
        legacy = srv.query_batch(sks, request=req)
    finally:
        ex.fused_safe = True
    np.testing.assert_array_equal(fused[1], legacy[1])   # ids
    np.testing.assert_array_equal(fused[3], legacy[3])   # m (exact counts)
    np.testing.assert_allclose(fused[0], legacy[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(fused[2], legacy[2], rtol=2e-5, atol=2e-5)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**28),
       scorer=st.sampled_from(["s2", "s4"]))
def test_fused_safe_never_drops_topk(seed, scorer):
    """The PR 4 never-drops-top-k contract carried through the fused plan:
    fused 'safe' results are a superset of the full scan's finite top-k."""
    rng = np.random.default_rng(seed)
    idx, srv = _server(rng, pad_to=32)
    sks = _sketches(rng, nq=4)
    req = PL.Request(k=5, scorer=scorer)
    full = srv.query_batch(sks, request=dataclasses.replace(
        req, prune="off"))
    safe = srv.query_batch(sks, request=dataclasses.replace(
        req, prune="safe"))
    _superset_with_equal_scores(full, safe)


def test_fused_rung_adaptation_single_steady_dispatch(rng):
    """First dispatch may overflow the seeded base rung and retry at the
    exact covering rung; the adapted `_fused_rung` makes every subsequent
    identical query a SINGLE device dispatch."""
    idx, srv = _server(rng, pad_to=32, prune_base=4)
    sks = _sketches(rng, nq=4)
    ex = _exec(srv)
    rungs = ex.prune_rungs()
    assert len(rungs) >= 2, rungs       # ladder tall enough to adapt within
    req = PL.Request(k=5, scorer="s2", prune="safe")
    srv.query_batch(sks, request=req)   # adaptation call (may retry once)
    n0 = ex.stage_stats()
    srv.query_batch(sks, request=req)
    n1 = ex.stage_stats()
    assert n1.get("fused", 0) - n0.get("fused", 0) == 1, (n0, n1)
    assert n1.get("stage1", 0) == n0.get("stage1", 0)    # no dense probe
    assert n1.get("stage2", 0) == n0.get("stage2", 0)    # no second launch
    assert n1.get("scan", 0) == n0.get("scan", 0)
    with ex._res_lock:
        assert ex._fused_rung in rungs


def test_fused_ladder_overflow_falls_back_to_scan(rng):
    """A survivor union wider than every rung ends in the full-scan
    fallback — same results, 'scan' counted in the stage telemetry."""
    # pad_to == n_tables: the tallest rung (8) sits below the ~9 joinable
    # columns every query touches, so the ladder can never cover the union
    idx, srv = _server(rng, n_tables=12, pad_to=12, prune_base=4)
    sks = _sketches(rng, nq=4)
    req = PL.Request(k=5, scorer="s2", prune="safe")
    ex = _exec(srv)
    fused = srv.query_batch(sks, request=req)
    n = ex.stage_stats()
    assert n.get("scan", 0) >= 1, n
    ex.fused_safe = False
    try:
        legacy = srv.query_batch(sks, request=req)
    finally:
        ex.fused_safe = True
    np.testing.assert_array_equal(fused[1], legacy[1])
    np.testing.assert_allclose(fused[0], legacy[0], rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# lifecycle: zero compiles through the fused path
# ---------------------------------------------------------------------------

def test_fused_live_mutation_zero_compiles(rng):
    """Post-warmup, a mutation sweep served entirely through the fused
    'safe' path compiles nothing: postings capacity (E), window (W) and
    survivor rung (M) all ride fixed ladders, warmed one rung ahead."""
    tables = _corpus(rng, n_tables=5)
    live = LC.LiveIndex(n=N_SKETCH, delta_cap=8)
    live.append(tables)
    srv = SV.Server(_mesh(), live,
                    PL.ShapePolicy(k_max=4, prune_base=2,
                                   candidates="inverted"),
                    buckets=(4,), cache=SV.CompileCache())
    srv.warmup(modes=("off", "safe"), include_ladder=True)
    sks = _sketches(rng, nq=3)
    misses = srv.cache.misses
    for step in range(3):
        m = int(rng.integers(64, 400))
        live.append([Table(
            keys=rng.choice(2000, size=m, replace=False).astype(np.uint32),
            values=rng.standard_normal(m).astype(np.float32),
            name=f"x{step}")])
        live.delete(f"t{step}")
        srv.query_batch(sks, request=PL.Request(k=4, prune="safe"))
    live.compact()
    srv.query_batch(sks, request=PL.Request(k=4, prune="safe"))
    assert srv.cache.misses == misses, "fused serve must not compile"
    tp = srv.throughput()
    assert tp["stages"].get("fused", {}).get("count", 0) >= 1
    assert tp["stages"].get("stage1", {}).get("count", 0) == 0


# ---------------------------------------------------------------------------
# satellite: per-stage serving telemetry
# ---------------------------------------------------------------------------

def test_stage_telemetry_shape_and_aggregation(rng):
    idx, srv = _server(rng, pad_to=32)
    sks = _sketches(rng, nq=4)
    srv.query_batch(sks, request=PL.Request(k=5, prune="safe"))
    srv.query_batch(sks, request=PL.Request(k=5, prune="off"))
    tp = srv.throughput()
    assert set(tp["stages"]) <= set(SV._STAGE_NAMES)
    for rec in tp["stages"].values():
        assert set(rec) == {"count"} and rec["count"] >= 1
    assert tp["stages"]["fused"]["count"] >= 1
    assert tp["stages"]["scan"]["count"] >= 1          # the prune='off' call
    # device_dispatches counts device launches only — host-side select and
    # combine windows are excluded
    want = sum(tp["stages"].get(s, {"count": 0})["count"]
               for s in SV._DEVICE_STAGES)
    assert tp["device_dispatches"] == want
    ex = _exec(srv)
    assert ex.stage_stats() == {name: rec["count"]
                                for name, rec in tp["stages"].items()}


def test_scored_rows_count_rung_and_scan_width(rng, monkeypatch):
    """``throughput()["scored_rows"]`` counts, by estimator, the candidate
    rows each dispatch hands to scoring: bucket width × the survivor rung
    of every fused dispatch, bucket width × the padded column count of
    every scan."""
    idx, srv = _server(rng, pad_to=32)
    ex = _exec(srv)
    rungs = []
    fused_fn = ex.inverted_fused_fn
    monkeypatch.setattr(ex, "inverted_fused_fn", lambda B, M, W: (
        rungs.append((B, M)) or fused_fn(B, M, W)))
    sks = _sketches(rng, nq=3)
    assert srv.throughput()["scored_rows"] == {}
    srv.query_batch(sks, request=PL.Request(k=5, prune="safe",
                                            estimator="spearman"))
    st = srv.throughput()["stages"]
    scans = st.get("scan", {}).get("count", 0)
    assert rungs and st["fused"]["count"] == len(rungs)
    want = sum(B * M for B, M in rungs) + scans * 4 * ex.C
    assert srv.throughput()["scored_rows"] == {"spearman": want}
    srv.query_batch(sks, request=PL.Request(k=5, prune="off"))
    assert ex.C == 32
    assert srv.throughput()["scored_rows"] == {"spearman": want,
                                               "pearson": 4 * 32}
    assert ex.scored_rows() == srv.throughput()["scored_rows"]


def test_rank_tiles_count_live_pair_tiles(rng):
    """``throughput()["rank_tiles"]`` reports, by estimator, the pair tiles
    the Pallas `rank_moments` computed against those its grid covers.
    One column of 32 shares the query's keys and the rest are disjoint, so
    in the scan's ``[B·C, n]`` hit plane only rows ``b·32 + 5`` carry
    weight: one live 8-row block per bucket row, one pair tile each at
    n = 32, of the 4·32/8 = 16 the grid covers. Pearson never reaches the
    kernel and gets no entry."""
    keys = rng.choice(1 << 20, size=200, replace=False).astype(np.uint32)
    tables = [Table(keys=(keys if c == 5 else
                          rng.choice(1 << 20, size=200, replace=False)
                          .astype(np.uint32) + np.uint32(1 << 21)),
                    values=rng.standard_normal(200).astype(np.float32),
                    name=f"t{c}") for c in range(32)]
    idx = IX.build_index(tables, n=N_SKETCH, pad_to=32)
    srv = SV.Server(_mesh(), idx,
                    PL.ShapePolicy(k_max=5,
                                   kernels=KernelConfig("interpret")),
                    buckets=(4,), cache=CACHE)
    sks = SV.build_query_sketches(
        [keys], [rng.standard_normal(200).astype(np.float32)], n=N_SKETCH)
    assert srv.throughput()["rank_tiles"] == {}
    s, g, _, _ = srv.query_batch(sks, request=PL.Request(
        k=5, prune="off", estimator="spearman"))
    assert int(np.asarray(g)[0, 0]) == 5
    live, total = srv.throughput()["rank_tiles"]["spearman"]
    assert (live, total) == (4, 16) and live < total
    srv.query_batch(sks, request=PL.Request(k=5, prune="off"))
    assert srv.throughput()["rank_tiles"] == {"spearman": [4, 16]}
    assert _exec(srv).rank_tiles() == {"spearman": [4, 16]}


def test_stage_telemetry_survives_segment_retirement(rng):
    """Stage totals from retired segment executors fold into the server
    aggregate (same discipline as the retired dispatch counters)."""
    tables = _corpus(rng, n_tables=5)
    live = LC.LiveIndex(n=N_SKETCH, delta_cap=8)
    live.append(tables)
    srv = SV.Server(_mesh(), live,
                    PL.ShapePolicy(k_max=4, prune_base=2,
                                   candidates="inverted"),
                    buckets=(4,), cache=SV.CompileCache())
    sks = _sketches(rng, nq=3)
    srv.query_batch(sks, request=PL.Request(k=4, prune="safe"))
    before = srv.throughput()["stages"]
    n_before = sum(rec["count"] for rec in before.values())
    rows_before = srv.throughput()["scored_rows"]
    live.compact()                      # retires every live executor
    srv.refresh()
    after = srv.throughput()["stages"]
    n_after = sum(rec["count"] for rec in after.values())
    assert n_after >= n_before > 0, (before, after)
    assert rows_before["pearson"] > 0
    assert srv.throughput()["scored_rows"] == rows_before


# ---------------------------------------------------------------------------
# satellite: candidates="auto"
# ---------------------------------------------------------------------------

def test_resolve_candidates_unit():
    assert PL.resolve_candidates("scan", 10**6) == "scan"
    assert PL.resolve_candidates("inverted", 1) == "inverted"
    lo, hi = PL.AUTO_INVERTED_MIN_C - 1, PL.AUTO_INVERTED_MIN_C
    assert PL.resolve_candidates("auto", lo) == "scan"
    assert PL.resolve_candidates("auto", hi) == "inverted"
    with pytest.raises(ValueError, match="unknown candidate source"):
        PL.resolve_candidates("btree", 100)
    with pytest.raises(ValueError, match="unknown candidate source"):
        PL.resolve_shape(PL.ShapePolicy(candidates="btree"), _mesh())
    # without a corpus size, "auto" is validated but kept (facade level)
    shape = PL.resolve_shape(PL.ShapePolicy(candidates="auto"), _mesh())
    assert shape.candidates == "auto"


def test_auto_resolves_per_segment(rng, monkeypatch):
    """A server built with candidates='auto' stamps each segment executor
    with the per-corpus-size winner; the threshold is the BENCH_scaling
    crossover (monkeypatched here so a toy corpus crosses it)."""
    idx, srv = _server(rng, pad_to=32, candidates="auto")
    assert srv.shape.candidates == "auto"               # facade keeps auto
    assert _exec(srv).shape.candidates == "scan"        # 32 < threshold
    monkeypatch.setattr(PL, "AUTO_INVERTED_MIN_C", 16)
    idx2, srv2 = _server(rng, pad_to=32, candidates="auto",
                         buckets=(2,))
    assert _exec(srv2).shape.candidates == "inverted"   # 32 >= 16
    sks = _sketches(rng, nq=2)
    out = srv2.query_batch(sks, request=PL.Request(k=5, prune="safe"))
    assert np.asarray(out[0]).shape == (2, 5)
    n = _exec(srv2).stage_stats()
    assert n.get("fused", 0) + n.get("scan", 0) >= 1
