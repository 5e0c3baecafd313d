"""Each per-layer metric's reader on a fabricated run record."""
import pytest

import run

REC = dict(
    loop="open", window_s=10.0,
    requests=[dict(query=0, due=0.0, send=0.002, sketch_s=0.010, done=0.1),
              dict(query=1, due=1.0, send=1.010, sketch_s=0.030, done=1.2),
              dict(query=2, due=2.0, send=2.000, sketch_s=0.020, done=None)],
    scheduler=dict(completed=6, batches=4),
    server=dict(queries=6, device_dispatches=9),
    compiles=dict(cache_misses=1, lowerings=2),
    setup=dict(ingest_s=120.5, warmup_s=40.25, setup_s=170.0),
    trace=dict(busy_s=2.0, window_s=8.0, inflight_s=4.0,
               busy_inflight_s=1.0, queries=4,
               op_seconds={
                   "%postings_select.1 = s32[1,65536] custom-call(%jit_postings_merge_.4)": 0.8,
                   "%postings_merge.1 = (s32[1,65536]) custom-call(%and_select_fusion)": 0.2,
                   "%vmap_jit_sketch_join_moments__.2 = (f32[64,8]) custom-call()": 0.4,
                   "%rank_moments.3 = f32[64,8] custom-call()": 0.1,
                   "%fusion.7 = f32[8] fusion(%jit_postings_merge_.5)": 0.5}))

WANT = dict(gen_lag_p95_ms=9.2, sketch_build_ms=20.0, coalesce=1.5,
            dispatches_per_query=1.5, window_compiles=3.0,
            cand_dev_ms=250.0, score_dev_ms=125.0, device_idle_share=0.75,
            device_idle_inflight=0.75, ingest_s=120.5, warmup_s=40.25)


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_the_record(name):
    assert run.read_metric(name, REC) == pytest.approx(WANT[name])


def test_every_listed_metric_has_a_reader():
    cell = run.load_cell("sbn-n256.safe-pearson.open80")
    assert {m["name"] for m in cell.per_layer} == set(WANT)


@pytest.mark.parametrize("name", ["cand_dev_ms", "score_dev_ms",
                                  "device_idle_share",
                                  "device_idle_inflight"])
def test_trace_metrics_are_silent_without_a_trace(name):
    assert run.read_metric(name, dict(REC, trace=None)) is None
    if name.endswith("_dev_ms"):
        empty = dict(REC["trace"], op_seconds={"%fusion.1 = f32[8] fusion(%jit_postings_merge_.5)": 1.0})
        assert run.read_metric(name, dict(REC, trace=empty)) is None


def test_gen_lag_is_silent_in_a_closed_loop():
    assert run.read_metric("gen_lag_p95_ms", dict(REC, loop="closed")) is None


def test_end_to_end_counts_a_lost_request_as_slowest():
    e = run.end_to_end(REC)
    assert e["qps"] == pytest.approx(0.2)
    assert e["setup_s"] == 170.0
    assert e["p50_ms"] == pytest.approx(200.0)
    assert e["p95_ms"] > 60_000


def test_peaks_know_the_v5e_and_refuse_an_unknown_chip():
    got = run.chip_peaks("TPU v5 lite")
    assert got["bf16_flop_per_s"] == 197e12 and got["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.CellError):
        run.chip_peaks("TPU v9 imaginary")
