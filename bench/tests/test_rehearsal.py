"""CPU rehearsal of a whole cell at a tiny size, with the Pallas kernels in
interpret mode: corpus, ingest, server, scheduler, load generator, the
reference check and the result line. Also the check's control and the
faults it must catch, and the command's refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import ml_dtypes
import numpy as np
import pytest

import run

CELL = "sbn-n256.safe-pearson.open80"
SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def cell():
    c = run.load_cell(CELL)
    c.config.update(tables=24, rows_min=256, rows_max=1500, n=64,
                    append_batch=8, query_chunk=512)
    c.traffic.update(pool=8, rate_qps=8.0)
    return c


@pytest.fixture(scope="module")
def ready(cell):
    import jax
    from repro.engine import plans as PL
    from repro.kernels.ops import KernelConfig
    policy = PL.ShapePolicy(candidates="inverted",
                            kernels=KernelConfig("interpret"))
    s = run.setup(cell, SEED, jax.devices()[0], policy)
    yield s
    s.sched.close()


def _device():
    return dict(platform="cpu", kind="cpu", count=1, memory_peak_bytes=0)


def test_window_agrees_with_reference(ready, cell):
    rec = run.window(ready, SEED, 1.5, trace=False)
    assert len(rec["requests"]) == 12
    correct, checks = run.judge(ready, rec)
    assert correct, checks
    assert checks["m_gap"]["value"] == 0 and checks["lost"]["value"] == 0
    assert rec["compiles"] == dict(cache_misses=0, lowerings=0)
    out = run.assemble(cell, rec, _device(), correct, checks, trace=False)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(out["metrics"]) == {"setup_s", "p50_ms", "qps"}
    assert out["failed"] == 0 and out["attempted"] == 12
    json.loads(json.dumps(out))


def test_run_cell_reports_where_its_time_went(cell, capsys):
    import jax
    from repro.engine import plans as PL
    from repro.kernels.ops import KernelConfig
    policy = PL.ShapePolicy(candidates="inverted",
                            kernels=KernelConfig("interpret"))
    out = run.run_cell(cell, SEED + 9, 0.5, False, jax.devices(), policy)
    assert out["correct"] and out["attempted"] == 4
    err = capsys.readouterr().err
    assert "bench: set-up" in err and "reference check" in err


def test_traced_window_reports_per_layer_metrics(ready, cell):
    rec = run.window(ready, SEED + 1, 1.0, trace=True)
    correct, checks = run.judge(ready, rec)
    assert correct, checks
    out = run.assemble(cell, rec, _device(), correct, checks, trace=True)
    assert list(out)[-2:] == ["breakdown", "checks"]
    names = {m["name"] for m in cell.per_layer}
    # the CPU has no device plane: the trace's metrics find nothing to read
    assert set(out["metrics"]) == names - {"cand_dev_ms", "score_dev_ms",
                                            "device_idle_share",
                                            "device_idle_inflight"}
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert out["device"]["window_s"] > 0


def test_control_in_bfloat16_is_not_correct(ready):
    rec = run.window(ready, SEED + 2, 1.0, trace=False)
    low = run.reference_answers(ready.lake, ready.queries, ready.cell.config,
                                ready.request,
                                [r["query"] for r in rec["requests"]],
                                dtype=ml_dtypes.bfloat16)
    correct, checks = run.judge(ready, rec, answers=low)
    assert not correct
    assert checks["score_gap"]["value"] > checks["score_gap"]["limit"]


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_control_script_fails_on_every_seed(cell, seed):
    import control
    got = control.control_numbers(cell, seed, 2.0)
    assert got["requests"] == 16 and not got["correct"], got


def _alter_top_id(out):
    s, g, r, m = out
    g = g.copy()
    g[:, 0] = np.where(g[:, 0] >= 0, (g[:, 0] + 1) % 24, g[:, 0])
    return s, g, r, m


def _perturb_scores(out):
    s, g, r, m = out
    return s + np.float32(1e-2), g, r, m


def _drop_half_of_batch(out):
    s, g, r, m = (np.array(a) for a in out)
    h = -(-s.shape[0] // 2)
    s[:h], g[:h], r[:h], m[:h] = -np.inf, -1, 0, 0
    return s, g, r, m


def _fail(out):
    raise RuntimeError("dispatch failed")


@pytest.mark.parametrize("fault", [_alter_top_id, _perturb_scores,
                                   _drop_half_of_batch, _fail])
def test_faults_in_the_timed_path_are_not_correct(ready, fault, monkeypatch):
    served = ready.srv.query_batch
    monkeypatch.setattr(ready.srv, "query_batch",
                        lambda *a, **kw: fault(served(*a, **kw)))
    rec = run.window(ready, SEED + 3, 1.0, trace=False)
    correct, checks = run.judge(ready, rec)
    assert not correct, checks


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu_or_the_program(tmp_path):
    root = run.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = _run(root, env)
    assert got.returncode != 0 and got.stdout.strip() == ""
    assert "no TPU" in got.stderr
    # a directory with only BENCHMARK.json and the benchmark's files
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "_out"))
    got = _run(tmp_path, env)
    assert got.returncode != 0 and got.stdout.strip() == ""
