"""The `sbn-n1024` cells' harness pieces: their per-layer readers on a
fabricated trace record, and the bfloat16 control of their check, which
must come out not correct on every seed."""
import pytest

import run
from test_metrics import REC

CELLS = ("sbn-n1024.safe-spearman.open80", "sbn-n1024.scan-spearman.closed8")
#: ms per completed query in `REC`: its trace holds 0.1 s of
#: ``%rank_moments.3`` and 0.4 s of ``%vmap_jit_sketch_join_moments__.2``
#: over 4 queries
WANT = dict(rank_dev_ms=25.0, join_dev_ms=100.0)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_the_trace(name):
    assert run.read_metric(name, REC) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_is_silent_without_its_kernel(name):
    assert run.read_metric(name, dict(REC, trace=None)) is None
    other = dict(REC["trace"], op_seconds={
        "%postings_merge.1 = (s32[1,65536]) custom-call()": 1.0})
    assert run.read_metric(name, dict(REC, trace=other)) is None


@pytest.mark.parametrize("cell", CELLS)
def test_cell_lists_its_readers(cell):
    c = run.load_cell(cell)
    assert {m["name"] for m in c.per_layer} == set(WANT)
    assert c.config["n"] == 1024
    assert c.traffic["request"]["estimator"] == "spearman"


@pytest.mark.parametrize("seed", [5, 6, 7])
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_on_every_seed(cell, seed):
    import control
    c = run.load_cell(cell)
    c.config.update(tables=24, rows_min=256, rows_max=1500, n=64,
                    append_batch=8)
    c.traffic.update(pool=8)
    got = control.control_numbers(c, seed, 2.0)
    assert got["requests"] > 0 and not got["correct"], got
