"""`bench/corpus.py` reproduces the generator it was copied from exactly
for a fixed seed, and its lake is that generator's pairs."""
import numpy as np
import pytest

import corpus


def _same_table(a, b):
    assert np.array_equal(a.keys, b.keys)
    assert np.array_equal(a.values, b.values, equal_nan=True)
    assert a.name == b.name and a.meta == b.meta


@pytest.mark.parametrize("kw", [dict(n_max=900), dict(n_max=70_000),
                                dict(n_max=5000, r=0.5, key_space=1 << 20)])
def test_sbn_pair_matches_pipeline(kw):
    from repro.data.pipeline import sbn_pair
    want = sbn_pair(np.random.default_rng(7), **kw)
    got = corpus.sbn_pair(np.random.default_rng(7), **kw)
    _same_table(want[0], got[0])
    _same_table(want[1], got[1])
    assert want[2:] == got[2:]


def test_lake_holds_the_pairs_y_tables_and_pools_their_x_tables():
    from repro.data.pipeline import sbn_pair
    gen = dict(tables=10, rows_min=256, rows_max=3000, key_space=1 << 30,
               append_batch=3)
    batches = []
    lake = corpus.generate(gen, 4, np.random.default_rng(99),
                           on_batch=batches.append)
    assert [len(b) for b in batches] == [3, 3, 3, 1]
    rng = np.random.default_rng(99)
    picks = sorted(int(i) for i in rng.choice(10, size=4, replace=False))
    assert len(lake.pool) == 4
    pool = dict(zip(picks, lake.pool))
    for i, t in enumerate(lake.tables):
        tx, ty, _, _ = sbn_pair(rng, n_max=3000)
        assert np.array_equal(t.keys, ty.keys)
        assert np.array_equal(t.values, ty.values[None, :])
        assert t.column_names == [f"p{i}.y"]
        if i in pool:
            assert np.array_equal(pool[i][0], tx.keys)
            assert np.array_equal(pool[i][1], tx.values)
