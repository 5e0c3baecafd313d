"""The benchmark's data lake and query pool, generated from ``--seed``.

The lake follows the paper's Synthetic Bivariate Normal corpus (SBN,
Santos et al., SIGMOD'21, arXiv:2104.03353, Sec. 5.1): a collection of
table pairs ⟨K, X⟩, ⟨K, Y⟩. `sbn_pair` is a copy of
`repro.data.pipeline.sbn_pair` (with the lower end of the row range as a
parameter; 256 there): the same random stream gives the same pair, so
`bench/tests/test_corpus.py` pins the copy to its original. The copy lives
here so that the yardstick does not move when the program's generator
does.

A pair has n rows, n uniform in [rows_min, rows_max), with unique keys
drawn from ``key_space``; (x, y) is bivariate normal with correlation r
uniform in (-1, 1); Y keeps a uniform subsample of the rows, a share c
uniform in [0.05, 1) of them (the join probability). The lake indexes the
Y tables, one numeric column each; a query is the X table of a pair, whose
keys join its own Y table and, by chance, a few keys of others.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Table:
    """⟨K, X⟩ column pair (the fields of `repro.data.pipeline.Table`)."""
    keys: np.ndarray     # [m] uint32
    values: np.ndarray   # [m] float32
    name: str = ""
    meta: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Group:
    """One lake table: a key column and its numeric columns (the fields of
    `repro.data.pipeline.TableGroup`)."""
    keys: np.ndarray             # [m] uint32
    values: np.ndarray           # [C, m] float32
    name: str = ""
    column_names: List[str] = dataclasses.field(default_factory=list)


def sbn_pair(rng, n_max: int = 500_000, r: Optional[float] = None,
             key_space: int = 1 << 30, n_min: int = 256):
    """One SBN table pair: (T_X, T_Y, r, c)."""
    n = int(rng.integers(n_min, n_max))
    r = float(rng.uniform(-1, 1)) if r is None else r
    keys = rng.choice(key_space, size=n, replace=False).astype(np.uint32)
    cov = np.array([[1.0, r], [r, 1.0]])
    xy = rng.multivariate_normal([0.0, 0.0], cov, size=n).astype(np.float32)
    c = float(rng.uniform(0.05, 1.0))
    m = max(int(n * c), 8)
    sel = rng.choice(n, size=m, replace=False)
    tx = Table(keys=keys, values=xy[:, 0], name="X", meta={"r": r})
    ty = Table(keys=keys[sel], values=xy[sel, 1], name="Y",
               meta={"r": r, "c": c})
    return tx, ty, r, c


@dataclasses.dataclass
class Lake:
    """The raw lake tables (kept on the host for the reference) and the
    query pool: the (keys, values) of X tables, in pair order."""
    tables: List[Group]
    pool: List[Tuple[np.ndarray, np.ndarray]]


def generate(gen: dict, pool: int, rng, on_batch=None) -> Lake:
    """Generate ``gen['tables']`` SBN pairs; the lake gets each Y table, in
    batches of ``gen['append_batch']`` (handed to ``on_batch`` as they are
    made, as `LiveIndex.append` takes them), and the pool the X tables of
    ``pool`` pairs picked first."""
    pairs = int(gen["tables"])
    picks = set(int(i) for i in rng.choice(pairs, size=pool, replace=False))
    tables, pool_xs = [], []
    step = int(gen["append_batch"])
    for start in range(0, pairs, step):
        batch = []
        for i in range(start, min(start + step, pairs)):
            tx, ty, _, _ = sbn_pair(rng, n_max=int(gen["rows_max"]),
                                    n_min=int(gen["rows_min"]),
                                    key_space=int(gen["key_space"]))
            batch.append(Group(keys=ty.keys, values=ty.values[None, :],
                               name=f"p{i}", column_names=[f"p{i}.y"]))
            if i in picks:
                pool_xs.append((tx.keys, tx.values))
        if on_batch is not None:
            on_batch(batch)
        tables.extend(batch)
    return Lake(tables, pool_xs)
