"""Plain reference for join-correlation top-k queries over a generated lake.

Works from the raw tables alone, in numpy, imports nothing of the program
and takes nothing it made. It follows the paper's definitions (Santos et
al., SIGMOD'21, arXiv:2104.03353):

* key hash h(k): MurmurHash3-32 of the 4-byte key, seed 0x9747B28C; order
  hash h_u: h(k) · 2654435769 mod 2^32 (Fibonacci hashing), §3.1;
* a column's sketch keeps, among its rows with a finite value, the ``n``
  distinct keys of smallest h_u, each with the MEAN of its values; the two
  hash values that pad sketches (0xFFFFFFFF as h(k) or as h_u) never enter
  one. The column's min and max are taken over all its finite values;
* the sketch join of a query with a column is the set of keys in both
  sketches; its size m is the sample size;
* estimators (§5.3): Pearson over population moments, Spearman over
  average ranks, RIN over rankits, Qn correlation (Shevlyakov–Oja);
* confidence interval (§4.3): the five Hoeffding intervals over the join
  sample shifted into [0, C], C = max(X ∪ Y) − min(X ∪ Y), with the ρ_HFD
  fallback to the sample denominator when a variance bound vanishes;
* scorers (§4.4): s1 = |r|, s2 = |r|·(1 − 1/sqrt(max(m, 4) − 3)),
  s4 = |r|·(1 − (len − min)/(max − min)) over the CI lengths of the
  eligible candidates; a candidate with m below ``min_sample`` is not
  eligible and scores −inf;
* top-k: score descending, then column name in catalog order.

``dtype`` sets the precision of the arithmetic after the join (float64 for
the reference; bfloat16 makes the control of `bench/check.py`).
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

_SEED = np.uint32(0x9747B28C)
_FIB = np.uint32(2654435769)
_PAD = np.uint32(0xFFFFFFFF)


def murmur3_u32(keys: np.ndarray) -> np.ndarray:
    """MurmurHash3-32 of each uint32 key as one 4-byte block."""
    k = np.asarray(keys, np.uint32)
    with np.errstate(over="ignore"):
        k = k * np.uint32(0xCC9E2D51)
        k = (k << np.uint32(15)) | (k >> np.uint32(17))
        k = k * np.uint32(0x1B873593)
        h = np.full(k.shape, _SEED, np.uint32) ^ k
        h = (h << np.uint32(13)) | (h >> np.uint32(19))
        h = h * np.uint32(5) + np.uint32(0xE6546B64)
        h = h ^ np.uint32(4)
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
        h = h ^ (h >> np.uint32(16))
    return h


def fib_order(h: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        return (h * _FIB).astype(np.uint32)


@dataclasses.dataclass
class Sketch:
    """A column's sketch: key hashes (ascending h_u), MEAN values, and the
    min and max of the whole column."""
    h: np.ndarray
    v: np.ndarray
    lo: float
    hi: float


def sketch(h: np.ndarray, values: np.ndarray, n: int) -> Sketch:
    """Sketch one column from its rows' key hashes and values."""
    values = np.asarray(values, np.float64)
    fin = np.isfinite(values)
    lo = float(values[fin].min()) if fin.any() else np.inf
    hi = float(values[fin].max()) if fin.any() else -np.inf
    ok = fin & (h != _PAD) & (fib_order(h) != _PAD)
    uh, inv = np.unique(h[ok], return_inverse=True)
    sums = np.bincount(inv, weights=values[ok], minlength=uh.size)
    cnts = np.bincount(inv, minlength=uh.size)
    order = np.argsort(fib_order(uh), kind="stable")[:n]
    return Sketch(uh[order], sums[order] / cnts[order], lo, hi)


# ---------------------------------------------------------------------------
# estimators, confidence interval, scorers
# ---------------------------------------------------------------------------

def _pearson(a, b):
    m = a.size
    if m < 2:
        return 0.0
    one = a.dtype.type(1) if hasattr(a.dtype, "type") else 1.0
    mu_a, mu_b = a.mean(), b.mean()
    va = max((a * a).mean() - mu_a * mu_a, 0 * one)
    vb = max((b * b).mean() - mu_b * mu_b, 0 * one)
    den = np.sqrt(va) * np.sqrt(vb)
    if not float(den) > 1e-12:
        return 0.0
    return float(((a * b).mean() - mu_a * mu_b) / den)


def _avg_ranks(x):
    from scipy.stats import rankdata
    return rankdata(x, method="average")


def _ndtri(p):
    from scipy.special import ndtri
    return ndtri(p)


def _qn_scale(x) -> float:
    m = x.size
    d = np.sort(np.abs(x[:, None] - x[None, :])[np.triu_indices(m, 1)])
    h = m // 2 + 1
    kq = max(h * (h - 1) // 2, 1)
    return 2.21914 * float(d[kq - 1]) if kq <= d.size else 0.0


def estimate(a, b, estimator: str, dtype=np.float64) -> float:
    """Correlation of a joined sample under ``estimator``, computed in
    ``dtype``."""
    cast = lambda x: np.asarray(x, np.float64).astype(dtype)
    if estimator == "pearson":
        return _pearson(cast(a), cast(b))
    if estimator in ("spearman", "rin"):
        ra, rb = _avg_ranks(np.asarray(a)), _avg_ranks(np.asarray(b))
        if estimator == "rin":
            q = lambda r: _ndtri(np.clip((r - 0.5) / r.size, 1e-6, 1 - 1e-6))
            ra, rb = q(ra), q(rb)
        return _pearson(cast(ra), cast(rb))
    if estimator == "qn":
        a, b = cast(a), cast(b)
        sa, sb = _qn_scale(a), _qn_scale(b)
        if not (sa > 1e-12 and sb > 1e-12):
            return 0.0
        u = (a / sa + b / sb) / np.sqrt(2.0)
        v = (a / sa - b / sb) / np.sqrt(2.0)
        qu, qv = _qn_scale(u), _qn_scale(v)
        den = qu * qu + qv * qv
        return float(np.clip((qu * qu - qv * qv) / den, -1, 1)) \
            if den > 1e-12 else 0.0
    raise ValueError(f"unknown estimator {estimator!r}")


def ci_length(a, b, lo: float, hi: float, alpha: float,
              dtype=np.float64) -> float:
    """Length of the §4.3 Hoeffding interval (ρ_HFD) of a joined sample
    whose columns span [lo, hi] together."""
    m = a.size
    if m < 2:
        return np.inf
    f = lambda x: np.asarray(x, np.float64).astype(dtype)
    a0, b0 = f(np.asarray(a) - lo), f(np.asarray(b) - lo)
    C = max(hi - lo, 1e-30)
    mu_a, mu_b = float(a0.mean()), float(b0.mean())
    va, vb, vab = (float((a0 * a0).mean()), float((b0 * b0).mean()),
                   float((a0 * b0).mean()))
    log_term = np.log(10.0 / alpha)
    t = np.sqrt(log_term * C * C / (2.0 * m))
    tp = np.sqrt(log_term * C ** 4 / (2.0 * m))
    num_lo = (vab - tp) - (mu_a + t) * (mu_b + t)
    num_hi = (vab + tp) - (mu_a - t) * (mu_b - t)
    den_lo = np.sqrt(max(0.0, (va - tp) - (mu_a + t) ** 2)
                     * max(0.0, (vb - tp) - (mu_b + t) ** 2))
    den_hi = np.sqrt(max(0.0, (va + tp) - (mu_a - t) ** 2)
                     * max(0.0, (vb + tp) - (mu_b - t) ** 2))
    if den_lo <= 1e-30 or den_hi <= 1e-30:
        den_lo = den_hi = np.sqrt(max(va - mu_a ** 2, 0.0)
                                  * max(vb - mu_b ** 2, 0.0))
    div = lambda x, d: x / max(d, 1e-30)
    ci_lo = div(num_lo, den_hi) if num_lo >= 0 else div(num_lo, den_lo)
    ci_hi = div(num_hi, den_lo) if num_hi >= 0 else div(num_hi, den_hi)
    return ci_hi - ci_lo


# ---------------------------------------------------------------------------
# the lake's catalog and top-k
# ---------------------------------------------------------------------------

class Reference:
    """Top-k join-correlation answers over raw tables (``keys [m]``,
    ``values [C, m]`` each) with sketch size ``n``. Column names follow
    ``names[t][c]``; catalog order is table order, then column order."""

    def __init__(self, tables: Sequence, names: Sequence[Sequence[str]],
                 n: int):
        self.names = names
        self.n = int(n)
        self._cols: List[List[Sketch]] = []
        for t in tables:
            h = murmur3_u32(t.keys)
            self._cols.append([sketch(h, row, self.n) for row in t.values])
        # every table under each key of its columns' sketches: a column
        # joins a query only through a key in both sketches
        keys = [np.unique(np.concatenate([c.h for c in cols]))
                for cols in self._cols]
        flat = np.concatenate(keys + [np.zeros(0, np.uint32)])
        owner = np.repeat(np.arange(len(keys)), [k.size for k in keys])
        order = np.argsort(flat, kind="stable")
        self._flat, self._owner = flat[order], owner[order]

    def columns(self, t: int) -> List[Sketch]:
        return self._cols[t]

    def candidates(self, qh: np.ndarray) -> np.ndarray:
        """Tables whose sketches hold any of the query sketch's keys."""
        lo = np.searchsorted(self._flat, qh, "left")
        hi = np.searchsorted(self._flat, qh, "right")
        hit = np.concatenate([self._owner[a:b] for a, b in zip(lo, hi)]
                             + [np.zeros(0, np.int64)])
        return np.unique(hit)

    def query(self, keys, values, *, k: int, estimator: str, scorer: str,
              alpha: float = 0.05, min_sample: int = 3,
              dtype=np.float64) -> dict:
        """The top-k answer to one query: ``names``, ``score``, ``r``, ``m``
        (lists of length ≤ k, best first)."""
        q = sketch(murmur3_u32(keys), values, self.n)
        rows = []
        for t in self.candidates(q.h):
            for c, col in enumerate(self.columns(int(t))):
                _, iq, ic = np.intersect1d(q.h, col.h, assume_unique=True,
                                           return_indices=True)
                m = iq.size
                if m < min_sample:
                    continue
                a, b = q.v[iq], col.v[ic]
                r = estimate(a, b, estimator, dtype)
                ln = ci_length(a, b, min(q.lo, col.lo), max(q.hi, col.hi),
                               alpha, dtype)
                rows.append((int(t), c, r, m, ln))
        if not rows:
            return dict(names=[], score=[], r=[], m=[])
        r = np.array([x[2] for x in rows])
        m = np.array([x[3] for x in rows], np.float64)
        ln = np.array([x[4] for x in rows])
        if scorer == "s1":
            s = np.abs(r)
        elif scorer == "s2":
            s = np.abs(r) * (1.0 - 1.0 / np.sqrt(np.maximum(m, 4.0) - 3.0))
        elif scorer == "s4":
            lmin, lmax = ln.min(), ln.max()
            f = 1.0 - (np.minimum(ln, lmax) - lmin) / max(lmax - lmin, 1e-12)
            s = np.abs(r) * np.clip(f, 0.0, 1.0)
        else:
            raise ValueError(f"unknown scorer {scorer!r}")
        catalog = np.array([x[0] for x in rows]) * 1_000_000 + \
            np.array([x[1] for x in rows])
        pick = np.lexsort((catalog, -s))[:k]
        return dict(names=[self.names[rows[i][0]][rows[i][1]] for i in pick],
                    score=[float(s[i]) for i in pick],
                    r=[float(r[i]) for i in pick],
                    m=[float(m[i]) for i in pick])
