"""The comparison that decides a run's ``correct``.

Every answer the timed requests received is compared with the plain
reference (`bench/reference.py`) on the same query columns. Each answer
is a dict of equal-length lists ``names``, ``score``, ``r``, ``m`` (best
first, slots without a candidate left out). The numbers compared:

* ``score_gap``: the widest |score difference| between the two answers
  slot by slot (the estimator, the CI and the scorer's normalisation);
* ``r_gap``: the widest |r difference| of a column in both answers (the
  estimator alone);
* ``m_gap``: the widest |m difference| of a column in both answers — the
  join's sample size, which is exact (the query sketch and stage 1);
* ``order_errors``: answers whose columns differ from the reference's,
  other than columns whose scores tie within the ``score_gap`` limit
  swapping places or, in a full answer, crossing the k-th slot (the top-k
  after the combine);
  an answer with another number of slots counts here too;
* ``lost``: requests that never got an answer, or got an error.

A run is correct when every number is within its limit, given per
traffic mix under ``limits``.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

NUMBERS = ("score_gap", "r_gap", "m_gap", "order_errors", "lost")


def _same_order(a: dict, b: dict, tol: float, k: int) -> bool:
    sa, sb = np.asarray(a["score"]), np.asarray(b["score"])
    if sa.size != sb.size:
        return False
    # only a full answer has a k-th slot that a tied column can cross
    full = sa.size == k
    na, nb = a["names"], b["names"]
    for j in range(sa.size):
        if na[j] == nb[j]:
            continue
        near_a = np.abs(sa - sa[j]) <= tol
        near_b = np.abs(sb - sb[j]) <= tol
        if {na[i] for i in np.nonzero(near_a)[0]} == \
                {nb[i] for i in np.nonzero(near_b)[0]}:
            continue
        if not (full and (near_a[-1] or near_b[-1])):
            return False
    return True


def compare(answers: List[Optional[dict]], refs: List[dict],
            limits: Dict[str, float], k: int) -> Dict[str, float]:
    """The compared numbers over paired top-``k`` answers (None = never
    answered)."""
    out = dict(score_gap=0.0, r_gap=0.0, m_gap=0.0, order_errors=0,
               lost=0)
    for a, b in zip(answers, refs):
        if a is None:
            out["lost"] += 1
            continue
        sa, sb = np.asarray(a["score"]), np.asarray(b["score"])
        both = min(sa.size, sb.size)
        if both:
            out["score_gap"] = max(out["score_gap"],
                                   float(np.max(np.abs(sa[:both] - sb[:both]))))
        ref_at = {nm: i for i, nm in enumerate(b["names"])}
        for i, nm in enumerate(a["names"]):
            j = ref_at.get(nm)
            if j is None:
                continue
            out["r_gap"] = max(out["r_gap"], abs(a["r"][i] - b["r"][j]))
            out["m_gap"] = max(out["m_gap"], abs(a["m"][i] - b["m"][j]))
        if not _same_order(a, b, limits["score_gap"], k):
            out["order_errors"] += 1
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {value, limit}}) for the result line."""
    checks = {k: dict(value=numbers[k], limit=limits[k]) for k in NUMBERS}
    ok = all(numbers[k] <= limits[k] for k in NUMBERS)
    return ok, checks
