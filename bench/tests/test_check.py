"""The comparison that decides ``correct``, on hand-made answers."""
import pytest

import check

LIMITS = dict(score_gap=1e-3, r_gap=1e-4, m_gap=0, order_errors=0, lost=0)


def _ans(names, score):
    return dict(names=list(names), score=list(score), r=list(score),
                m=[10.0] * len(names))


@pytest.mark.parametrize("got, want, k, errors", [
    # a lone answer naming another column is wrong
    (_ans("b", [0.9]), _ans("a", [0.9]), 10, 1),
    (_ans("a", [0.9]), _ans("a", [0.9]), 10, 0),
    # columns tied within the limit may swap places
    (_ans("ba", [0.9, 0.9]), _ans("ab", [0.9, 0.9]), 10, 0),
    # a column tied with the k-th may cross it in a full answer only
    (_ans("ac", [0.9, 0.5]), _ans("ab", [0.9, 0.5]), 2, 0),
    (_ans("ac", [0.9, 0.5]), _ans("ab", [0.9, 0.5]), 3, 1),
    # an answer with another number of slots
    (_ans("a", [0.9]), _ans("ab", [0.9, 0.5]), 10, 1),
])
def test_order_errors(got, want, k, errors):
    assert check.compare([got], [want], LIMITS, k)["order_errors"] == errors


def test_gaps_and_lost():
    got = check.compare([_ans("ab", [0.9, 0.5]), None],
                        [_ans("ab", [0.9002, 0.5]), _ans("a", [0.1])],
                        LIMITS, 10)
    assert got["score_gap"] == pytest.approx(2e-4)
    assert got["lost"] == 1
    ok, checks = check.verdict(got, LIMITS)
    assert not ok and checks["lost"] == dict(value=1, limit=0)
