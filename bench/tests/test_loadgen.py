"""The open loop hands requests off on schedule even when each one is
slow to build, and every seed offers the same set of gaps."""
import time

import numpy as np

import loadgen


class _Ticket:
    def __init__(self):
        self.t_done = time.monotonic()

    def result(self, timeout=None):
        return "answer"


def test_slow_builds_do_not_delay_the_schedule():
    import jax  # noqa: F401  (the host spans import it; a run has it loaded)
    due = np.array([0.0, 0.01, 0.02, 0.03, 0.04, 0.05])
    build = lambda q: time.sleep(0.2) or q
    t0 = time.monotonic() + 0.05
    reqs = loadgen.run_open(due, np.arange(6), build, lambda sk: _Ticket(),
                            t0, 0.3, grace=5.0, senders=6)
    lag = [r.send - r.due for r in reqs]
    # one sender at a time would send the last request a second late
    assert max(lag) < 0.1, lag
    assert all(r.result == "answer" and r.done is not None for r in reqs)


def test_a_request_waiting_for_a_sender_is_late_from_its_due_time():
    due = np.zeros(4)
    t0 = time.monotonic() + 0.05
    reqs = loadgen.run_open(due, np.arange(4), lambda q: time.sleep(0.1),
                            lambda sk: _Ticket(), t0, 0.1, grace=5.0,
                            senders=2)
    lag = sorted(r.send - r.due for r in reqs)
    assert lag[-1] > 0.09 and all(r.done - r.due >= 0.1 for r in reqs)


def test_seeds_reorder_the_same_gaps():
    a = loadgen.open_schedule(20.0, 30.0, np.random.default_rng(1))
    b = loadgen.open_schedule(20.0, 30.0, np.random.default_rng(2))
    assert a.size == b.size == 600 and not np.array_equal(a, b)
    # the same gaps in another order (each schedule leaves out its last)
    ga, gb = np.round(np.diff(a), 9), np.round(np.diff(b), 9)
    assert np.setdiff1d(ga, gb).size <= 1
