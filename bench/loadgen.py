"""Load generator: open-loop Poisson arrivals or closed-loop clients.

Every request is timed by the benchmark itself, from raw query columns to
the top-k in hand: its query sketch is built, then the sketch is submitted
to the scheduler. An open-loop request is timed from the moment it was due,
so a late generator or a stall shows in the latency of every request behind
it; a closed-loop request from its send. The host spans named in
`HOST_SPANS` mark what the generator's and the senders' threads are doing,
for the trace's attribution of idle device time.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional

import numpy as np

#: host spans the benchmark writes into the profiler's trace
HOST_SPANS = ("gen_sleep", "sketch_build", "submit", "await_result")

#: threads that build and submit open-loop requests
SENDERS = 8


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def open_schedule(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate·seconds)``
    Poisson arrivals. The gaps are the same set for every seed — the
    exponential distribution's quantiles at (i + ½)/N, scaled to fill the
    window — and the seed only orders them, so seeds change which requests
    bunch up, not how much work arrives."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = gaps[rng.permutation(n)] * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


@dataclasses.dataclass
class Req:
    """One request: its pool entry and its times (monotonic seconds)."""
    query: int
    due: float = 0.0
    send: float = 0.0
    sketch_s: float = 0.0
    done: Optional[float] = None
    ticket: object = None
    result: object = None
    error: Optional[str] = None


def _issue(req: Req, build: Callable, submit: Callable) -> None:
    req.send = time.monotonic()
    with _span("sketch_build"):
        sk = build(req.query)
    req.sketch_s = time.monotonic() - req.send
    with _span("submit"):
        req.ticket = submit(sk)


def _await(req: Req, deadline: float) -> None:
    """Wait for a request's answer until ``deadline``; an answer that comes
    later, or an error, leaves ``done`` unset."""
    if req.ticket is None:
        return
    try:
        with _span("await_result"):
            req.result = req.ticket.result(
                timeout=max(deadline - time.monotonic(), 0.0))
        req.done = req.ticket.t_done
    except TimeoutError:
        req.error = "not answered within the grace"
    except Exception as e:          # an error the server returned
        req.error = f"{type(e).__name__}: {e}"


def run_open(due: np.ndarray, queries: np.ndarray, build: Callable,
             submit: Callable, t0: float, seconds: float, grace: float,
             senders: int = SENDERS) -> List[Req]:
    """Hand request i to a pool of ``senders`` threads at ``t0 + due[i]``;
    a sender builds its query sketch and submits it. Then wait for every
    answer until ``grace`` seconds past the window's end. The generator's
    own thread only sleeps and hands off, so its schedule does not slip
    when the system slows: a request that waits for a free sender is late
    from its due time, and its latency shows it."""
    reqs = [Req(int(q), due=t0 + float(d)) for q, d in zip(queries, due)]
    sent = []

    def send(r: Req) -> None:
        try:
            _issue(r, build, submit)
        except Exception as e:
            r.error = f"{type(e).__name__}: {e}"

    pool = ThreadPoolExecutor(senders, thread_name_prefix="sender")
    for r in reqs:
        wait = r.due - time.monotonic()
        if wait > 0:
            with _span("gen_sleep"):
                time.sleep(wait)
        sent.append(pool.submit(send, r))
    end = t0 + seconds
    wait = end - time.monotonic()
    if wait > 0:
        with _span("gen_sleep"):
            time.sleep(wait)
    for r, f in zip(reqs, sent):
        try:
            f.result(timeout=max(end + grace - time.monotonic(), 0.0))
        except TimeoutError:
            r.error = "not sent within the grace"
            continue
        _await(r, end + grace)
    pool.shutdown(wait=True, cancel_futures=True)
    return reqs


def run_closed(clients: int, pick: Callable[[int], int], build: Callable,
               submit: Callable, t0: float, seconds: float,
               grace: float) -> List[Req]:
    """``clients`` threads, each sending its next request when its last
    answer is in, until the window ends; ``pick(i)`` chooses the query of
    the i-th request a client sends (client c sends i = c, c + clients,
    ...)."""
    end = t0 + seconds
    out: List[List[Req]] = [[] for _ in range(clients)]

    def client(c: int) -> None:
        i = c
        while time.monotonic() < end:
            r = Req(pick(i))
            r.due = time.monotonic()
            out[c].append(r)
            try:
                _issue(r, build, submit)
            except Exception as e:
                r.error = f"{type(e).__name__}: {e}"
                return
            _await(r, end + grace)
            if r.done is None:
                return
            i += clients

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + grace + 60)
    return [r for rs in out for r in rs]
