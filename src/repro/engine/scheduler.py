"""Async SLO-aware serving on top of the plan/executor engine
(DESIGN.md §9).

`AsyncScheduler` turns the synchronous `repro.engine.serve.Server` into an
open-loop system under load: callers `submit()` query-sketch batches and
get a `QueryTicket` (a future) back immediately; a worker thread pool
drains an admission queue over the server's already-compiled plan
executors. Three properties carry the design:

  * **continuous batching** — queued tickets with compatible request
    semantics (`repro.engine.plans.coalesce_key`: same estimator, scorer,
    prune mode, α, eligibility floor — ``k`` deliberately excluded, it is
    a host-side slice) are coalesced into one engine dispatch. This
    generalises the PR 2 `_plan_cover` DP from "cover one batch with
    bucket dispatches" to an admission loop: whatever queue depth has
    accumulated while the workers were busy becomes the next batch, which
    the engine then covers with its measured-cost bucket ladder. No timer,
    no minimum batch — dispatch is work-conserving, and batching emerges
    exactly when the system is saturated (the regime where it pays).
  * **deadline pressure** — admission is earliest-deadline-first across
    coalesce groups, and a group is *shrunk* before dispatch until its
    estimated cost (the engine's own `plan_batches` DP over warmed bucket
    timings) fits the oldest member's remaining slack. A group whose head
    already missed takes the full coalesce width instead: those queries
    are late regardless, so the scheduler maximises goodput by clearing
    backlog at the cheapest per-query cost.
  * **snapshot isolation** — workers call `Server.query_batch`, which
    reads one immutable segment-map snapshot per dispatch, so background
    `append`/`delete`/`compact` + `refresh()` never race a scan
    (DESIGN.md §9; the serving-layer races this rides on were fixed with
    the scheduler).

Determinism: with ``workers=1`` results are bit-identical to calling
`Server.query_batch` directly for ``prune='off'`` requests (engine
batching is bit-identical to sequential, and a coalesced dispatch is just
a bigger batch); pruned modes agree to the engine's documented ulp-level
reassociation.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.engine import plans as PL
from repro.engine import trace as TR


class QueryTicket:
    """A submitted query batch: completion future + timing telemetry.

    ``result()`` blocks until the worker pool serves the ticket and
    returns the usual ``(scores, gids, r, m)`` numpy tuple (rows = this
    ticket's queries, ``k`` = this ticket's request.k), re-raising any
    worker-side exception. Its stamps are monotonic-clock seconds:
    ``t_submit`` (queued), ``t_admit`` (popped into a dispatch group, the
    admission numbered ``batch``), ``t_served`` (`Server.query_batch`
    returned) and ``t_done`` (result set); ``latency_s``/
    ``missed_deadline`` are available after completion.
    """

    __slots__ = ("sketches", "request", "nq", "seq", "t_submit", "deadline",
                 "t_admit", "batch", "t_served", "t_done", "_event",
                 "_result", "_error")

    def __init__(self, sketches, request: PL.Request, nq: int, seq: int,
                 t_submit: float, deadline: Optional[float]):
        self.sketches = sketches
        self.request = request
        self.nq = nq
        self.seq = seq
        self.t_submit = t_submit
        self.deadline = deadline
        self.t_admit: Optional[float] = None
        self.batch: Optional[int] = None
        self.t_served: Optional[float] = None
        self.t_done: Optional[float] = None
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("query ticket not served within timeout")
        if self._error is not None:
            raise self._error
        return self._result

    @property
    def latency_s(self) -> float:
        """Submit → completion seconds (queue wait included)."""
        assert self.t_done is not None, "ticket not completed"
        return self.t_done - self.t_submit

    @property
    def missed_deadline(self) -> bool:
        return (self.deadline is not None and self.t_done is not None
                and self.t_done > self.deadline)

    # -- worker side ---------------------------------------------------------
    def _finish(self, result, t_done: float) -> None:
        self.sketches = None          # free the query payload eagerly
        self._result = result
        self.t_done = t_done
        self._event.set()

    def _fail(self, err: BaseException, t_done: float) -> None:
        self.sketches = None
        self._error = err
        self.t_done = t_done
        self._event.set()


def _merge_sketches(tickets: List[QueryTicket]):
    """Concatenate the tickets' query-sketch pytrees along the leading
    [NQ] axis — every `CorrelationSketch` leaf carries it. Host-side
    `np.concatenate` on purpose: group widths vary per admission, and an
    eager `jnp.concatenate` would trace/compile once per distinct width;
    the merged arrays cross to the device exactly once, inside the
    dispatch's jitted scan."""
    if len(tickets) == 1:
        return tickets[0].sketches
    return jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
        *[t.sketches for t in tickets])


class AsyncScheduler:
    """Admission queue + worker pool over a warmed `Server` (DESIGN.md §9).

    ``workers`` threads drain the queue; each admission takes the
    earliest-deadline coalesce group, sizes it against the measured-cost
    bucket ladder under the head's deadline slack, merges the sketches and
    dispatches one `Server.query_batch`. ``slo_ms`` is the default
    deadline budget stamped on every submit (per-submit overrides win);
    ``None`` disables deadlines — pure throughput mode. ``max_coalesce``
    bounds one dispatch group (default: the server's largest bucket, the
    width the engine amortises best). ``max_queue`` (queries) makes
    `submit` raise when the backlog is full — ``None`` (default) queues
    without bound, the open-loop bench's regime.

    Attaches itself to the server: `Server.throughput()` reports
    ``queue_depth`` and ``deadline_misses`` alongside the engine counters.
    Use as a context manager, or `close()` explicitly (drains the queue,
    then joins the workers).
    """

    def __init__(self, server, *, workers: int = 2,
                 slo_ms: Optional[float] = None,
                 max_coalesce: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 refresh: bool = True):
        assert workers >= 1
        self.server = server
        self.refresh = refresh
        self.slo_s = None if slo_ms is None else float(slo_ms) / 1e3
        self.max_coalesce = int(max_coalesce if max_coalesce is not None
                                else max(server.buckets))
        assert self.max_coalesce >= 1
        self.max_queue = max_queue
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        #: coalesce_key → FIFO of waiting tickets (EDF picks across keys)
        self._pending: Dict[tuple, Deque[QueryTicket]] = {}
        self._pending_n = 0          # queued queries (not tickets)
        self._seq = 0
        self._closed = False
        # counters (under _lock)
        self._submitted = 0          # queries accepted
        self._completed = 0          # queries served (errors excluded)
        self._errors = 0             # tickets failed
        self._batches = 0            # engine dispatch groups flushed
        self._deadline_misses = 0    # queries completed past their deadline
        self._flush_deadline = 0     # groups shrunk by deadline pressure
        self._flush_full = 0         # groups capped at max_coalesce
        self._flush_drain = 0        # groups that drained their whole queue
        self._workers = [
            threading.Thread(target=self._worker_loop, daemon=True,
                             name=f"corrsketch-serve-{i}")
            for i in range(workers)]
        server._scheduler = self
        for t in self._workers:
            t.start()

    # -- submission ----------------------------------------------------------
    def submit(self, sketches, *, request: Optional[PL.Request] = None,
               slo_ms: Optional[float] = None,
               deadline_s: Optional[float] = None) -> QueryTicket:
        """Enqueue a query-sketch batch (leading [NQ] axis) and return its
        `QueryTicket`. ``deadline_s`` is an absolute monotonic-clock
        deadline; ``slo_ms`` a relative budget from now; neither falls
        back to the scheduler's default SLO. Invalid requests (unknown
        estimator/scorer/prune, k > k_max) raise *here*, in the caller."""
        req = request if request is not None else self.server.request
        key = PL.coalesce_key(req)          # validates the request
        if req.k > self.server.shape.k_max:
            raise ValueError(
                f"request k={req.k} exceeds ShapePolicy.k_max="
                f"{self.server.shape.k_max}; raise k_max (a compile-time "
                "width) or lower k")
        nq = int(jax.tree.leaves(sketches)[0].shape[0])
        now = time.monotonic()
        if deadline_s is None:
            slo = self.slo_s if slo_ms is None else float(slo_ms) / 1e3
            deadline_s = None if slo is None else now + slo
        with self._work:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            if (self.max_queue is not None
                    and self._pending_n + nq > self.max_queue):
                raise RuntimeError(
                    f"admission queue full ({self._pending_n} queries "
                    f"queued, max_queue={self.max_queue})")
            t = QueryTicket(sketches, req, nq, self._seq, now, deadline_s)
            self._seq += 1
            self._pending.setdefault(key, deque()).append(t)
            self._pending_n += nq
            self._submitted += nq
            self._work.notify()
        return t

    def query(self, sketches, *, request: Optional[PL.Request] = None,
              slo_ms: Optional[float] = None,
              timeout: Optional[float] = None):
        """Blocking convenience: submit + wait for the result."""
        return self.submit(sketches, request=request,
                           slo_ms=slo_ms).result(timeout)

    # -- admission -----------------------------------------------------------
    @staticmethod
    def _urgency(t: QueryTicket) -> tuple:
        """EDF order: deadline first (∞ when absent), then arrival."""
        return (t.deadline if t.deadline is not None else math.inf,
                t.t_submit, t.seq)

    def _est_cost_s(self, nq: int) -> float:
        """Estimated seconds to serve ``nq`` coalesced queries: the
        engine's own measured-cost bucket cover (`plan_batches` — the
        `_plan_cover` DP), summed over the ladder and scaled by the
        segment fan-out. Zero before warmup (no costs measured yet)."""
        view = self.server._view
        if not view:
            return 0.0
        ex = view[0].exec
        costs = ex._bucket_cost
        if not costs:
            return 0.0
        worst = max(costs.values())
        est = sum(costs.get(b, worst) for b in ex.plan_batches(nq))
        return est * max(len(view), 1)

    def _take_locked(self, now: float) -> Tuple[List[QueryTicket], int]:
        """Pop the next dispatch group (called under ``_lock``): the
        earliest-deadline coalesce queue, FIFO-prefix up to
        ``max_coalesce`` queries, shrunk until the estimated dispatch cost
        fits the head's remaining slack — unless the head is already past
        its deadline, in which case the full width ships (clearing backlog
        at max amortisation is the goodput-optimal move for late work)."""
        key = min(self._pending,
                  key=lambda k: self._urgency(self._pending[k][0]))
        q = self._pending[key]
        group: List[QueryTicket] = [q[0]]
        total = q[0].nq
        for t in list(q)[1:]:
            if total + t.nq > self.max_coalesce:
                break
            group.append(t)
            total += t.nq
        capped = len(group) < len(q)
        head = group[0]
        shrunk = False
        if head.deadline is not None:
            slack = head.deadline - now
            if slack > 0:
                while len(group) > 1 and self._est_cost_s(total) > slack:
                    total -= group.pop().nq
                    shrunk = True
        for t in group:
            q.popleft()
            t.t_admit = now
            t.batch = self._batches
        if not q:
            del self._pending[key]
        self._pending_n -= total
        self._batches += 1
        if shrunk:
            self._flush_deadline += 1
        elif capped:
            self._flush_full += 1
        else:
            self._flush_drain += 1
        return group, total

    # -- worker pool ---------------------------------------------------------
    def _worker_loop(self) -> None:
        while True:
            with self._work:
                while not self._pending and not self._closed:
                    self._work.wait()
                if not self._pending:     # closed + drained
                    return
                with TR.span("sched.admit"):
                    group, _ = self._take_locked(time.monotonic())
            # the dispatch's spans carry its head ticket and width
            with TR.span("sched.batch", seq=group[0].seq, width=len(group)):
                self._execute(group)

    def _execute(self, group: List[QueryTicket]) -> None:
        try:
            k_rep = max(t.request.k for t in group)
            rep = dataclasses.replace(group[0].request, k=k_rep)
            with TR.span("sched.merge"):
                sks = _merge_sketches(group)
            out = self.server.query_batch(sks, request=rep,
                                          refresh=self.refresh)
            served_at = time.monotonic()
            for t in group:
                t.t_served = served_at
            # one device→host transfer per dispatch; the per-ticket row/k
            # slices below are then numpy views (an eager jax slice would
            # compile per distinct (nq, k) shape)
            with TR.span("serve.wait"):
                out_np = tuple(np.asarray(a) for a in out)
            now = time.monotonic()
            misses = served = 0
            s = 0
            for t in group:
                res = tuple(a[s:s + t.nq, :t.request.k] for a in out_np)
                s += t.nq
                t._finish(res, now)
                served += t.nq
                if t.missed_deadline:
                    misses += t.nq
            with self._lock:
                self._completed += served
                self._deadline_misses += misses
        except BaseException as err:   # propagate to every waiter
            now = time.monotonic()
            for t in group:
                t._fail(err, now)
            with self._lock:
                self._errors += len(group)

    # -- lifecycle / telemetry -----------------------------------------------
    def close(self) -> None:
        """Stop accepting work, drain the queue, join the workers."""
        with self._work:
            if self._closed:
                return
            self._closed = True
            self._work.notify_all()
        for t in self._workers:
            t.join()

    def __enter__(self) -> "AsyncScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def queue_stats(self) -> dict:
        """The admission counters `Server.throughput()` merges in."""
        with self._lock:
            return dict(queue_depth=self._pending_n,
                        deadline_misses=self._deadline_misses)

    def stats(self) -> dict:
        """Full scheduler telemetry (all counters under one lock read)."""
        with self._lock:
            batches = self._batches
            completed = self._completed
            return dict(
                workers=len(self._workers),
                queue_depth=self._pending_n,
                submitted=self._submitted,
                completed=completed,
                errors=self._errors,
                batches=batches,
                avg_coalesce=completed / max(batches, 1),
                deadline_misses=self._deadline_misses,
                flush_deadline=self._flush_deadline,
                flush_full=self._flush_full,
                flush_drain=self._flush_drain)
