"""Program spans: named host intervals at each serving layer's boundary
(DESIGN.md §11).

``with span("serve.fused"):`` marks one piece of work. Tracing is off by
default, and then `span` is one boolean check that hands back a shared
no-op context: no clock read, no lock, no allocation. `enable(True)` turns
it on for the whole process. Each span then

  * enters a `jax.profiler.TraceAnnotation` of its name, so that a profiler
    trace taken meanwhile holds it on the device trace's clock;
  * adds its count, wall ns (`time.perf_counter_ns`) and thread CPU ns
    (`time.thread_time_ns`) to per-name totals, which `totals()` copies.

A span's metadata (``span("sched.batch", seq=..., width=...)``) is passed
on to the spans nested inside it on the same thread, so every span of one
dispatch carries the dispatch's head ticket and width in the trace.
Totals are lifetime sums: a reader takes their difference over its window.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict

import jax

#: every span name the program writes, by layer: front end
#: (`serve.build_query_sketches`), scheduler (`AsyncScheduler`), server
#: (`Server`, `_SegmentExec`; ``serve.wait`` is a worker blocked on the
#: device)
SPANS = ("front.sketch", "front.layout", "front.build",
         "sched.admit", "sched.batch", "sched.merge",
         "serve.query", "serve.refresh", "serve.merge",
         "serve.stage1", "serve.stage2", "serve.scan", "serve.topm",
         "serve.fused", "serve.select", "serve.combine",
         "serve.wait")

_on = False
_OFF = contextlib.nullcontext()
_lock = threading.Lock()
#: name → [count, wall ns, thread CPU ns], under ``_lock``
_totals: Dict[str, list] = {}
#: the metadata of the innermost open span, per thread
_local = threading.local()


def enable(on: bool = True) -> None:
    """Turn span recording on or off for the whole process. Spans already
    open when it flips finish as they started."""
    global _on
    _on = bool(on)


def span(name: str, **meta):
    """A context that records ``name`` while tracing is on (a shared no-op
    otherwise); ``meta`` joins the trace event's metadata."""
    if not _on:
        return _OFF
    return _Span(name, meta)


def totals() -> Dict[str, Dict[str, int]]:
    """Copy of the per-name totals: ``{name: {count, wall_ns, cpu_ns}}``."""
    with _lock:
        return {k: dict(count=c, wall_ns=w, cpu_ns=u)
                for k, (c, w, u) in _totals.items()}


class _Span:
    __slots__ = ("name", "meta", "outer", "ann", "w0", "c0")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.meta = meta

    def __enter__(self):
        self.outer = getattr(_local, "meta", None)
        meta = {**self.outer, **self.meta} if self.outer else self.meta
        _local.meta = meta
        self.ann = jax.profiler.TraceAnnotation(self.name, **meta)
        self.ann.__enter__()
        self.w0 = time.perf_counter_ns()
        self.c0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter_ns() - self.w0
        cpu = time.thread_time_ns() - self.c0
        self.ann.__exit__(*exc)
        _local.meta = self.outer
        with _lock:
            t = _totals.get(self.name)
            if t is None:
                _totals[self.name] = [1, wall, cpu]
            else:
                t[0] += 1
                t[1] += wall
                t[2] += cpu
        return False
