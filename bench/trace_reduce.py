"""Reduce a profiler trace of the measured window to device metrics.

Two steps, kept apart so the second can be checked on a small recorded
trace (`bench/tests/trace_small.json`):

* `load` reads the ``.xplane.pb`` the JAX profiler wrote and keeps the
  device operations (the ``XLA Ops`` line of each ``/device:TPU:*`` plane;
  on a TPU v5e each event's name is its HLO instruction, e.g.
  ``%postings_merge.1 = (...) custom-call(...)``, so a Pallas kernel is
  named by the jitted function that holds it) and the benchmark's host
  spans;
* `reduce` takes those events, the traced window and the intervals in
  which at least one request was outstanding (all on the trace's clock, in
  ns), and returns the device's busy time (the union of its operations'
  intervals, averaged over the devices), the busy time while requests were
  in flight, the device time of each operation label, and the longest idle
  gaps, each named by the host span that covered most of it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

from loadgen import HOST_SPANS

#: the host span whose start marks the window on the trace's clock
MARK = "window_start"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_SUFFIX = re.compile(r"[.\d]+$")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> dict:
    """Device operations and host spans of one trace file, as plain lists:
    ``device_ops`` of [device, label, start_ns, dur_ns] and ``host_spans``
    of [name, start_ns, dur_ns]."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops, spans = [], []
    wanted = set(HOST_SPANS) | {MARK}
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            for ev in line.events:
                ops.append([plane.name, ev.name, float(ev.start_ns),
                            float(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)])
    return dict(device_ops=ops, host_spans=spans)


def op_name(label: str) -> str:
    """An operation's own name: the first word of its label, without the
    HLO ``%`` (``postings_merge.1``)."""
    return label.split(" ", 1)[0].lstrip("%")


def short_label(label: str) -> str:
    """A stable short name for an operation: its own name without the
    numeric suffix XLA numbers instances with (``postings_merge``,
    ``fusion``)."""
    return _SUFFIX.sub("", op_name(label)) or label


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> List[Tuple[float, float]]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def mark_ns(events: dict) -> float:
    """Start of the window mark on the trace's clock."""
    marks = [s for name, s, _ in events["host_spans"] if name == MARK]
    if not marks:
        raise ValueError(f"the trace has no {MARK!r} host span")
    return min(marks)


def reduce(events: dict, window: Tuple[float, float],
           inflight: Sequence[Tuple[float, float]], top: int = 10) -> dict:
    """Device metrics of the window ``(t0_ns, t1_ns)``; ``inflight`` are
    the intervals in which at least one request was outstanding."""
    t0, t1 = window
    clip = lambda s, e: (max(s, t0), min(e, t1))
    per_dev: Dict[str, list] = {}
    per_label: Dict[str, float] = {}
    for dev, label, s, d in events["device_ops"]:
        s, e = clip(s, s + d)
        if e <= s:
            continue
        per_dev.setdefault(dev, []).append((s, e))
        per_label[label] = per_label.get(label, 0.0) + (e - s) * 1e-9
    busy = {dev: union(iv) for dev, iv in per_dev.items()}
    ndev = max(len(busy), 1)
    fly = union([clip(s, e) for s, e in inflight])
    busy_s = sum(length(b) for b in busy.values()) * 1e-9 / ndev
    busy_fly_s = sum(length(intersect(b, fly))
                     for b in busy.values()) * 1e-9 / ndev

    # idle gaps of the busiest device, named by the host span covering most
    gaps = []
    if busy:
        b = max(busy.values(), key=length)
        edges = [t0] + [x for iv in b for x in iv] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
    spans: Dict[str, list] = {}
    for name, s, d in events["host_spans"]:
        if name in HOST_SPANS:
            spans.setdefault(name, []).append((s, s + d))
    spans = {k: union(v) for k, v in spans.items()}
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        cover = {k: length(intersect([(s, e)], v)) for k, v in spans.items()}
        best = max(cover, key=cover.get) if cover else None
        name = best if best is not None and cover[best] > 0 else "other"
        named.append([name, (e - s) * 1e-9])

    by_short: Dict[str, float] = {}
    for label, sec in per_label.items():
        k = short_label(label)
        by_short[k] = by_short.get(k, 0.0) + sec
    device_ops = sorted(by_short.items(), key=lambda kv: -kv[1])[:top]
    return dict(busy_s=busy_s, window_s=(t1 - t0) * 1e-9,
                inflight_s=length(fly) * 1e-9, busy_inflight_s=busy_fly_s,
                devices=len(busy), op_seconds=per_label,
                device_ops=[[k, v] for k, v in device_ops], idle_gaps=named)
