"""From a profiler trace to device busy time, kernel time and idle gaps.

``load(path)`` reads a JAX profiler ``.xplane.pb`` into plain events:
``(plane, line, name, start_ns, dur_ns)``. ``reduce(events)`` works on
such events alone, so it is tested on synthetic ones and on a trimmed
recorded trace (``tests/bench/fixtures``).

- Device ops are the events of the ``XLA Ops`` line of each
  ``/device:...`` plane.
- The window is the host annotation ``bench.window``; everything is
  clipped to it.
- Busy time is the union of a device's op intervals; with several
  devices, their mean. Idle share is 1 - busy / window.
- Per-op time leaves out control flow (``while``, ``conditional``): on
  the TPU a loop is an event that spans the ops of its body, which are
  events of their own. An op is named by its HLO name and the start of
  its HLO text.
- An idle gap (an interval of the window with no op on a device) is
  charged to the host annotation ``bench.*`` that covers most of it,
  else to "(no annotation)".
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
CONTROL_FLOW = re.compile(r"^%?(while|conditional|call)[.\s]")
NAME_CHARS = 120


def find_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> list[tuple]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        keep_all = plane.name.startswith("/host:")
        for line in plane.lines:
            if not keep_all and line.name != DEVICE_OPS_LINE:
                continue
            for e in line.events:
                if keep_all and not e.name.startswith("bench."):
                    continue
                out.append((plane.name, line.name, e.name,
                            int(e.start_ns), int(e.duration_ns)))
    return out


def _union(intervals):
    """Sorted, merged [(start, end)]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(events, *, top: int = 10) -> dict:
    """Busy and idle seconds, per-op device time and idle by host span.

    Returns {"window_s", "busy_s", "idle_share", "n_devices",
    "op_seconds": {name: s}, "device_ops": [[name, s]] (top),
    "idle_gaps": [[label, s]] (top)}; op and idle seconds are means
    over the devices."""
    win = [(s, s + d) for p, _, n, s, d in events
           if p.startswith("/host:") and n == WINDOW]
    if not win:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    lo, hi = win[0]
    # the benchmark's host spans do not nest, so sorted by start they are
    # sorted by end too
    host = sorted(((n, s, s + d) for p, _, n, s, d in events
                   if p.startswith("/host:") and n != WINDOW),
                  key=lambda h: h[1])
    starts = [h[1] for h in host]
    per_dev = collections.defaultdict(list)
    for p, line, n, s, d in events:
        if p.startswith("/device:") and line == DEVICE_OPS_LINE:
            per_dev[p].append((n, s, s + d))
    if not per_dev:
        raise ValueError("trace has no device ops")
    nd = len(per_dev)
    busy = 0.0
    op_s: dict = collections.defaultdict(float)
    idle: dict = collections.defaultdict(float)
    for ops in per_dev.values():
        for n, s, e in ops:
            if CONTROL_FLOW.match(n):
                continue
            for cs, ce in _clip([(s, e)], lo, hi):
                op_s[n[:NAME_CHARS]] += (ce - cs) / 1e9 / nd
        merged = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy += sum(e - s for s, e in merged) / 1e9 / nd
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            best, label = 0, "(no annotation)"
            for n, s, e in host[max(0, bisect.bisect_right(starts, gs) - 1):]:
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > best:
                    best, label = ov, n
            idle[label] += (ge - gs) / 1e9 / nd
    window_s = (hi - lo) / 1e9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": busy,
            "idle_share": 1.0 - busy / window_s, "n_devices": nd,
            "op_seconds": dict(op_s),
            "device_ops": [[k, v] for k, v in rank(op_s)],
            "idle_gaps": [[k, v] for k, v in rank(idle)]}


def op_time(red: dict, pattern: str) -> tuple[float, int]:
    """Device seconds of the ops whose name matches ``pattern`` (a regular
    expression), and how many distinct names matched."""
    rx = re.compile(pattern)
    hits = [v for k, v in red["op_seconds"].items() if rx.search(k)]
    return sum(hits), len(hits)
