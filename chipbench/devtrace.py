"""Reduce a profiler trace to device intervals and host spans.

Two steps, kept apart so the second can be checked on a recorded trace:

* :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
  keeps only what the metrics read: every device's operation events (the
  ``XLA Ops`` line of each ``/device:TPU:n`` plane) and the harness's own
  host spans (``window``, ``chunk.*``), as ``[name, start_ns, dur_ns]``.
* :class:`Trace` holds that and does the interval arithmetic: the union of
  a device's busy intervals, their overlap with a set of operations, idle
  gaps and what the host was doing in each.

Each device event is named by its HLO instruction text
(``%name = shape opcode(operands), ...``); :func:`opcode` reads the opcode
from it. The ``XLA Ops`` line nests a loop's body operations inside the
``while`` operation itself, so control-flow containers (``while``,
``conditional``, ``call``) are dropped and only leaf operations count.

Host and device events share one clock in the profiler's output, so a
device gap can be put down to the host span it falls in. The two clocks
are aligned to within about a millisecond on a TPU v5e host: a device op
can read as starting up to ~1 ms before the host span that dispatched it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Callable, Iterable

HOST_SPANS = ("window", "chunk.copy", "chunk.dispatch", "chunk.sync")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def opcode(text: str) -> str:
    """The opcode of an HLO instruction's text, e.g. ``fusion``."""
    m = _OPCODE.search(text.split(" = ", 1)[-1])
    return m.group(1) if m else text


def short_name(text: str) -> str:
    """The instruction's name without its shapes: ``copy.1``."""
    return text.split(" = ", 1)[0].lstrip("%")


def extract(log_dir: str) -> dict:
    """Device op events and harness spans from the trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    devices: dict = {}
    host: list = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith(DEVICE_PREFIX):
                ops = devices.setdefault(plane.name[len("/device:"):], [])
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops.extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend([e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


def load(path: str) -> dict:
    """Events that :func:`extract` returned, kept as gzipped JSON."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: Iterable) -> list:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: list, b: list) -> list:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out = []
    for s, e in a:
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append([s, bs])
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append([s, e])
    return out


class Trace:
    """Device busy intervals clipped to the traced ``window`` span."""

    def __init__(self, events: dict):
        windows = [(s, s + d) for n, s, d in events["host"] if n == "window"]
        if not windows:
            raise ValueError("the trace holds no 'window' span")
        self.start = min(s for s, _ in windows)
        self.end = max(e for _, e in windows)
        self.spans = [(n, s, s + d) for n, s, d in events["host"]]
        clip = [[self.start, self.end]]
        self.ops = {dev: [(short_name(n), opcode(n), s, s + d)
                          for n, s, d in ops if opcode(n) not in CONTAINERS]
                    for dev, ops in sorted(events["devices"].items())}
        self.busy = {dev: intersect(union([s, e] for *_, s, e in ops), clip)
                     for dev, ops in self.ops.items()}

    @property
    def window_ns(self) -> float:
        return self.end - self.start

    def devices(self) -> list:
        return list(self.busy)

    def busy_ns(self, dev: str) -> float:
        return length(self.busy[dev])

    def mean_busy_ns(self) -> float:
        return sum(map(self.busy_ns, self.busy)) / max(len(self.busy), 1)

    def op_intervals(self, dev: str, pick: Callable[[str], bool]) -> list:
        """Busy intervals of the operations whose opcode ``pick`` takes."""
        clip = [[self.start, self.end]]
        return intersect(union([s, e] for _, op, s, e in self.ops[dev]
                               if pick(op)), clip)

    def top_ops(self, n: int = TOP) -> list:
        """[name, seconds] of the operations that took most device time,
        summed over the window and averaged over devices."""
        total: dict = {}
        for ops in self.ops.values():
            for name, _, s, e in ops:
                s, e = max(s, self.start), min(e, self.end)
                if s < e:
                    total[name] = total.get(name, 0.0) + (e - s)
        k = max(len(self.ops), 1)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / k / 1e9] for name, ns in ranked]

    def idle_gaps(self, n: int = TOP) -> list:
        """[host span, seconds] of the longest idle gaps of any device,
        each named by the innermost harness span its middle falls in."""
        gaps = []
        for busy in self.busy.values():
            idle = subtract([[self.start, self.end]], busy)
            gaps.extend((e - s, self._span_at((s + e) / 2)) for s, e in idle)
        gaps.sort(key=lambda g: -g[0])
        return [[label, ns / 1e9] for ns, label in gaps[:n]]

    def _span_at(self, t: float) -> str:
        inside = [(e - s, n) for n, s, e in self.spans if s <= t < e]
        return min(inside)[1] if inside else "outside"
