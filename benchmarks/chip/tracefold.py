"""Reduction of a profiler trace to device busy time, idle gaps and device
time by harness span.

A trace is read into three lists of ``(start_ns, end_ns, name)``: device
operations (each chip's "XLA Ops" line), device program executions ("XLA
Modules"), and the harness's own spans (``bench.*`` annotations on the
host). Everything after that is plain arithmetic on intervals, so the
reduction can be checked on a small recorded trace without a chip.

- busy: the union of the operation intervals of one chip inside the
  traced part of the window, averaged over chips;
- idle gaps: the holes in that union, each named by the harness span open
  at its midpoint (``window`` when none is);
- device time by span: the busy time that falls inside each harness span
  (every harness call ends in a host sync, so the work it launched runs
  inside it), the rest under ``window``;
- time by operation: each operation's self time (its duration less that
  of the operations nested in it, as a loop holds its body's), under its
  short name.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[int, int, str]
WINDOW = "bench.traced"      # the harness span around the traced part


def from_xplane(log_dir: str) -> Dict[str, object]:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    planes = []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        planes.append((plane.name, sorted(lines)))
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            ops[plane.name] = [(e.start_ns, e.end_ns, e.name)
                               for e in lines["XLA Ops"].events]
            if "XLA Modules" in lines:
                modules[plane.name] = [(e.start_ns, e.end_ns, e.name)
                                       for e in lines["XLA Modules"].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith("bench."))
    return {"ops": ops, "modules": modules, "spans": sorted(spans),
            "planes": planes}


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8,512]{...} fusion(...)`` -> ``%fusion.12 =
    bf16[8,512]``."""
    return hlo.split("{", 1)[0].split("(", 1)[0].strip()


def self_times(ops: List[Interval]) -> Dict[str, float]:
    """Seconds of each operation not covered by operations nested in it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []            # [end, name, self_ns]

    def close(item):
        out[short_name(item[1])] += item[2] / 1e9

    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, n, e - s])
    while stack:
        close(stack.pop())
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class SpanIndex:
    """Innermost harness span at a time; harness spans other than the
    window never overlap one another."""

    def __init__(self, spans: List[Interval]):
        leaf = sorted(s for s in spans
                      if s[2] not in (WINDOW, "bench.window"))
        self._starts = [s[0] for s in leaf]
        self._leaf = leaf

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self._leaf[i][1]:
            return self._leaf[i][2]
        return "window"

    def split(self, s: int, e: int) -> List[Tuple[str, int]]:
        """The parts of [s, e) inside each span, the rest as ``window``."""
        out, t = [], s
        i = max(bisect.bisect_right(self._starts, s) - 1, 0)
        while t < e and i < len(self._leaf):
            a, b, name = self._leaf[i]
            if b <= t:
                i += 1
                continue
            if a >= e:
                break
            if a > t:
                out.append(("window", a - t))
                t = a
            out.append((name, min(b, e) - t))
            t = min(b, e)
            i += 1
        if t < e:
            out.append(("window", e - t))
        return out


def reduce(trace: Dict[str, object]) -> Dict[str, object]:
    """Busy and idle time of the traced window, device time by harness
    span and by operation, and idle time by harness span."""
    windows = [s for s in trace["spans"] if s[2] == WINDOW]
    if not windows or not trace["ops"]:
        raise ValueError("trace holds no window span or no device operations")
    w0, w1 = windows[0][0], windows[0][1]
    index = SpanIndex(trace["spans"])
    busy_ns, dev_by_span, op_totals = [], defaultdict(float), \
        defaultdict(float)
    idle_by_span = defaultdict(float)
    for plane, ops in trace["ops"].items():
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                  if e > w0 and s < w1]
        for n, t in self_times(inside).items():
            op_totals[n] += t
        u = union([(s, e) for s, e, _ in inside])
        for s, e in u:
            for name, dt in index.split(s, e):
                dev_by_span[name] += dt / 1e9
        busy_ns.append(sum(e - s for s, e in u))
        edges = [w0] + [t for iv in u for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = index.at((a + b) / 2)
                idle_by_span[name] += (b - a) / 1e9
    n_chips = len(trace["ops"])
    modules = defaultdict(float)
    for mods in trace["modules"].values():
        for s, e, n in mods:
            if e > w0 and s < w1:
                modules[n] += (min(e, w1) - max(s, w0)) / 1e9 / n_chips
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "device_s_by_span": {k: v / n_chips for k, v in dev_by_span.items()},
        "idle_s_by_span": {k: v / n_chips for k, v in idle_by_span.items()},
        "op_s": {k: v / n_chips for k, v in op_totals.items()},
        "module_s": dict(modules),
    }


def top(d: Dict[str, float], n: int = 10) -> List[List[object]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
