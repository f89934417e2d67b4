"""Device time of the operations that have one of a layer's own dimensions
in their shapes, by harness span: the part of a layer's work that a
per-layer metric reads when the layer has no kernel of its own.

``tracefold.py`` keys device operations by their HLO short name alone. The
program's ``jax.named_scope`` names do not reach the TPU profiler's
"XLA Ops" events (on a TPU v5e with jax 0.9.0 neither an event's name nor
its stats carry them), so a layer's operations are picked by their shapes:
the caller gives a pattern (``dims_pattern``) of dimensions only that layer
has, matched against the event's name, which is the operation's HLO text
with its result's and operands' shapes. Each operation's self time (its
duration less that of the operations nested in it) inside the traced part
of the window is counted under the harness span open at the operation's
start (``window`` when none is), averaged over chips, as
``tracefold.reduce`` counts device time.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

from tracefold import WINDOW, SpanIndex

Op = Tuple[int, int, bool]         # (start_ns, end_ns, matched)


def matched_s_by_span(ops: Dict[str, List[Op]],
                      spans: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Seconds of self time of the matched operations, by harness span."""
    windows = [s for s in spans if s[2] == WINDOW]
    if not windows or not ops:
        return {}
    w0, w1 = windows[0][0], windows[0][1]
    index = SpanIndex(spans)
    out: Dict[str, float] = defaultdict(float)
    for plane_ops in ops.values():
        inside = sorted(((max(s, w0), min(e, w1), k) for s, e, k in plane_ops
                         if e > w0 and s < w1), key=lambda o: (o[0], -o[1]))
        stack: List[List] = []            # [end, start, matched, self_ns]

        def close(item):
            if item[2]:
                out[index.at(item[1])] += item[3] / 1e9

        for s, e, matched in inside:
            while stack and stack[-1][0] <= s:
                close(stack.pop())
            if stack:
                stack[-1][3] -= min(e, stack[-1][0]) - s
            stack.append([e, s, matched, e - s])
        while stack:
            close(stack.pop())
    return {k: v / len(ops) for k, v in out.items()}


def dims_pattern(dims) -> str:
    """A pattern matching an HLO shape that has one of ``dims`` (ints, or
    runs of adjacent dimensions as tuples) among its dimensions."""
    alts = [",".join(map(str, d)) if isinstance(d, tuple) else str(d)
            for d in dims]
    return r"[\[,](?:" + "|".join(alts) + r")[,\]]"


def from_xplane(log_dir: str, pattern: str) -> Dict[str, float]:
    """``matched_s_by_span`` of the newest ``.xplane.pb`` under ``log_dir``,
    for the device operations whose HLO text matches ``pattern``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {}
    match = re.compile(pattern).search
    ops: Dict[str, List[Op]] = {}
    spans: List[Tuple[int, int, str]] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            ops[plane.name] = [(e.start_ns, e.end_ns, bool(match(e.name)))
                               for e in lines["XLA Ops"].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith("bench."))
    return matched_s_by_span(ops, sorted(spans))
