"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle share,
per-program and per-kernel time, and idle gaps labelled by host span.

A device plane is ``/device:TPU:<n>``. Its ``XLA Ops`` line holds one event
per operation run; its ``XLA Modules`` line one event per program run, named
after the jitted function (``jit_train_step(...)``). The window is the host
span ``bench.traced`` that the harness puts around the traced part of a run;
a trace without it uses the span of its device events. Host spans are the
``bench.*`` events of the host plane's threads.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.traced"
# ops whose events enclose those of the ops they run (a scan's loop)
CONTAINER = re.compile(r"^%(while|conditional|call|closed_call)\b")
SPAN_PREFIX = "bench."
# the Pallas flash forward: a custom call that gives o in bf16 and the f32
# log-sum-exp in 128 lanes (its HLO name follows the enclosing function)
FLASH_FORWARD_OP = (r"= \(bf16\[\d+,\d+,\d+,\d+\]\{[^}]*\}, "
                    r"f32\[\d+,\d+,\d+,128\]\{[^}]*\}\) custom-call\(")

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def covered(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] between disjoint sorted busy ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """The events of one trace, in seconds on the profiler's clock."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        self.ops: Dict[str, List[tuple]] = {}       # plane -> (name, t0, t1)
        self.modules: Dict[str, List[tuple]] = {}
        self.host: List[tuple] = []                 # (name, t0, t1)
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        evs = [(e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events]
                        target = self.ops if line.name == OPS_LINE \
                            else self.modules
                        target[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            self.host.append(
                                (e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
        if not self.ops:
            raise ValueError(f"{path}: no {OPS_LINE!r} line on a TPU plane")

    def window(self) -> Interval:
        spans = [(a, b) for n, a, b in self.host if n == WINDOW_SPAN]
        if spans:
            return min(a for a, _ in spans), max(b for _, b in spans)
        evs = [e for evs in self.ops.values() for e in evs]
        return min(e[1] for e in evs), max(e[2] for e in evs)


def short(op: str, width: int = 96) -> str:
    """An op's HLO text cut to its name and the start of its result type."""
    return op if len(op) <= width else op[:width] + "..."


def _within(inside: List[Interval], starts: List[float], a: float,
            b: float) -> bool:
    i = bisect.bisect_right(starts, a) - 1
    return i >= 0 and inside[i][1] >= b


def reduce(path: str, programs: Optional[Dict[str, str]] = None,
           kernels: Optional[Dict[str, Tuple[str, str]]] = None,
           top: int = 10) -> dict:
    """Summarise a trace over its window.

    ``programs`` maps a tag to a regular expression on program names;
    ``kernels`` maps a tag to (regex on op names, regex on the names of the
    programs it must run inside). Device figures are averaged over the
    device planes present.
    """
    tr = Trace(path)
    lo, hi = tr.window()
    window = hi - lo
    planes = sorted(tr.ops)
    busy_total, op_time = 0.0, defaultdict(float)
    idle_by_span: Dict[str, float] = defaultdict(float)
    host = sorted(((b - a), n[len(SPAN_PREFIX):], a, b) for n, a, b in tr.host
                  if n != WINDOW_SPAN)
    all_gaps = []
    for plane in planes:
        evs = [e for e in tr.ops[plane] if e[2] > lo and e[1] < hi]
        busy = union(clip([(a, b) for _, a, b in evs], lo, hi))
        busy_total += covered(busy)
        for name, a, b in evs:
            if not CONTAINER.match(name):
                op_time[short(name)] += min(b, hi) - max(a, lo)
        for a, b in gaps(busy, lo, hi):
            mid = 0.5 * (a + b)
            label = next((n for _, n, s0, s1 in host if s0 <= mid <= s1),
                         "outside any span")
            idle_by_span[label] += (b - a) / len(planes)
            all_gaps.append((b - a, label))
    busy_s = busy_total / len(planes)
    out = {"window_s": window, "busy_s": busy_s,
           "idle_share": 1.0 - busy_s / window if window > 0 else None,
           "top_ops": [[n, s / len(planes)] for n, s in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
           "idle_by_span": [[n, s] for n, s in
                            sorted(idle_by_span.items(),
                                   key=lambda kv: -kv[1])[:top]],
           "longest_gaps": [[n, s] for s, n in sorted(all_gaps,
                                                      reverse=True)[:top]],
           "programs": {}, "kernels": {}}
    for tag, pattern in (programs or {}).items():
        rx = re.compile(pattern)
        n, secs = 0, 0.0
        for plane in planes:
            for name, a, b in tr.modules.get(plane, []):
                if rx.search(name) and b > lo and a < hi:
                    n += 1
                    secs += min(b, hi) - max(a, lo)
        out["programs"][tag] = {"count": n / len(planes),
                                "seconds": secs / len(planes)}
    for tag, (op_pat, prog_pat) in (kernels or {}).items():
        orx, prx = re.compile(op_pat), re.compile(prog_pat)
        n, secs = 0, 0.0
        for plane in planes:
            inside = union((a, b) for name, a, b in tr.modules.get(plane, [])
                           if prx.search(name))
            starts = [s0 for s0, _ in inside]
            for name, a, b in tr.ops[plane]:
                if orx.search(name) and b > lo and a < hi and _within(
                        inside, starts, a, b):
                    n += 1
                    secs += min(b, hi) - max(a, lo)
        out["kernels"][tag] = {"count": n / len(planes),
                               "seconds": secs / len(planes)}
    return out
