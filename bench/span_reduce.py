"""Reduce the program's own host spans in a profiler trace to time per phase
and the device's idle time inside each phase.

The program wraps each phase of its host loops in a ``TraceAnnotation``
named ``repro.<loop>.<phase>`` (``repro.runtime.telemetry``), so they lie on
the trace's host plane, on the clock of the device's ops. Over the window
(the ``bench.traced`` span, as in ``trace_reduce``), for each span name under
a prefix this gives:

- ``count``: the spans that overlap the window;
- ``seconds``: their host seconds in the window;
- ``self_s``: those seconds less what spans inside them cover;
- ``idle_s``: the device's idle seconds in them, where the innermost span
  open at an instant holds it (averaged over the device planes).

Besides: the idle that no such span covers; and each step span
(``<prefix>step``) that lies wholly in the window, with the seconds of the
spans inside it on its thread and the device's idle inside it, each by the
name of the innermost span. A trace without such spans, from a program that
has none, gives empty tables. ``summarise`` takes plain event lists.
``bench/phase_split.py`` runs a train cell's traced window and prints this
reduction.

The device's times reach the trace on a clock that may lead the host's:
traces recorded on a TPU v5e (``tests/data/*.xplane.pb``) show programs
starting 0.5 to 1.1 ms before the host span that enqueued them began. Given
the program that each ``<prefix>dispatch`` span enqueues, the k-th such
program cannot start before its dispatch span starts, nor end after the
``<prefix>sync`` span that follows it ends: that bounds the shift from
device to host times (``device_offset_s``). Where the device provably
leads, its intervals are moved by the least shift that satisfies both for
every step, before the idle is split. Each step's idle by span is also
given at both ends of the feasible shift (``idle_lo``, ``idle_hi``), since
the trace pins the shift down only to that range: how far the split of a
step's idle between its phases could move with the clock.
"""
from __future__ import annotations

import bisect
import re
import statistics
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace_reduce as T

OUTSIDE = "outside any span"

# a run of the program that starts further than this from a dispatch span
# is not matched to it
MATCH_S = 0.02

# (name, t0, t1, thread): a host span, in seconds on the profiler's clock
Event = Tuple[str, float, float, int]


def read(path: str, prefix: str, program: Optional[str] = None) -> tuple:
    """The spans named ``prefix*``, each with the index of its host thread,
    and the window, from a trace; the device's op intervals per plane as
    ``trace_reduce.Trace`` reads them; the runs of programs whose names
    match ``program``, per plane."""
    from jax.profiler import ProfileData
    tr = T.Trace(path)
    spans: List[Event] = []
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    spans.append((e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9, thread))
            thread += 1
    busy = {p: [(a, b) for _, a, b in evs] for p, evs in tr.ops.items()}
    rx = re.compile(program) if program else None
    runs = {p: sorted((a, b) for n, a, b in tr.modules.get(p, [])
                      if rx and rx.search(n)) for p in tr.ops}
    return spans, busy, runs, tr.window()


def reduce(path: str, prefix: str, program: Optional[str] = None) -> dict:
    """``program``: a regular expression on the names of the programs that
    the ``<prefix>dispatch`` spans enqueue, which bounds the device clock's
    offset."""
    spans, busy, runs, (lo, hi) = read(path, prefix, program)
    return summarise(spans, busy, lo, hi, prefix, runs)


def device_offset(spans: List[Event], runs: List[T.Interval], prefix: str,
                  lo: float, hi: float) -> dict:
    """Bounds on the shift from device to host times, from each dispatch
    span in the window and the run of its program that starts nearest to
    it, within ``MATCH_S``: ``lo`` from the runs' starts, ``hi`` from the
    ends of the sync spans that follow. ``applied`` is ``lo`` where the
    device leads (``lo`` > 0) and the bounds agree, else 0."""
    dispatch = sorted(a for n, a, b, _ in spans
                      if n == prefix + "dispatch" and lo <= a <= hi)
    syncs = sorted((a, b) for n, a, b, _ in spans if n == prefix + "sync")
    lows, highs, j = [], [], 0
    for d in dispatch:
        while j + 1 < len(runs) and \
                abs(runs[j + 1][0] - d) <= abs(runs[j][0] - d):
            j += 1
        if j == len(runs) or abs(runs[j][0] - d) > MATCH_S:
            continue
        lows.append(d - runs[j][0])
        k = bisect.bisect_left(syncs, (d,))
        if k < len(syncs):
            highs.append(syncs[k][1] - runs[j][1])
        j += 1
    low = max(lows) if lows else None
    high = min(highs) if highs else None
    ok = low is not None and low > 0 and (high is None or low <= high)
    return {"lo": low, "hi": high, "matched": len(lows),
            "applied": low if ok else 0.0}


def innermost(spans: List[Event], lo: float, hi: float) -> List[tuple]:
    """Split [lo, hi] at every span's edges into (index of the innermost
    span open, or None, a, b) pieces. The innermost of the spans open at an
    instant is the shortest (of equal ones, the later in the list)."""
    edges = sorted([(max(a, lo), 1, i) for i, (_, a, b, _) in enumerate(spans)
                    if b > lo and a < hi] +
                   [(min(b, hi), 0, i) for i, (_, a, b, _) in enumerate(spans)
                    if b > lo and a < hi])
    key = lambda i: (spans[i][2] - spans[i][1], -i)
    out, open_, t = [], set(), lo
    for x, starts, i in edges:
        if x > t:
            out.append((min(open_, key=key) if open_ else None, t, x))
            t = x
        (open_.add if starts else open_.discard)(i)
    if hi > t:
        out.append((None, t, hi))
    return out


def _piece_idle(pieces: List[tuple], busy: Dict[str, List[T.Interval]],
                shifts: Dict[str, float], lo: float, hi: float) -> List[float]:
    """The device's idle seconds in each piece, averaged over the planes,
    each plane's op intervals moved by its shift."""
    out = [0.0] * len(pieces)
    for plane, ops in busy.items():
        d = shifts[plane]
        gaps = T.gaps(T.union(T.clip([(a + d, b + d) for a, b in ops], lo,
                                     hi)), lo, hi)
        j = 0
        for i, (_, a, b) in enumerate(pieces):
            while j < len(gaps) and gaps[j][1] <= a:
                j += 1
            k = j
            while k < len(gaps) and gaps[k][0] < b:
                out[i] += (min(b, gaps[k][1]) - max(a, gaps[k][0])) / len(busy)
                k += 1
    return out


def _bound(offset: dict, end: str) -> float:
    """The shift at one end (``lo`` or ``hi``) of the feasible range, or the
    applied one where the trace gives no such range."""
    low, high = offset["lo"], offset["hi"]
    if low is None or high is None or low > high:
        return offset["applied"]
    return offset[end]


def summarise(spans: Iterable[Event], busy: Dict[str, List[T.Interval]],
              lo: float, hi: float, prefix: str,
              runs: Optional[Dict[str, List[T.Interval]]] = None) -> dict:
    """The reduction over [lo, hi] of host ``spans`` against the device's
    op intervals ``busy`` (per plane); ``runs`` the runs of the dispatched
    program per plane, which bound the device clock's offset on each
    plane."""
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    offsets = {p: device_offset(spans, (runs or {}).get(p, []), prefix, lo,
                                hi) for p in busy}
    pieces = innermost(spans, lo, hi)
    table: Dict[str, Dict[str, float]] = {}
    for name, a, b, _ in spans:
        if b > lo and a < hi:
            row = table.setdefault(name, {"count": 0, "seconds": 0.0,
                                          "self_s": 0.0, "idle_s": 0.0})
            row["count"] += 1
            row["seconds"] += min(b, hi) - max(a, lo)
    idle = {end: _piece_idle(pieces, busy, {
        p: (o["applied"] if end == "applied" else _bound(o, end))
        for p, o in offsets.items()}, lo, hi)
        for end in ("applied", "lo", "hi")}
    uncovered = 0.0
    for (who, a, b), secs in zip(pieces, idle["applied"]):
        if who is None:
            uncovered += secs
        else:
            table[spans[who][0]]["self_s"] += b - a
            table[spans[who][0]]["idle_s"] += secs
    total = sum(idle["applied"])
    return {"window_s": hi - lo, "idle_s": total,
            "uncovered_idle_s": uncovered,
            "coverage": 1.0 - uncovered / total if total > 0 else None,
            "spans": table,
            "steps": _steps(spans, pieces, idle, lo, hi, prefix + "step"),
            "device_offset_s": offsets}


def _steps(spans: List[Event], pieces: List[tuple], idle: Dict[str, list],
           lo: float, hi: float, step: str) -> List[dict]:
    """Each step span wholly in the window: its seconds, the seconds of the
    spans inside it on its thread by name, and the device's idle inside it
    by the innermost span's name (``idle``; ``idle_lo`` and ``idle_hi`` at
    the ends of the clock offset's range). ``spans`` sorted by start."""
    starts = [s[1] for s in spans]
    piece_starts = [p[1] for p in pieces]
    out = []
    for i, (name, a, b, th) in enumerate(spans):
        if name != step or a < lo or b > hi:
            continue
        phases: Dict[str, float] = defaultdict(float)
        for n, c, d, t in spans[i + 1:bisect.bisect_right(starts, b)]:
            if t == th and d <= b and n != step:
                phases[n] += d - c
        row = {"seconds": b - a, "phases": dict(phases)}
        inside = range(bisect.bisect_left(piece_starts, a),
                       bisect.bisect_left(piece_starts, b))
        for end, key in (("applied", "idle"), ("lo", "idle_lo"),
                         ("hi", "idle_hi")):
            by: Dict[str, float] = defaultdict(float)
            for j in inside:
                who = pieces[j][0]
                by[OUTSIDE if who is None else spans[who][0]] += idle[end][j]
            row[key] = dict(by)
        out.append(row)
    return out


def step_medians(summary: dict, prefix: str) -> Optional[dict]:
    """Two per-step figures, in ms, each a median over the whole steps:
    ``host_ms``, the step span less its ``<prefix>sync`` child (the loop's
    own host time, with the wait on the device taken out), and
    ``sync_idle_ms``, the device's idle inside ``<prefix>sync`` at the
    applied clock offset. None where the trace has no whole steps.

    Both are defined by where the program puts its spans, so a change that
    moves a ``<prefix>*`` span moves them too."""
    steps, sync = summary["steps"], prefix + "sync"
    if not steps:
        return None
    return {"host_ms": statistics.median(
                1e3 * (s["seconds"] - s["phases"].get(sync, 0.0))
                for s in steps),
            "sync_idle_ms": statistics.median(
                1e3 * s["idle"].get(sync, 0.0) for s in steps)}


def describe(summary: dict, prefix: str) -> str:
    """One line for a run's notes: the idle per phase, with the share of the
    window's idle the spans cover, and the median idle per step by phase at
    the applied clock offset and at the ends of its feasible range."""
    steps = summary["steps"]
    if not summary["spans"]:
        return f"program spans: no {prefix}* spans in the window"
    idle = summary["idle_s"]
    parts = ", ".join(
        f"{n[len(prefix):]} {r['idle_s']:.6f} s"
        for n, r in sorted(summary["spans"].items(),
                           key=lambda kv: -kv[1]["idle_s"]))
    cov: Optional[float] = summary["coverage"]

    def median_ms(key: str, name: str) -> float:
        return 1e3 * statistics.median(s[key].get(name, 0.0) for s in steps)

    names = sorted({n for s in steps for n in s["idle"]})
    per_step = ", ".join(
        f"{n[len(prefix):] if n.startswith(prefix) else n} "
        f"{median_ms('idle', n):.4f} ms ({median_ms('idle_lo', n):.4f} to "
        f"{median_ms('idle_hi', n):.4f})" for n in names)
    return (f"program spans: {len(steps)} steps; device idle {idle:.6f} s: "
            f"{parts}, outside any span {summary['uncovered_idle_s']:.6f} s "
            f"(covered {'n/a' if cov is None else f'{100 * cov:.2f}%'}); "
            f"device clock offset {summary['device_offset_s']}; "
            f"median idle per step at that offset (at the ends of its "
            f"feasible range): {per_step or 'no whole steps'}")
