"""Step-level telemetry: rates, EMAs, the straggler-detector feed, and the
host loops' spans and counters.

The control agent heartbeats these numbers to the overwatch (`/telemetry/...`,
`/jobs/.../status.rate`); the dispatcher's straggler check compares job rates
against the fleet median — so everything here must be cheap and monotone.

Spans. ``LoopSpans`` wraps each phase of a host loop (``Trainer``,
``Server``, the checkpoint writer) in a ``jax.profiler.TraceAnnotation``
named ``repro.<loop>.<phase>``, and the loop's step in a
``StepTraceAnnotation``. A profiler trace then holds them on its host plane,
on the clock of the device's ops, so each idle gap of the device can be put
down to the phase the host was in. With no profiler session an annotation
costs about a microsecond, so they are always on. Names are fixed strings;
per-call facts (``step_num``, a prompt's ``length``) ride as the
annotation's stats. In memory each phase keeps its count and host seconds
in the loop's ``StepTimer``.

Counters. A loop counts ``host_transfers``, the arrays it brings to the
host; its steps and tokens are the ``StepTimer``'s.

Compiles. One ``jax.monitoring`` listener per process (``COMPILES``),
registered when this module is imported, totals the programs loaded and the
persistent-cache hits, and puts each load down to the innermost ``repro.*``
span open on the loading thread and the step its loop was in: which step
needed a new program, and in which phase.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import Counter, deque
from typing import Deque, Dict, List, Optional

import jax.monitoring
from jax.profiler import StepTraceAnnotation, TraceAnnotation

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _Local(threading.local):
    """Per thread: ``spans``, the open spans (innermost last)."""

    def __init__(self):
        self.spans: List["Span"] = []


_local = _Local()


@dataclasses.dataclass
class StepTimer:
    """EMA of step wall time + derived tokens/s. Pure-python, checkpoint-free."""
    tokens_per_step: int = 0
    alpha: float = 0.1
    ema_s: Optional[float] = None
    last_t: Optional[float] = None
    steps: int = 0
    # phase -> [spans closed, host seconds in them]
    phases: Dict[str, List[float]] = dataclasses.field(default_factory=dict)

    def tick(self, now: Optional[float] = None) -> Optional[float]:
        """Close a step at ``now`` (``time.perf_counter``; a step span passes
        its end)."""
        now = time.perf_counter() if now is None else now
        dt = None
        if self.last_t is not None:
            dt = now - self.last_t
            self.ema_s = dt if self.ema_s is None else (
                (1 - self.alpha) * self.ema_s + self.alpha * dt)
        self.last_t = now
        self.steps += 1
        return dt

    @property
    def steps_per_s(self) -> float:
        return 1.0 / self.ema_s if self.ema_s else 0.0

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_per_step * self.steps_per_s

    def add_phase(self, phase: str, seconds: float) -> None:
        total = self.phases.get(phase)
        if total is None:
            self.phases[phase] = [1, seconds]
        else:
            total[0] += 1
            total[1] += seconds

    @property
    def phase_ms(self) -> Dict[str, float]:
        """Mean host milliseconds per span of each phase."""
        return {p: 1e3 * s / n for p, (n, s) in self.phases.items()}

    def snapshot(self) -> dict:
        return {"steps": self.steps, "ema_step_s": self.ema_s,
                "steps_per_s": self.steps_per_s,
                "tokens_per_s": self.tokens_per_s,
                "phase_ms": self.phase_ms}


class Span:
    """One phase of a loop while it runs: the profiler annotation, an entry
    on this thread's stack of open spans (which the compile counter reads)
    and, when it closes, the phase's host seconds. ``t0`` and ``t1`` are
    its ``time.perf_counter`` stamps."""

    __slots__ = ("loop", "phase", "ann", "t0", "t1")

    def __init__(self, loop: "LoopSpans", phase: str, ann):
        self.loop, self.phase, self.ann = loop, phase, ann

    def __enter__(self) -> "Span":
        _local.spans.append(self)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        _local.spans.pop()
        self.loop.timer.add_phase(self.phase, t1 - self.t0)


class StepSpan(Span):
    """A loop's step: ticks the loop's timer with the span's end."""

    __slots__ = ()

    def __exit__(self, *exc) -> None:
        Span.__exit__(self, *exc)
        self.loop.timer.tick(self.t1)


class LoopSpans:
    """The spans and counters of one host loop, named ``repro.<loop>.*``.

    ``host_transfers`` counts the arrays the loop brings to the host;
    ``compiles`` the programs loaded under this loop's spans, by (span name,
    step)."""

    def __init__(self, loop: str, timer: Optional[StepTimer] = None):
        self.prefix = f"repro.{loop}."
        self.timer = timer if timer is not None else StepTimer()
        self.host_transfers = 0
        self.compiles: Counter = Counter()
        self.step_num: Optional[int] = None

    def span(self, phase: str, **stats) -> Span:
        return Span(self, phase, TraceAnnotation(self.prefix + phase, **stats))

    def step(self, step_num: int) -> StepSpan:
        self.step_num = step_num
        return StepSpan(self, "step", StepTraceAnnotation(
            self.prefix + "step", step_num=step_num))


class CompileCounter:
    """Programs loaded by this process, from JAX's own events. JAX times
    every load of a program under one event, whether the backend compiled
    it or the persistent cache held it, and counts a cache hit on its own;
    so ``since()["compiles"]`` (loads less hits) is what the backend
    compiled. Each load also counts under the innermost ``repro.*`` span
    open on the thread that loaded it: compiled or read from the cache, the
    loop waited for a program it did not have."""

    def __init__(self):
        self.seconds = 0.0
        self.loads = 0
        self.hits = 0
        self._lock = threading.Lock()

    def _event(self, event, **_):
        if event == CACHE_HIT:
            with self._lock:
                self.hits += 1

    def _duration(self, event, secs, **_):
        if event != BACKEND_COMPILE:
            return
        spans = _local.spans
        with self._lock:
            self.loads += 1
            self.seconds += secs
            if spans:
                top = spans[-1]
                loop = top.loop
                loop.compiles[loop.prefix + top.phase, loop.step_num] += 1

    def mark(self) -> tuple:
        return self.seconds, self.loads, self.hits

    def since(self, mark: tuple) -> dict:
        s, n, h = mark
        return {"compile_s": self.seconds - s, "loads": self.loads - n,
                "compiles": (self.loads - n) - (self.hits - h),
                "cache_hits": self.hits - h}


COMPILES = CompileCounter()
jax.monitoring.register_event_duration_secs_listener(COMPILES._duration)
jax.monitoring.register_event_listener(COMPILES._event)


@dataclasses.dataclass
class MetricsLog:
    """Bounded in-memory metrics ring (examples/tests read loss curves off it).

    The ring is a ``deque(maxlen=capacity)``: append past capacity evicts the
    oldest row in O(1) instead of the old list's O(n) front-slice on every
    overflowing append."""
    capacity: int = 4096
    rows: Deque = None

    def __post_init__(self):
        # maxlen depends on the capacity field, so it can't be a field default
        self.rows = deque(self.rows or (), maxlen=self.capacity)

    def log(self, step: int, metrics: Dict[str, float]) -> None:
        row = {"step": step}
        for k, v in metrics.items():
            try:
                row[k] = float(v)
            except (TypeError, ValueError):
                pass
        self.rows.append(row)

    def latest(self) -> Optional[dict]:
        return self.rows[-1] if self.rows else None

    def series(self, key: str) -> List[float]:
        return [r[key] for r in self.rows if key in r]
