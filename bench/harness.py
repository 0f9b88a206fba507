"""What every cell's run shares: finding a cell's files by name, the device
check, host spans, compile counting, the traced window and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its ``config``
names ``bench/configs/<config>.json`` and its ``traffic`` names
``bench/traffic/<traffic>.json``; the traffic file's ``driver`` names
``bench/drivers/<driver>.py``; each per-layer metric ``m`` is read by
``bench/metrics/<m>.py``. Adding a cell, a mix or a metric adds files and
entries; no file here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
SPAN_PREFIX = "bench."


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------- files
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(benchmark: dict, workload: str) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def config_file(name: str, root: Path = ROOT) -> dict:
    return load_json(root / "bench" / "configs" / f"{name}.json")


def traffic_file(name: str, root: Path = ROOT) -> dict:
    return load_json(root / "bench" / "traffic" / f"{name}.json")


def driver_module(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "drivers" / f"{name}.py",
                       f"bench_driver_{name}")


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")).read


# --------------------------------------------------------------------- device
def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(n_chips: int) -> None:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:          # no backend could start
        raise NoDevice(str(e)) from e
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < n_chips:
        raise NoDevice(f"the cell asks for {n_chips} chips, JAX found "
                       f"{len(devs)}")


def memory_peak_bytes(n_chips: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept."""
    import os
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def open_cell(workload: str, root: Path = ROOT) -> tuple:
    """What every entry point does first: look the cell up in
    ``BENCHMARK.json``, refuse (``NoDevice``) without a TPU holding as many
    chips as it asks for, and turn the persistent compile cache on.
    Returns (benchmark, cell)."""
    benchmark = load_json(root / "BENCHMARK.json")
    cell = find_cell(benchmark, workload)
    require_tpu(cell["chips"])
    enable_compile_cache()
    return benchmark, cell


class CompileLog:
    """Programs loaded, from JAX's own events. JAX times every load of a
    program under one event, whether the backend compiled it or the
    persistent cache held it; a cache hit is also counted on its own, so
    ``compiles`` (loads less hits) is what the backend compiled."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.loads = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def compiles(self) -> int:
        return self.loads - self.hits

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.seconds += secs
            self.loads += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def mark(self):
        return self.seconds, self.loads, self.hits

    def since(self, mark) -> dict:
        s, n, h = mark
        return {"load_s": self.seconds - s, "loads": self.loads - n,
                "compiles": (self.loads - n) - (self.hits - h),
                "cache_hits": self.hits - h}

    def describe(self) -> str:
        return (f"{self.loads} programs loaded in {self.seconds:.3f} s, "
                f"{self.compiles} compiled, {self.hits} from the cache")


# ---------------------------------------------------------------------- spans
class Spans:
    """Host spans around the benchmark's calls into each layer, on
    ``time.perf_counter`` and, through ``TraceAnnotation``, in the
    profiler's trace, where they label the device's idle gaps."""

    def __init__(self):
        self.records: List[tuple] = []      # (name, t0, t1)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))

    def wrap(self, name: str, fn: Callable) -> Callable:
        def wrapped(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        return wrapped

    def of(self, name: str) -> List[tuple]:
        return [r for r in self.records if r[0] == name]


# ---------------------------------------------------------------------- run
@dataclasses.dataclass
class Run:
    """One run of one cell, as the drivers see it."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float
    spans: Spans = dataclasses.field(default_factory=Spans)
    compile_log: Optional[CompileLog] = None

    def note(self, text: str) -> None:
        print(text, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back: end-to-end metrics, counts, the compared
    numbers with their limits, and what the per-layer readers read."""
    metrics: Dict[str, float]
    attempted: int
    failed: int
    checks: List[dict]                 # {"name", "value", "limit"}
    observed: dict
    memory_peak_bytes: Optional[int] = None


def check(name: str, value, limit) -> dict:
    """A compared number passes when it is a number at or under its limit;
    NaN, a missing number or a crash reads as a failure."""
    ok = value is not None and value == value and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


class TracedWindow:
    """A profiler trace taken through the window, in a temporary directory
    removed once the trace is reduced. The profiler starts before the window
    (``open``) and stops after it (``close``), since starting and stopping it
    stall the host for a while; the part of the window that the reduction
    reads lies between ``start`` and ``stop``, marked by the host span
    ``bench.traced``."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Optional[str] = None
        self.active = False
        self.t0 = self.t1 = None
        self._ann = None

    def open(self) -> None:
        if self.enabled:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)

    def start(self) -> None:
        if self.dir is None or self.active or self.t1 is not None:
            return
        import jax
        self.active = True
        self.t0 = time.perf_counter()
        self._ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + "traced")
        self._ann.__enter__()

    def stop(self) -> None:
        if self.active:
            self._ann.__exit__(None, None, None)
            self.t1 = time.perf_counter()
            self.active = False

    def close(self) -> None:
        self.stop()
        if self.dir is not None:
            import jax
            jax.profiler.stop_trace()

    def covers(self, t: float) -> bool:
        return self.t0 is not None and self.t0 <= t <= self.t1

    def path(self) -> Optional[str]:
        if self.dir is None:
            return None
        found = sorted(glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True))
        return found[-1] if found else None

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def result_line(out: Outcome, cell: dict, benchmark: dict, device: dict,
                traced: bool, root: Path = ROOT) -> dict:
    """The run's result, printed as the last line of standard output: the
    end-to-end metrics, or with ``traced`` the per-layer ones, read from
    what the cell's driver observed; the compared numbers come last."""
    ok = all(c["ok"] for c in out.checks) and bool(out.checks)
    trace_summary = out.observed.get("trace") if traced else None
    if trace_summary is None:
        metrics = {m["name"]: {"value": out.metrics[m["name"]],
                               "unit": m["unit"]}
                   for m in benchmark["end_to_end"] if m["name"] in out.metrics}
    else:
        metrics = {}
        for m in benchmark["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            value = metric_reader(m["name"], root)(out.observed)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    line = {"correct": ok, "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": dev}
    if trace_summary is not None:
        dev["busy_s"] = trace_summary["busy_s"]
        dev["window_s"] = trace_summary["window_s"]
        line["breakdown"] = {"device_ops": trace_summary["top_ops"],
                             "idle_gaps": trace_summary["idle_by_span"]}
    line["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in out.checks}
    return line
