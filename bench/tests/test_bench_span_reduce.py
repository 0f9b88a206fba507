"""The reduction of the program's host spans, on synthetic intervals and on
a small trace recorded on a TPU v5e by ``data/record_train_trace.py``: a
reduced ``Trainer``'s three steps inside ``bench.traced``."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import span_reduce as S  # noqa: E402
from bench import trace_reduce as T  # noqa: E402

TRACE = str(Path(__file__).parent / "data" / "train_spans.xplane.pb")
P = "repro.train."
PHASES = ("batch", "dispatch", "sync", "log")


def step(t0, th=0, batch=1.0, dispatch=1.0, sync=4.0, log=1.0, tail=1.0):
    """One synthetic step's spans from ``t0``: its phases back to back, then
    ``tail`` seconds in the step after them."""
    out, t = [], t0
    for name, d in (("batch", batch), ("dispatch", dispatch), ("sync", sync),
                    ("log", log)):
        out.append((P + name, t, t + d, th))
        t += d
    return [(P + "step", t0, t + tail, th)] + out


def test_idle_goes_to_the_innermost_span():
    # step [0, 8): batch [0,1) dispatch [1,2) sync [2,6) log [6,7) tail [7,8)
    # device busy [1.5, 5): idle [0,1.5) and [5,8)
    out = S.summarise(step(0.0), {"d": [(1.5, 5.0)]}, 0.0, 8.0, P)
    spans = out["spans"]
    assert spans[P + "batch"]["idle_s"] == pytest.approx(1.0)
    assert spans[P + "dispatch"]["idle_s"] == pytest.approx(0.5)
    assert spans[P + "sync"]["idle_s"] == pytest.approx(1.0)
    assert spans[P + "log"]["idle_s"] == pytest.approx(1.0)
    assert spans[P + "step"]["idle_s"] == pytest.approx(1.0)   # the tail
    assert out["idle_s"] == pytest.approx(4.5)
    assert out["uncovered_idle_s"] == 0.0 and out["coverage"] == 1.0


def test_uncovered_idle_is_counted():
    # two steps with a 1 s host gap between them, and the window reaching
    # 1 s past each end: the gap and the ends are idle no span covers
    spans = step(1.0) + step(10.0)
    busy = {"d": [(3.0, 6.5), (12.0, 15.5)]}
    out = S.summarise(spans, busy, 0.0, 19.0, P)
    assert out["uncovered_idle_s"] == pytest.approx(1.0 + 1.0 + 1.0)
    covered = sum(r["idle_s"] for r in out["spans"].values())
    assert covered + out["uncovered_idle_s"] == pytest.approx(out["idle_s"])
    assert out["coverage"] == pytest.approx(1 - 3.0 / out["idle_s"])


def test_self_time_is_the_span_less_its_children():
    out = S.summarise(step(0.0, tail=2.5), {"d": []}, 0.0, 9.5, P)
    row = out["spans"][P + "step"]
    assert row["seconds"] == pytest.approx(9.5)
    assert row["self_s"] == pytest.approx(2.5)
    for name in PHASES:
        r = out["spans"][P + name]
        assert r["self_s"] == pytest.approx(r["seconds"])
    # a span nested in a phase takes its time out of that phase's self time
    inner = step(0.0) + [(P + "checkpoint", 6.0, 6.5, 0)]
    out = S.summarise(inner, {"d": []}, 0.0, 8.0, P)
    assert out["spans"][P + "log"]["self_s"] == pytest.approx(0.5)


def test_steps_hold_their_own_thread_s_children():
    spans = step(0.0) + step(8.0) + [("repro.train.sync", 1.0, 3.0, 1)]
    out = S.summarise(spans, {"d": []}, 0.0, 16.0, P)
    assert [s["seconds"] for s in out["steps"]] == [8.0, 8.0]
    assert out["steps"][0]["phases"] == {P + "batch": 1.0, P + "dispatch": 1.0,
                                         P + "sync": 4.0, P + "log": 1.0}
    # a step cut by the window's end is not a step of the window
    assert len(S.summarise(spans, {"d": []}, 0.0, 15.0, P)["steps"]) == 1


def test_idle_is_averaged_over_device_planes():
    busy = {"a": [(0.0, 8.0)], "b": [(0.0, 2.0)]}   # b idles in sync on
    out = S.summarise(step(0.0), busy, 0.0, 8.0, P)
    assert out["idle_s"] == pytest.approx(3.0)
    assert out["spans"][P + "sync"]["idle_s"] == pytest.approx(2.0)


def test_a_device_clock_that_leads_is_moved_back():
    """A step in ms: dispatch [1, 2), sync [2, 6). The device reports its
    program at [0.5, 4.75), before the dispatch that enqueued it: it leads
    by at least 0.5 ms and at most 6 - 4.75 = 1.25 ms, and its intervals
    move by 0.5 ms before the idle is split."""
    ms = 1e-3
    spans = step(0.0, batch=ms, dispatch=ms, sync=4 * ms, log=ms, tail=ms)
    run = [(0.5 * ms, 4.75 * ms)]
    early = S.summarise(spans, {"d": run}, 0.0, 8 * ms, P, runs={"d": run})
    off = early["device_offset_s"]["d"]
    assert off["lo"] == pytest.approx(0.5 * ms)
    assert off["hi"] == pytest.approx(1.25 * ms)
    assert off["applied"] == off["lo"] and off["matched"] == 1
    # busy [1, 5.25) ms: batch idles 1 ms, dispatch none, sync 0.75 ms
    assert early["spans"][P + "batch"]["idle_s"] == pytest.approx(ms)
    assert early["spans"][P + "dispatch"]["idle_s"] == pytest.approx(0.0)
    assert early["spans"][P + "sync"]["idle_s"] == pytest.approx(0.75 * ms)
    # a run that starts after its dispatch proves no lead: nothing moves
    run = [(1.75 * ms, 4.75 * ms)]
    late = S.summarise(spans, {"d": run}, 0.0, 8 * ms, P, runs={"d": run})
    assert late["device_offset_s"]["d"]["applied"] == 0.0
    assert late["spans"][P + "dispatch"]["idle_s"] == pytest.approx(0.75 * ms)
    # without the program's runs the trace's clocks stand
    plain = S.summarise(spans, {"d": [(0.5 * ms, 4.75 * ms)]}, 0.0, 8 * ms, P)
    assert plain["device_offset_s"]["d"]["applied"] == 0.0
    assert plain["device_offset_s"]["d"]["matched"] == 0


def test_the_chip_trace_shows_the_device_clock_leading():
    """``small.xplane.pb``: each ``bench.step`` (and ``bench.prefill``)
    enqueues one program and waits for it. Paired in order, every program
    starts on the device's clock 0.96 to 1.11 ms before its span does: the
    lead that ``device_offset`` bounds. (Its steps are too short, 1 ms, to
    match programs to spans by nearness, as ``device_offset`` does.)"""
    small = str(Path(__file__).parent / "data" / "small.xplane.pb")
    tr = T.Trace(small)
    (runs,) = tr.modules.values()
    runs = sorted(runs, key=lambda r: r[1])
    calls = sorted((s for s in tr.host
                    if s[0] in ("bench.step", "bench.prefill")),
                   key=lambda s: s[1])
    assert len(runs) == len(calls) == 4
    leads = [a - r[1] for (_, a, _), r in zip(calls, runs)]
    assert 0.95e-3 < min(leads) and max(leads) < 1.12e-3
    tails = [b - r[2] for (_, _, b), r in zip(calls, runs)]
    assert min(tails) > max(leads)          # a shift exists that fits all


def test_step_idle_is_split_inside_whole_steps_only():
    """Each whole step holds the idle of its own pieces, by innermost span;
    a step cut by the window's edge holds none, though its spans' idle
    still counts in the window's table."""
    spans = step(0.0) + step(8.0)
    busy = {"d": [(1.5, 5.0), (9.5, 13.0)]}
    out = S.summarise(spans, busy, 0.0, 15.0, P)
    (whole,) = out["steps"]
    assert whole["idle"] == pytest.approx({
        P + "batch": 1.0, P + "dispatch": 0.5, P + "sync": 1.0,
        P + "log": 1.0, P + "step": 1.0})
    assert sum(whole["idle"].values()) == pytest.approx(4.5)
    # the cut step's sync [10, 14) idles [13, 14) in the window
    assert out["spans"][P + "sync"]["idle_s"] == pytest.approx(2.0)
    assert S.step_medians(out, P)["sync_idle_ms"] == pytest.approx(1e3 * 1.0)


def test_step_idle_at_both_ends_of_the_clock_offset():
    """In ms: the device's run [0.5, 4.75) leads its dispatch [1, 2) by at
    least 0.5 and at most 1.25 (its sync ends at 6). At the least shift the
    run is [1, 5.25): sync [2, 6) idles 0.75; at the most, [1.75, 6): sync
    idles nothing and dispatch 0.75."""
    ms = 1e-3
    spans = step(0.0, batch=ms, dispatch=ms, sync=4 * ms, log=ms, tail=ms)
    run = [(0.5 * ms, 4.75 * ms)]
    out = S.summarise(spans, {"d": run}, -ms, 9 * ms, P, runs={"d": run})
    (row,) = out["steps"]
    assert row["idle"][P + "sync"] == pytest.approx(0.75 * ms)
    assert row["idle_lo"] == pytest.approx(row["idle"])
    assert row["idle_hi"].get(P + "sync", 0.0) == pytest.approx(0.0)
    assert row["idle_hi"][P + "dispatch"] == pytest.approx(0.75 * ms)
    assert sum(row["idle_hi"].values()) == pytest.approx(
        sum(row["idle"].values()))
    note = S.describe(out, P)
    assert "sync 0.7500 ms (0.7500 to 0.0000)" in note


def test_no_spans_gives_empty_tables_and_no_metrics():
    """A program without spans: no table, no steps, no per-step figures."""
    out = S.summarise([], {"d": [(1.0, 2.0)]}, 0.0, 4.0, P)
    assert out["spans"] == {} and out["steps"] == []
    assert out["uncovered_idle_s"] == pytest.approx(3.0)
    assert "no repro.train.* spans" in S.describe(out, P)
    assert S.step_medians(out, P) is None


def test_readers_on_synthetic_steps():
    spans = step(0.0, batch=0.002, dispatch=0.003, sync=0.125, log=0.0005,
                 tail=0.0005)
    spans += step(0.135, batch=0.002, dispatch=0.001, sync=0.127, log=0.0005,
                  tail=0.0005)
    busy = {"d": [(0.004, 0.124), (0.1385, 0.2605)]}
    out = S.summarise(spans, busy, 0.0, 0.27, P)
    per_step = S.step_medians(out, P)
    assert per_step["host_ms"] == pytest.approx(1e3 * (0.006 + 0.004) / 2)
    idle = per_step["sync_idle_ms"]
    # sync [0.005, 0.130) idles after 0.124; [0.138, 0.265) before 0.1385
    # and after 0.2605
    assert idle == pytest.approx(1e3 * (0.006 + 0.0005 + 0.0045) / 2)


# ------------------------------------------------------ the recorded traces
def test_reads_the_trace_of_bench_spans():
    """``small.xplane.pb`` (``data/record_trace.py``) read under the prefix
    ``bench.``: three ``bench.step`` spans, and the 30 ms host pause holds
    most of the device's idle."""
    small = str(Path(__file__).parent / "data" / "small.xplane.pb")
    out = S.reduce(small, "bench.")
    whole = T.reduce(small)
    assert out["idle_s"] == pytest.approx(
        whole["window_s"] - whole["busy_s"], rel=1e-9)
    assert out["spans"]["bench.step"]["count"] == len(out["steps"]) == 3
    pause = out["spans"]["bench.host_pause"]["idle_s"]
    assert 0.029 < pause < 0.031
    assert pause == max(r["idle_s"] for r in out["spans"].values())
    assert sum(r["idle_s"] for r in out["spans"].values()) + \
        out["uncovered_idle_s"] == pytest.approx(out["idle_s"])


@pytest.fixture(scope="module")
def recorded():
    return S.reduce(TRACE, P, r"^jit_train_step\(")


def test_trace_holds_three_steps_of_four_phases(recorded):
    assert len(recorded["steps"]) == 3
    for s in recorded["steps"]:
        assert set(s["phases"]) == {P + p for p in PHASES}
        assert sum(s["phases"].values()) <= s["seconds"]
    for name in ("step", *PHASES):
        assert recorded["spans"][P + name]["count"] == 3
    for s in recorded["steps"]:
        for key in ("idle", "idle_lo", "idle_hi"):
            assert sum(s[key].values()) <= s["seconds"] + 1e-12


def test_trace_idle_agrees_with_the_device_reduction(recorded):
    whole = T.reduce(TRACE)
    assert recorded["window_s"] == pytest.approx(whole["window_s"])
    assert recorded["idle_s"] == pytest.approx(
        whole["window_s"] - whole["busy_s"], rel=1e-6)
    rows = recorded["spans"].values()
    assert sum(r["idle_s"] for r in rows) + recorded["uncovered_idle_s"] == \
        pytest.approx(recorded["idle_s"])
    assert all(r["self_s"] <= r["seconds"] + 1e-12 for r in rows)
    assert recorded["coverage"] > 0.99
    # the reduced trainer's feed is eager: the host spends its steps there
    batch = recorded["spans"][P + "batch"]["idle_s"]
    assert batch == max(r["idle_s"] for r in rows)


def test_trace_device_clock_leads_and_is_moved(recorded):
    """In this recording each train program starts, on the device's clock,
    at least 0.50 ms before its dispatch span: the device's intervals move
    by that much, and each step's program still ends before its sync."""
    (off,) = recorded["device_offset_s"].values()
    assert off["matched"] == 3
    assert off["lo"] == pytest.approx(5.033e-4, abs=1e-6)
    assert off["applied"] == off["lo"] < off["hi"]


def test_trace_step_medians_split_each_step(recorded):
    """On the recorded steps: the host time per step is the step less its
    sync, and the sync's idle lies inside the sync."""
    per_step = S.step_medians(recorded, P)
    steps = recorded["steps"]
    host = sorted(1e3 * (s["seconds"] - s["phases"][P + "sync"])
                  for s in steps)
    assert per_step["host_ms"] == pytest.approx(host[1])
    assert 0.0 < per_step["host_ms"] < 1e3 * max(s["seconds"] for s in steps)
    for s in steps:
        assert s["idle"].get(P + "sync", 0.0) <= s["phases"][P + "sync"]
    assert 0.0 <= per_step["sync_idle_ms"] <= 1e3 * max(
        s["phases"][P + "sync"] for s in steps)


def test_phase_split_refuses_without_a_tpu():
    """``bench/phase_split.py`` exits non-zero and prints no line off a
    TPU, as ``bench/run.py`` does."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "phase_split.py"),
         "--workload", "qwen3-0.6b.train.s1024", "--seed", str(2**31 + 7)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 3 and out.stdout.strip() == ""
    assert "refusing to run" in out.stderr
