"""The trace reduction, on interval arithmetic and on a small trace recorded
on a TPU v5e by ``data/record_trace.py``: three runs of a jitted pair of
matrix products (``bench.step``), a 30 ms host pause (``bench.host_pause``)
and one call of the Pallas flash kernel (``bench.prefill``), all inside
``bench.window``. The trace has no ``bench.traced`` span, so its window is
the span of its device events."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import trace_reduce as T  # noqa: E402

TRACE = str(Path(__file__).parent / "data" / "small.xplane.pb")
FLASH_OP = T.FLASH_FORWARD_OP


def test_union_merges_overlaps_and_drops_empty():
    assert T.union([(3, 4), (0, 1), (0.5, 2), (5, 5), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]
    assert T.covered(T.union([(0, 2), (1, 3), (10, 11)])) == 4


def test_clip_and_gaps():
    busy = T.clip(T.union([(-1, 1), (2, 3), (9, 12)]), 0, 10)
    assert busy == [(0, 1), (2, 3), (9, 10)]
    assert T.gaps(busy, 0, 10) == [(1, 2), (3, 9)]
    assert T.gaps([], 0, 1) == [(0, 1)]


@pytest.fixture(scope="module")
def summary():
    return T.reduce(TRACE, programs={"lambda": r"^jit__lambda"},
                    kernels={"flash": (FLASH_OP, r"^jit__lambda"),
                             "flash_elsewhere": (FLASH_OP, r"^jit_train")})


def test_busy_is_the_union_of_device_ops(summary):
    tr = T.Trace(TRACE)
    (plane,) = tr.ops
    lo, hi = tr.window()
    busy = T.union((a, b) for _, a, b in tr.ops[plane])
    assert summary["busy_s"] == pytest.approx(T.covered(busy))
    assert summary["window_s"] == pytest.approx(hi - lo)
    # three 2048^3 products pairs and one flash call: under a millisecond
    # of device work in a 34 ms window
    assert 0.5e-3 < summary["busy_s"] < 1e-3
    assert summary["idle_share"] == pytest.approx(
        1 - summary["busy_s"] / summary["window_s"])
    assert 0.97 < summary["idle_share"] < 0.99


def test_program_and_kernel_time(summary):
    assert summary["programs"]["lambda"]["count"] == 4
    # every op of these programs lies inside one of their four runs
    assert summary["programs"]["lambda"]["seconds"] == pytest.approx(
        summary["busy_s"], rel=1e-3)
    assert summary["kernels"]["flash"]["count"] == 1
    assert summary["kernels"]["flash"]["seconds"] == pytest.approx(
        178.371e-6, rel=1e-6)
    assert summary["kernels"]["flash_elsewhere"]["count"] == 0


def test_gaps_are_labelled_by_the_innermost_host_span(summary):
    label, secs = summary["longest_gaps"][0]
    assert label == "host_pause" and 0.030 < secs < 0.033
    assert summary["idle_by_span"][0][0] == "host_pause"
    assert {n for n, _ in summary["idle_by_span"]} <= {
        "host_pause", "step", "prefill", "window", "outside any span"}


def test_top_ops_hold_the_products_and_the_kernel(summary):
    names = [n for n, _ in summary["top_ops"][:3]]
    assert any("convolution" in n for n in names)
    assert any("custom" in n or "_lambda_" in n for n in names)
    assert all(len(n) <= 99 for n, _ in summary["top_ops"])
