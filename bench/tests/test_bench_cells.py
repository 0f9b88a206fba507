"""``BENCHMARK.json`` against the benchmark's contract, the files it names,
the command's refusals, and that a new cell needs only data files and an
entry."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import harness as H  # noqa: E402

BENCH = H.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and UNIT.match(m["unit"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_name_finds_its_file():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        data = H.load_json(ROOT / c["file"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert data["published"][key] != data[key]
    for w in cells.values():
        assert w["config"] in configs
        traffic = H.traffic_file(w["traffic"])
        assert (ROOT / "bench" / "drivers" / f"{traffic['driver']}.py").is_file()
    for m in BENCH["per_layer"]:
        assert callable(H.metric_reader(m["name"]))
        assert set(m["workloads"]) <= set(cells)


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        mine = [n for n, m in e2e.items()
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        layer = [m for m in BENCH["per_layer"] if w["name"] in m["workloads"]]
        assert layer and all(m["moves"] in mine for m in layer)


def run_py(cwd: Path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_refuses_without_a_tpu():
    out = run_py(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = run_py(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_a_traffic_file_and_an_entry_make_a_cell(tmp_path):
    """A new mix is a data file plus a ``BENCHMARK.json`` entry: the copy of
    the benchmark below gains a cell without any file of it changing."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    small = dict(H.config_file("qwen3-0.6b"), hidden_size=64,
                 intermediate_size=128, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                 vocab_size=512)
    (tmp_path / "bench/configs/small.json").write_text(json.dumps(small))
    mix = dict(H.traffic_file("train.s1024"), seq_len=32, global_batch=2)
    (tmp_path / "bench/traffic/train.small.json").write_text(json.dumps(mix))
    bench = json.loads(json.dumps(BENCH))
    cell = {"name": "small.train.small", "config": "small",
            "traffic": "train.small", "chips": 1, "why": "test"}
    bench["workloads"].append(cell)
    for m in bench["end_to_end"]:
        if "train_tokens_per_s" == m["name"]:
            m["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    from bench import run as R
    line = R.run_cell(H.load_json(tmp_path / "BENCHMARK.json"),
                      H.find_cell(bench, cell["name"]), 2**31 + 3, 1.0, False,
                      time.perf_counter(), root=tmp_path)
    assert line["correct"], line["compared"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("kind", ["TPU v5 lite"])
def test_peaks_hold_the_device(kind):
    from bench import flops
    assert flops.peaks(kind)["bf16_flops_per_s"] == 197e12


def test_result_lines_traced_and_not():
    """A traced run reports the cell's per-layer metrics, the device's busy
    and window seconds and the breakdown; an untraced one its end-to-end
    metrics; both end in the compared numbers."""
    from bench import flops
    cell = H.find_cell(BENCH, "qwen3-0.6b.train.s1024")
    obs = {"peak": flops.peaks("TPU v5 lite"),
           "trace": {"busy_s": 0.9, "window_s": 1.0, "idle_share": 0.1,
                     "top_ops": [["%fusion.1", 0.5]],
                     "idle_by_span": [["train.step_once", 0.1]],
                     "programs": {"train": {"count": 2, "seconds": 0.8}},
                     "kernels": {"flash.train": {"count": 56,
                                                 "seconds": 0.1}}},
           "work": {"train": {"flops": 1e13, "calls": 2},
                    "flash.train": {"ops": 1e11, "bytes": 1e8, "calls": 56}}}
    out = H.Outcome(metrics={"train_tokens_per_s": 1.0, "setup_s": 2.0},
                    attempted=2, failed=0,
                    checks=[H.check("loss_gap", 0.001, 0.006)],
                    observed=obs, memory_peak_bytes=5)
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = H.result_line(out, cell, BENCH, dev, True)
    assert set(line["metrics"]) == {m["name"] for m in BENCH["per_layer"]
                                    if cell["name"] in m["workloads"]}
    assert line["metrics"]["idle_share.train"]["value"] == pytest.approx(10.0)
    assert line["device"]["busy_s"] == 0.9 and line["device"]["window_s"] == 1.0
    assert line["breakdown"]["idle_gaps"] == [["train.step_once", 0.1]]
    assert line["correct"] and list(line)[-1] == "compared"
    plain = H.result_line(out, cell, BENCH, dev, False)
    assert set(plain["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert "breakdown" not in plain and "busy_s" not in plain["device"]
    assert list(plain)[-1] == "compared"
