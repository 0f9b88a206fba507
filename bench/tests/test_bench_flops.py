"""Operation and byte counts against hand counts, and the peaks table."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import flops as FL  # noqa: E402

ONE_LAYER = dict(hidden_size=1024, intermediate_size=3072,
                 num_hidden_layers=1, num_attention_heads=16,
                 num_key_value_heads=8, head_dim=128, vocab_size=151936)


def test_one_qwen3_layer_by_hand():
    # q 1024x2048, k and v 1024x1024, o 2048x1024, gate/up/down 1024x3072:
    # two operations per multiply-add
    q, kv, o = 2 * 1024 * 2048, 2 * 2 * 1024 * 1024, 2 * 2048 * 1024
    mlp = 3 * 2 * 1024 * 3072
    assert FL.dense_per_token(ONE_LAYER) == q + kv + o + mlp == 31_457_280
    # 4 tokens attend to 1+2+3+4 = 10 (query, key) pairs; q.k and p.v each
    # take 2 x 128 operations per pair and head
    assert FL.attention_scores(ONE_LAYER, FL.causal_pairs(4)) == \
        2 * 2 * 16 * 128 * 10
    assert FL.head(ONE_LAYER) == 2 * 1024 * 151936


def test_step_counts_compose():
    a = dict(ONE_LAYER, num_hidden_layers=28)
    fwd = FL.forward(a, 1024, 1024)
    assert FL.train_step(a, 2, 1024) == 3 * 2 * fwd
    assert FL.prefill(a, 1024) == fwd - 1023 * FL.head(a)
    assert FL.decode_step(a, [10, 20]) == (
        2 * (FL.dense_per_token(a) + FL.head(a))
        + FL.attention_scores(a, 30))


def test_flash_forward_by_hand():
    f = FL.flash_forward(batch=1, heads=8, kv_heads=1, seq_len=1024,
                         head_dim=128)
    assert f["ops"] == 4 * 8 * 128 * (1024 * 1025 // 2) == 2_149_580_800
    # q and o (8 heads), k and v (1 head) in bf16, the f32 lse per q row
    assert f["bytes"] == (2 * 8 * 1024 * 128 * 2 + 2 * 1024 * 128 * 2
                          + 8 * 1024 * 4)


def test_roofline_share_names_its_bound():
    peak = FL.peaks("TPU v5 lite")
    r = FL.roofline_share(197e12, 1.0, 2.0, peak)
    assert r == {"share": pytest.approx(0.5), "bound": "compute"}
    r = FL.roofline_share(1.0, 819e9, 4.0, peak)
    assert r == {"share": pytest.approx(0.25), "bound": "memory"}


def test_a_device_missing_from_the_table_is_an_error(tmp_path):
    with pytest.raises(KeyError, match="TPU v9"):
        FL.peaks("TPU v9")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"source": "x", "devices": {}}))
    with pytest.raises(KeyError):
        FL.peaks("TPU v5 lite", table)


def test_peaks_name_their_source():
    table = json.loads(FL.PEAKS.read_text())
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


READERS = sorted(p.stem for p in (ROOT / "bench" / "metrics").glob("*.py"))


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    from bench import harness as H
    assert H.metric_reader(name)({}) is None


def test_mfu_and_roofline_readings():
    obs = {"peak": FL.peaks("TPU v5 lite"),
           "trace": {"programs": {"train": {"count": 2, "seconds": 2.0}},
                     "kernels": {"flash.train": {"count": 4, "seconds": 1.0}}},
           "work": {"train": {"flops": 197e12, "calls": 2},
                    "flash.train": {"ops": 1.0, "bytes": 819e9 / 4,
                                    "calls": 4}}}
    assert FL.program_mfu(obs, "train") == pytest.approx(50.0)
    assert FL.kernel_roofline(obs, "flash.train") == pytest.approx(25.0)
    assert FL.program_mfu(obs, "decode") is None
    assert FL.kernel_roofline(obs, "flash.prefill") is None
