"""A whole run of each driver at a small size on the CPU, past the harness's
look for a chip: a sound program comes out ``correct``, and the same run with
the timed path broken underneath comes out not correct, once for each fault
the cell can have. The limits are the cells' own, from their traffic files.

The cells run on one chip, so "the exchange between chips left out" has no
place here.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import pytest  # noqa: E402

from bench import harness as H  # noqa: E402
from bench import run as R  # noqa: E402

SMALL = {"source": "test", "registry_base": "qwen3-0.6b", "reduced": [],
         "assumed": [], "deployment": "test", "head_dim": 32,
         "hidden_size": 64, "intermediate_size": 128,
         "max_position_embeddings": 4096, "num_attention_heads": 4,
         "num_hidden_layers": 2, "num_key_value_heads": 2,
         "rms_norm_eps": 1e-06, "rope_theta": 1000000,
         "tie_word_embeddings": True, "torch_dtype": "bfloat16",
         "vocab_size": 512}
SMALL_UNTIED = dict(SMALL, registry_base="qwen3-32b", num_attention_heads=8,
                    num_key_value_heads=1, tie_word_embeddings=False)
BENCH = H.load_json(ROOT / "BENCHMARK.json")


def train_traffic():
    t = H.traffic_file("train.s1024")
    return dict(t, seq_len=64, global_batch=4)


def serve_traffic():
    t = H.traffic_file("serve.chat")
    # arrivals faster than 4 slots serve them: every slot is busy
    return dict(t, slots=4, max_len=128, rate_per_s=40.0,
                prompt={"median": 16, "sigma": 0.5,
                        "lengths": [8, 16, 24, 40]},
                output={"median": 24, "sigma": 0.5, "min": 16, "max": 48},
                grace_s=10.0,
                check=dict(t["check"], requests=4, tokens_per_request=16))


def run_small(name, config, traffic, seconds=1.0):
    cell = {"name": f"test.{name}", "config": f"test-{name}",
            "traffic": "test", "chips": 1}
    return R.run_cell(BENCH, cell, 2**31 + 11, seconds, False,
                      time.perf_counter(), config=config, traffic=traffic)


def broken_train_step(fault):
    from repro.launch import steps

    real = steps.make_train_step

    def make(model, opt_cfg, num_microbatches, *a, **kw):
        step = real(model, opt_cfg, num_microbatches, *a, **kw)

        def broken(state, batch):
            if fault == "unchanged":
                return state, step(state, batch)[1]
            half = jax.tree_util.tree_map(
                lambda x: x[: x.shape[0] // 2], batch)
            return step(state, half)
        return broken
    return make


def test_sound_train_run_is_correct():
    line = run_small("train", SMALL, train_traffic())
    assert line["correct"], line["compared"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(fault, monkeypatch):
    from repro.runtime import train_loop
    monkeypatch.setattr(train_loop, "make_train_step",
                        broken_train_step(fault))
    line = run_small(f"train-{fault}", SMALL, train_traffic())
    assert not line["correct"], line["compared"]


def test_sound_serve_run_is_correct():
    line = run_small("serve", SMALL_UNTIED, serve_traffic())
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("slot", [0, 3])
def test_altered_token_is_not_correct(slot, monkeypatch):
    from repro.runtime.serve_loop import Server
    real = Server._sample

    def altered(self, logits):
        tok = real(self, logits)
        # every batched decode step serves another id in one slot
        if logits.shape[0] > 1:
            tok = tok.at[slot].set((tok[slot] + 1) % logits.shape[-1])
        return tok
    monkeypatch.setattr(Server, "_sample", altered)
    line = run_small(f"serve-altered-{slot}", SMALL_UNTIED, serve_traffic())
    assert not line["correct"], line["compared"]
