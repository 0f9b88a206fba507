"""The control at a size a test run holds: the float32 reference put in the
program's place at the precision below the configuration's (float8 e4m3 for
bfloat16) must fail at least one of each cell's limits, as it does at the
cells' own sizes on the chip (``bench/calibrate.py``; readings in PERF.md).
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness as H  # noqa: E402

F8 = jnp.float8_e4m3fn
SMALL = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             vocab_size=512, rope_theta=1e6, rms_norm_eps=1e-6,
             tie_word_embeddings=True)


def test_train_control_fails_a_limit():
    train = H.driver_module("train")
    t = H.traffic_file("train.s1024")
    args = (SMALL, 2**31 + 21, 2, 64, t["opt"], t["check"]["steps"],
            jnp.bfloat16)
    ref = train.reference_readings(*args)
    ctl = train.compare(train.reference_readings(*args, compute_dtype=F8), ref)
    limits = t["check"]["limits"]
    assert any(ctl[k] > limits[k] for k in limits), ctl


def test_serve_control_fails_the_limit():
    serve = H.driver_module("serve")
    t = H.traffic_file("serve.chat")
    arch = dict(SMALL, hidden_size=256, intermediate_size=512,
                num_hidden_layers=4, num_attention_heads=8,
                num_key_value_heads=1, head_dim=16, tie_word_embeddings=False)
    rng = np.random.default_rng(0)
    sample = [(rng.integers(0, 512, n).tolist(),
               rng.integers(0, 512, 32).tolist(), list(range(32)))
              for n in (16, 40)]
    gap = serve.control_gap(arch, 2**31 + 22, sample, 128, jnp.bfloat16, F8)
    assert gap > t["check"]["limits"]["served_logit_gap"], gap
