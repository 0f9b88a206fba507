"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e, at
real widths: what the chip's compiler refuses (tiling, VMEM, layouts) fails
here, with no chip attached. Each compile asserts that the kernel is in the
program (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one process
may load the TPU library, and under several pytest workers only the worker
that runs this file does.
"""
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.trace_reduce import FLASH_FORWARD_OP  # noqa: E402
from repro.configs import base as configs  # noqa: E402
from repro.kernels import ops  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_shapes(S, B=1):
    cfg = configs.get("qwen3-0.6b")
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return [((B, S, H, D), jnp.bfloat16)] + [((B, S, K, D), jnp.bfloat16)] * 2


def _flash(q, k, v):
    return ops.flash_attention(q, k, v, impl="pallas")


@pytest.mark.parametrize("S", [512, 5])      # prefill, and a 5-token prompt
def test_flash_forward_compiles(one_chip, S):
    text = _compile_text(_flash, *_flash_shapes(S), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_flash_forward_is_the_benchmarks_kernel(one_chip):
    """At the train cell's shape (B 2, S 1024) the forward is one custom call,
    in the form the benchmark's trace reader times as the flash kernel."""
    text = _compile_text(_flash, *_flash_shapes(1024, B=2), sharding=one_chip)
    assert len(re.findall(FLASH_FORWARD_OP, text)) == 1


@pytest.mark.parametrize("S", [512, 5])
def test_flash_gradient_compiles(one_chip, S):
    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v).astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    text = _compile_text(grad, *_flash_shapes(S), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles(one_chip):
    D = configs.get("qwen3-0.6b").d_model
    text = _compile_text(lambda x, s: ops.rmsnorm(x, s, impl="pallas"),
                         ((4, 256, D), jnp.bfloat16), ((D,), jnp.bfloat16),
                         sharding=one_chip)
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles(one_chip):
    cfg = configs.get("mamba2-2.7b")
    H, P, N, Q = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_chunk
    assert (H, P, N, Q) == (80, 64, 128, 256)
    B, S = 1, 2 * Q
    text = _compile_text(
        lambda *a: ops.ssd_scan(*a, chunk=Q, impl="pallas"),
        ((B, S, H, P), jnp.bfloat16), ((B, S, H), jnp.float32),
        ((H,), jnp.float32), ((B, S, N), jnp.bfloat16),
        ((B, S, N), jnp.bfloat16), sharding=one_chip)
    assert "tpu_custom_call" in text
