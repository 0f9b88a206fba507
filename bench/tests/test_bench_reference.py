"""The benchmark's float32 reference against the program's ``Model`` at a
small size on the CPU, in float32 on both sides: forward logits, prefill then
decode through the cache, and the training loss with its gradients. The two
configurations' equations are covered: a tied head with a GQA group of 2 (as
qwen3-0.6b) and an untied head with a group of 8 (as qwen3-32b).

Every tolerance is float32 rounding: the two sides sum the same terms in
another order (blocked attention, einsum contraction order), which moves a
result by a few ulps of its magnitude, about 1e-6 relative per operation and
a few 1e-5 after a handful of layers; a wrong mask, scale, norm or rotation
moves it by 1e-2 or more.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import reference as R  # noqa: E402
from bench import weights as W  # noqa: E402

CASES = {
    "tied_group2": dict(hidden_size=64, intermediate_size=96,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, head_dim=32, vocab_size=256,
                        rope_theta=1e6, rms_norm_eps=1e-6,
                        tie_word_embeddings=True),
    "untied_group8": dict(hidden_size=64, intermediate_size=96,
                          num_hidden_layers=2, num_attention_heads=8,
                          num_key_value_heads=1, head_dim=16, vocab_size=256,
                          rope_theta=1e6, rms_norm_eps=1e-6,
                          tie_word_embeddings=False),
}
TOL = 1e-4          # relative to the largest magnitude compared


def program_model(arch: dict):
    from repro.configs.base import ArchConfig
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model
    from repro.parallel.sharding import MeshPlan
    cfg = ArchConfig(
        name="bench-test", family="dense",
        num_layers=arch["num_hidden_layers"], d_model=arch["hidden_size"],
        num_heads=arch["num_attention_heads"],
        num_kv_heads=arch["num_key_value_heads"], head_dim=arch["head_dim"],
        d_ff=arch["intermediate_size"], vocab_size=arch["vocab_size"],
        qk_norm=True, rope_theta=arch["rope_theta"],
        norm_eps=arch["rms_norm_eps"],
        tie_embeddings=arch["tie_word_embeddings"], dtype="float32",
        remat="none")
    return Model(cfg, MeshPlan(mesh=make_test_mesh(), fsdp=False))


def close(a, b, tol=TOL):
    a, b = np.asarray(a), np.asarray(b)
    return np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


@pytest.fixture(params=sorted(CASES))
def case(request):
    arch = CASES[request.param]
    w = W.reference_all(arch, seed=3, dtype=jnp.float32)
    return arch, w, program_model(arch)


def ref_logits(arch, w, tokens):
    with jax.default_matmul_precision("highest"):
        return R.served_logits(arch, w, lambda i: jax.tree_util.tree_map(
            lambda t: t[i], w["layers"]), tokens)


def test_forward_logits(case):
    arch, w, model = case
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0,
                                arch["vocab_size"])
    logits, _ = model.forward(W.to_program(w), {"tokens": tokens})
    assert close(logits, ref_logits(arch, w, tokens))


def test_prefill_then_decode_logits(case):
    arch, w, model = case
    seq = jax.random.randint(jax.random.PRNGKey(1), (1, 20), 0,
                             arch["vocab_size"])
    ref = ref_logits(arch, w, seq)[0]
    params = W.to_program(w)
    P = 14
    last, cache = model.prefill(params, {"tokens": seq[:, :P]}, max_len=32)
    assert close(last[0], ref[P - 1])
    for pos in range(P, 20):
        logits, cache = model.decode_step(params, seq[:, pos:pos + 1], cache)
        assert close(logits[0], ref[pos])


def test_train_loss_and_gradients(case):
    arch, w, model = case
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 17), 0,
                              arch["vocab_size"])
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:],
             "loss_mask": jnp.ones((2, 16), jnp.float32)}
    (loss, _), grads = jax.value_and_grad(model.loss_fn, has_aux=True)(
        W.to_program(w), batch)
    fn = R.make_loss_and_grad(arch)
    with jax.default_matmul_precision("highest"):
        parts = [fn(w, batch["tokens"][r], batch["targets"][r], 32.0)
                 for r in range(2)]
    ref_loss = sum(float(p[0]) for p in parts)
    ref_grads = jax.tree_util.tree_map(jnp.add, parts[0][1], parts[1][1])
    assert abs(float(loss) - ref_loss) <= TOL * ref_loss
    got = W.from_program(grads)
    for path, g in jax.tree_util.tree_leaves_with_path(ref_grads):
        mine = got
        for k in path:
            mine = mine[k.key]
        assert close(mine, g), jax.tree_util.keystr(path)


@pytest.mark.parametrize("block", [1, 8, 16, 37])
def test_attention_in_row_blocks_equals_one_block(block):
    """The reference runs attention and the MLP over blocks of rows, so that
    long sequences fit; any block size, the last one padded, gives what one
    block over the whole sequence gives."""
    arch = CASES["untied_group8"]
    w = W.reference_all(arch, seed=5, dtype=jnp.float32)
    p = jax.tree_util.tree_map(lambda t: t[0], w["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (37, arch["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        whole = R.layer(arch, p, x, block=37)
        got = R.layer(arch, p, x, block=block)
    assert close(got, whole)


def test_weights_stacked_equal_layer_by_layer():
    """The program's copy (all layers in one call) and the reference's (one
    layer at a time) hold the same numbers."""
    arch = CASES["untied_group8"]
    stacked = W.reference_all(arch, seed=2**31 + 77, dtype=jnp.bfloat16)
    layer = W.reference_layer(arch, seed=2**31 + 77, dtype=jnp.bfloat16)
    for i in range(arch["num_hidden_layers"]):
        for name, t in layer(i).items():
            np.testing.assert_array_equal(t, stacked["layers"][name][i])
    top = W.reference_top(arch, seed=2**31 + 77, dtype=jnp.bfloat16)
    for name, t in top.items():
        np.testing.assert_array_equal(t, stacked[name])
