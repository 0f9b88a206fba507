"""Weights and inputs made from the run's seed, in the benchmark's own layout.

Every leaf of every layer is drawn from its own key, folded from the seed,
the leaf's name and the layer's index, so the program's copy (all layers in
one jitted call, on the device, in the dtype it is served in) and the
reference's copy (one layer at a time, in float32) hold the same numbers
without either reading the other.

Matrices are normal with standard deviation 1/sqrt(fan_in); the embedding and
the output head 1/sqrt(hidden_size), so logits start near unit scale whether
the head is tied or not; norm scales are 1 + 0.05 * normal, so a scale that a
program drops or applies twice shows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                "w_gate", "w_up", "w_down")
TOP_LEAVES = ("embed", "unembed", "final_norm")


def seed_key(seed: int, *path: int) -> jax.Array:
    """A key from a seed of up to 64 bits and a path of small integers."""
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    for p in path:
        key = jax.random.fold_in(key, np.uint32(p))
    return key


def layer_shapes(arch: dict) -> dict:
    """leaf -> (shape, fan_in); fan_in None marks a norm scale."""
    D, H, K = (arch["hidden_size"], arch["num_attention_heads"],
               arch["num_key_value_heads"])
    hd, F = arch["head_dim"], arch["intermediate_size"]
    return {"ln1": ((D,), None), "ln2": ((D,), None),
            "wq": ((D, H, hd), D), "wk": ((D, K, hd), D), "wv": ((D, K, hd), D),
            "wo": ((H, hd, D), H * hd),
            "q_norm": ((hd,), None), "k_norm": ((hd,), None),
            "w_gate": ((D, F), D), "w_up": ((D, F), D), "w_down": ((F, D), F)}


def top_shapes(arch: dict) -> dict:
    D, V = arch["hidden_size"], arch["vocab_size"]
    out = {"embed": ((V, D), D), "final_norm": ((D,), None)}
    if not arch["tie_word_embeddings"]:
        out["unembed"] = ((D, V), D)
    return out


def _draw(key, shape, fan_in, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    w = 1.0 + 0.05 * z if fan_in is None else z * (fan_in ** -0.5)
    # round to the served dtype: both copies then hold the served numbers
    return w.astype(dtype)


def layer_weights(arch: dict, seed_k: jax.Array, i, dtype) -> dict:
    """Layer i's leaves from the key ``seed_key(seed)``."""
    key = jax.random.fold_in(jax.random.fold_in(seed_k, 1), i)
    return {name: _draw(jax.random.fold_in(key, j), shape, fan, dtype)
            for j, (name, (shape, fan)) in enumerate(layer_shapes(arch).items())}


def top_weights(arch: dict, seed_k: jax.Array, dtype) -> dict:
    key = jax.random.fold_in(seed_k, 0)
    return {name: _draw(jax.random.fold_in(key, TOP_LEAVES.index(name)),
                        shape, fan, dtype)
            for name, (shape, fan) in top_shapes(arch).items()}


def all_weights(arch: dict, seed_k: jax.Array, dtype) -> dict:
    """Top leaves plus every layer stacked on a leading axis; layers are made
    one after another (``lax.map``), so only one layer's float32 draw is live."""
    layers = jax.lax.map(lambda i: layer_weights(arch, seed_k, i, dtype),
                         jnp.arange(arch["num_hidden_layers"]))
    return dict(top_weights(arch, seed_k, dtype), layers=layers)


@functools.lru_cache(maxsize=None)
def _jitted_layer(arch_items: tuple, dtype_name: str):
    arch = dict(arch_items)
    return jax.jit(lambda k, i: jax.tree_util.tree_map(
        lambda t: t.astype(jnp.float32),
        layer_weights(arch, k, i, jnp.dtype(dtype_name))))


def reference_layer(arch: dict, seed: int, dtype):
    """i -> layer i's weights in float32 (the served numbers), for the
    reference's layer-by-layer passes."""
    fn = _jitted_layer(tuple(sorted(arch.items())), jnp.dtype(dtype).name)
    k = seed_key(seed)
    return lambda i: fn(k, jnp.int32(i))


def reference_top(arch: dict, seed: int, dtype) -> dict:
    fn = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda t: t.astype(jnp.float32), top_weights(arch, k, dtype)))
    return fn(seed_key(seed))


def reference_all(arch: dict, seed: int, dtype) -> dict:
    """Every weight in float32, layers stacked (small models only)."""
    fn = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda t: t.astype(jnp.float32), all_weights(arch, k, dtype)))
    return fn(seed_key(seed))


def to_program(tree: dict) -> dict:
    """The benchmark's layout -> the program's parameter tree
    (``repro.models.params.param_defs`` for a dense qk-norm model)."""
    L = tree["layers"]
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"],
           "layers": {"ln1": L["ln1"], "ln2": L["ln2"],
                      "attn": {k: L[k] for k in ("wq", "wk", "wv", "wo",
                                                 "q_norm", "k_norm")},
                      "mlp": {k: L[k] for k in ("w_gate", "w_up", "w_down")}}}
    if "unembed" in tree:
        out["unembed"] = tree["unembed"]
    return out


def from_program(tree: dict) -> dict:
    """Inverse of ``to_program``."""
    L = tree["layers"]
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"],
           "layers": dict(ln1=L["ln1"], ln2=L["ln2"], **L["attn"], **L["mlp"])}
    if "unembed" in tree:
        out["unembed"] = tree["unembed"]
    return out


# ---------------------------------------------------------------- train rows
def token_rows(seed_k: jax.Array, step, batch: int, seq_len: int,
               vocab: int) -> dict:
    """Step ``step``'s batch: rows of uniform token ids, targets shifted by
    one; every row and every step draws its own ids."""
    key = jax.random.fold_in(jax.random.fold_in(seed_k, 2), step)
    toks = jax.random.randint(key, (batch, seq_len + 1), 0, vocab, jnp.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:],
            "loss_mask": jnp.ones((batch, seq_len), jnp.bfloat16)}
