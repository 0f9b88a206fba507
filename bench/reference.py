"""Plain Qwen3 decoder in float32 ``jax.numpy``: the yardstick that decides
``correct``.

It follows the published Qwen3 description (huggingface ``Qwen3ForCausalLM``):
RMSNorm before attention and MLP, per-head RMSNorm on q and k, rotary
embedding on the split halves of each head (theta from the configuration),
grouped-query attention with a causal mask, SwiGLU, a final RMSNorm and a tied
or untied output head. No kernels, no cache, no batching tricks; every matrix
product runs under ``default_matmul_precision("highest")``.

It imports nothing of the program. Weights come from ``bench.weights`` (made
from the seed, as the benchmark hands them to the program), never from the
program's state.

``compute_dtype`` rounds every weight and every operand of a matrix product
to a lower precision (float8 for the control run) while accumulating in
float32: the reference put in the program's place at the precision below the
one the configuration states.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

F32 = jnp.float32
# rows of a sequence whose attention scores and MLP activations are live at
# once: at 64 heads and 8,192 positions a block's f32 scores take 1 GB
ROW_BLOCK = 512


def _rounder(compute_dtype) -> Callable:
    if compute_dtype is None or jnp.dtype(compute_dtype) == F32:
        return lambda x: x
    dt = jnp.dtype(compute_dtype)
    return lambda x: x.astype(dt).astype(F32)


def rmsnorm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [S, H, hd], positions [S]: rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions[:, None].astype(F32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def blocks(fn, x, block: int):
    """fn over row blocks of x [S, ...] one after another (padding the last),
    so one block's intermediates are live at a time; fn(i, rows) gets the
    block's index too."""
    S = x.shape[0]
    nb = -(-S // block)
    xb = jnp.pad(x, [(0, nb * block - S)] + [(0, 0)] * (x.ndim - 1))
    out = jax.lax.map(lambda a: fn(a[0], a[1]),
                      (jnp.arange(nb), xb.reshape(nb, block, *x.shape[1:])))
    return out.reshape(nb * block, *out.shape[2:])[:S]


def attention(q, k, v, r, block: int = ROW_BLOCK):
    """Causal grouped-query attention of q [S, H, hd] over k, v [S, K, hd]:
    query head h reads kv head h // (H / K). Blocks of query rows each see
    every key, masked, so a block's scores [K, H/K, block, S] are all that
    is live."""
    S, H, hd = q.shape
    K = k.shape[1]
    kr, vr = r(k), r(v)
    keys = jnp.arange(S)

    def one(i, qi):
        qi = qi.reshape(qi.shape[0], K, H // K, hd)
        s = jnp.einsum("qkgd,tkd->kgqt", r(qi), kr) / math.sqrt(hd)
        pos = i * qi.shape[0] + jnp.arange(qi.shape[0])
        s = jnp.where((keys[None, :] <= pos[:, None])[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", r(a), vr).reshape(-1, H, hd)
    return blocks(one, q, min(block, S))


def layer(arch: dict, p: dict, x, compute_dtype=None, block: int = ROW_BLOCK):
    """One decoder layer over one sequence x [S, D] (f32); attention and the
    MLP run over blocks of ``block`` rows."""
    r = _rounder(compute_dtype)
    eps, theta = arch["rms_norm_eps"], arch["rope_theta"]
    S = x.shape[0]
    pos = jnp.arange(S)
    h = r(rmsnorm(x, p["ln1"], eps))
    q = jnp.einsum("sd,dhk->shk", h, r(p["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, r(p["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, r(p["wv"]))
    q = rope(rmsnorm(q, p["q_norm"], eps), pos, theta)
    k = rope(rmsnorm(k, p["k_norm"], eps), pos, theta)
    o = attention(q, k, v, r, block)
    x = x + jnp.einsum("shk,hkd->sd", r(o), r(p["wo"]))

    def mlp(_, xi):
        h = r(rmsnorm(xi, p["ln2"], eps))
        g = jnp.einsum("sd,df->sf", h, r(p["w_gate"]))
        u = jnp.einsum("sd,df->sf", h, r(p["w_up"]))
        m = r(jax.nn.silu(g) * u)
        return xi + jnp.einsum("sf,fd->sd", m, r(p["w_down"]))
    return blocks(mlp, x, min(block, S))


def head_table(arch: dict, top: dict):
    """[D, V] output projection: the embedding's transpose when tied."""
    return top["embed"].T if arch["tie_word_embeddings"] else top["unembed"]


def logits(arch: dict, top: dict, x, compute_dtype=None):
    r = _rounder(compute_dtype)
    h = r(rmsnorm(x, top["final_norm"], arch["rms_norm_eps"]))
    return jnp.einsum("sd,dv->sv", h, r(head_table(arch, top)))


def embed(top: dict, tokens, compute_dtype=None):
    return _rounder(compute_dtype)(top["embed"])[tokens].astype(F32)


# ------------------------------------------------------------------ training
def sequence_loss_sum(arch: dict, params: dict, tokens, targets,
                      compute_dtype=None):
    """Sum over one row of -log p(target): params hold every layer stacked
    on a leading axis; each layer is recomputed in the backward pass, so one
    layer's activations are live at a time."""
    x = embed(params, tokens, compute_dtype)
    body = jax.checkpoint(
        lambda x, p: (layer(arch, p, x, compute_dtype), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    lg = logits(arch, params, x, compute_dtype)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


def make_loss_and_grad(arch: dict, compute_dtype=None):
    """(params, tokens [S], targets [S], n_tokens) -> (loss share, grads) of
    one row; summing rows gives the batch's mean loss and its gradient."""
    def fn(params, tokens, targets, n_tokens):
        loss, g = jax.value_and_grad(sequence_loss_sum, argnums=1)(
            arch, params, tokens, targets, compute_dtype)
        return loss / n_tokens, jax.tree_util.tree_map(
            lambda t: t / n_tokens, g)
    return jax.jit(fn)


def warmup_cosine(step, opt: dict):
    """Linear warm-up to ``peak_lr`` over ``warmup_steps``, then cosine decay
    to ``lr_floor`` (default 0.1) of it at ``total_steps``."""
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    floor = opt.get("lr_floor", 0.1)
    frac = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return peak * jnp.where(step < warm, step / max(warm, 1), cos)


def adamw_step(params, grads, m, v, step, opt: dict):
    """Decoupled-weight-decay Adam after clipping the gradient's global norm;
    ``step`` counts from 1 (float32). Returns (params, m, v, unclipped
    global norm)."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    lr = warmup_cosine(step, opt)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, g, m_, v_):
        g = g * clip
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        p = p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + wd * p)
        return p, m_, v_

    out = jax.tree_util.tree_map(upd, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), gnorm


# ------------------------------------------------------------------- serving
def served_logits(arch: dict, top: dict, layer_weights: Callable[[int], dict],
                  rows, compute_dtype=None, picks=None):
    """Logits of each row of ``rows`` [N, S] (int32) over the whole sequence,
    layer by layer: ``layer_weights(i)`` gives layer i's f32 weights, so one
    layer is on the device at a time, and each row goes through it alone,
    so the programs' shapes do not depend on N. ``picks`` ((row, position)
    index arrays) keeps only those positions' logits, [len, V]; otherwise
    [N, S, V]."""
    run_layer = jax.jit(lambda p, x: layer(arch, p, x, compute_dtype))
    # weights go in as arguments, never as constants of a program
    emb = jax.jit(lambda w, t: embed(w, t, compute_dtype))
    head = jax.jit(lambda w, h: logits(arch, w, h, compute_dtype))
    with jax.default_matmul_precision("highest"):
        xs = [emb(top, row) for row in rows]
        for i in range(arch["num_hidden_layers"]):
            w = layer_weights(i)
            xs = [run_layer(w, x) for x in xs]
        x = jnp.stack(xs)
        if picks is not None:
            return head(top, x[picks[0], picks[1]])
        return jax.jit(jax.vmap(lambda w, h: logits(arch, w, h, compute_dtype),
                                in_axes=(None, 0)))(top, x)
