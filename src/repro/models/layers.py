"""Shared layer primitives: RMSNorm, RoPE, SwiGLU MLP, GQA attention (train/prefill
via the flash kernel, decode via cache attention).

All functions are pure; parameters arrive as dicts produced by ``models.params`` and
activations carry logical-axis sharding constraints through the ``MeshPlan``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.parallel.sharding import MeshPlan, constrain


# GSPMD cannot partition a Pallas kernel: on a mesh of several devices each
# kernel below runs per shard under shard_map, on specs that keep whole every
# dim the kernel reduces over.
def _pallas_per_shard(plan: MeshPlan) -> bool:
    return ops.resolve_impl() == "pallas" and plan.mesh.size > 1


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float, plan: MeshPlan,
            logical=("batch",)) -> jax.Array:
    """RMSNorm over the last dim of x, whose leading dims are ``logical``
    (the rest are kept whole per shard)."""
    if not _pallas_per_shard(plan):
        return ops.rmsnorm(x, scale, eps=eps)
    spec = plan.spec(logical, x.shape[:len(logical)])
    return jax.shard_map(functools.partial(ops.rmsnorm, eps=eps),
                         mesh=plan.mesh, in_specs=(spec, P()),
                         out_specs=spec, check_vma=False)(x, scale)


def flash_attention(plan: MeshPlan, q, k, v, causal: bool, window: int = 0):
    """Flash attention over [B,S,H,D] q and [B,S,K,D] k/v. Per shard, batch
    is split over the batch axes and heads over the axis that splits the kv
    heads (q heads follow it, so each q head keeps its kv head)."""
    if not _pallas_per_shard(plan):
        return ops.flash_attention(q, k, v, causal=causal, window=window)
    batch_ax, _, head_ax = (tuple(plan.spec(("batch", "seq", "kv_heads"),
                                            k.shape[:3])) + (None,) * 3)[:3]
    spec = P(batch_ax, None, head_ax, None)
    fn = functools.partial(ops.flash_attention, causal=causal, window=window)
    return jax.shard_map(fn, mesh=plan.mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ------------------------------------------------------------------------------ RoPE
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D] (D even), positions: [B, S] int32."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)                                  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs     # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------------------- MLP
def swiglu(p: dict, x: jax.Array, plan: MeshPlan) -> jax.Array:
    pet = plan.reduce_dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w_gate"], preferred_element_type=pet)
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"], preferred_element_type=pet)
    h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * u.astype(x.dtype)
    h = constrain(h, plan, ("batch", "seq", "ffn"))
    # w_down contracts over the TP-sharded ffn dim: its output dtype IS the
    # all-reduce dtype (bf16 under plan.bf16_reduce)
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"], preferred_element_type=pet)
    return constrain(out.astype(x.dtype), plan, ("batch", "seq", None))


# -------------------------------------------------------------------------- attention
def _qk_norm(p: dict, q: jax.Array, k: jax.Array, eps: float,
             plan: MeshPlan):
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], eps, plan, ("batch", "seq", "heads"))
        k = rmsnorm(k, p["k_norm"], eps, plan, ("batch", "seq", "kv_heads"))
    return q, k


def qkv_project(p: dict, x: jax.Array, plan: MeshPlan, *,
                positions: Optional[jax.Array], theta: float, eps: float,
                kv_from: Optional[jax.Array] = None,
                kv_positions: Optional[jax.Array] = None):
    """Project q from x and k/v from ``kv_from`` (cross-attn) or x (self-attn)."""
    src = x if kv_from is None else kv_from
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    q, k = _qk_norm(p, q, k, eps, plan)
    if positions is not None:
        q = apply_rope(q, positions, theta)
        kp = kv_positions if kv_positions is not None else positions
        k = apply_rope(k, kp, theta)
    q = constrain(q, plan, ("batch", "seq", "heads", None))
    k = constrain(k, plan, ("batch", "seq", "kv_heads", None))
    v = constrain(v, plan, ("batch", "seq", "kv_heads", None))
    return q, k, v


def attn_out(p: dict, o: jax.Array, plan: MeshPlan) -> jax.Array:
    # wo contracts over TP-sharded heads: output dtype = all-reduce dtype
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"],
                     preferred_element_type=plan.reduce_dtype)
    return constrain(out.astype(o.dtype), plan, ("batch", "seq", None))


def attention(p: dict, x: jax.Array, plan: MeshPlan, *,
              positions: jax.Array, theta: float, eps: float,
              causal: bool = True, window: int = 0) -> jax.Array:
    """Full self-attention over a [B, S, D] block (train / prefill)."""
    q, k, v = qkv_project(p, x, plan, positions=positions, theta=theta, eps=eps)
    o = ops.flash_attention(q, k, v, causal=causal, window=window)
    o = constrain(o, plan, ("batch", "seq", "heads", None))
    return attn_out(p, o, plan)


def cross_attention(p: dict, x: jax.Array, memory: jax.Array, plan: MeshPlan, *,
                    eps: float) -> jax.Array:
    """Cross-attention of x [B, S, D] onto memory [B, M, D] (no mask, no RoPE)."""
    q, k, v = qkv_project(p, x, plan, positions=None, theta=0.0, eps=eps,
                          kv_from=memory)
    o = ops.flash_attention(q, k, v, causal=False)
    o = constrain(o, plan, ("batch", "seq", "heads", None))
    return attn_out(p, o, plan)


def decode_attention(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                     plan: MeshPlan, *, theta: float, eps: float,
                     window: int = 0) -> tuple:
    """One-token self-attention against a KV cache.

    x: [B, 1, D]; cache: {"k","v": [B, Smax, K, Dh]}; pos: [B] int32 (next index).
    Returns (out [B,1,D], new_cache).
    """
    positions = pos[:, None]
    q, k_new, v_new = qkv_project(p, x, plan, positions=positions, theta=theta,
                                  eps=eps)
    k_cache = _cache_update(cache["k"], k_new, pos)
    v_cache = _cache_update(cache["v"], v_new, pos)
    k_cache = constrain(k_cache, plan, ("batch", "cache_seq", "kv_heads", None))
    v_cache = constrain(v_cache, plan, ("batch", "cache_seq", "kv_heads", None))
    o = ops.attend_cache(q, k_cache, v_cache, pos[:, None, None, None],
                         window=window)
    o = constrain(o, plan, ("batch", "seq", "heads", None))
    return attn_out(p, o, plan), {"k": k_cache, "v": v_cache}


def _cache_update(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write new [B, 1, K, D] into cache [B, Smax, K, D] at per-row position pos."""
    B, Smax = cache.shape[0], cache.shape[1]
    onehot = jax.nn.one_hot(pos, Smax, dtype=cache.dtype)        # [B, Smax]
    return cache * (1 - onehot[:, :, None, None]) + onehot[:, :, None, None] * new
