"""Mamba-2 (SSD) block: in-proj -> causal depthwise conv -> selective state-space
scan (kernels.ops.ssd_scan) -> gated RMSNorm -> out-proj.

Single B/C group (G=1) as in the assigned mamba2/zamba2 configs. The scan runs
chunked (SSD dual form) for train/prefill; decode carries a [B, H, N, P] state and a
(W-1)-token conv tail.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import layers as LY
from repro.parallel.sharding import MeshPlan, constrain


def _causal_conv(x: jax.Array, kernel: jax.Array,
                 tail: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. x: [B, S, C], kernel: [W, C], tail: [B, W-1, C]
    (previous tokens, for decode). Returns (y [B,S,C], new_tail [B,W-1,C])."""
    W = kernel.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)                      # [B, S+W-1, C]
    S = x.shape[1]
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for w in range(W):
        y = y + xp[:, w : w + S].astype(jnp.float32) * kernel[w].astype(jnp.float32)
    new_tail = xp[:, S:]                                         # last W-1 inputs
    return y.astype(x.dtype), new_tail


def ssm_block(cfg: ArchConfig, p: dict, x: jax.Array, plan: MeshPlan, *,
              state: Optional[dict] = None, return_state: bool = False):
    """x: [B, S, D]. state (decode): {"conv": [B,W-1,DI+2N], "ssd": [B,H,N,P]}.
    Returns y [B,S,D] (and the new state when ``return_state``)."""
    B, S, D = x.shape
    DI, N, Hs, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim

    z = jnp.einsum("bsd,de->bse", x, p["w_z"])                   # gate branch
    xs = jnp.einsum("bsd,de->bse", x, p["w_x"])
    bm = jnp.einsum("bsd,dn->bsn", x, p["w_b"])
    cm = jnp.einsum("bsd,dn->bsn", x, p["w_c"])
    dt = jnp.einsum("bsd,dh->bsh", x, p["w_dt"])
    z = constrain(z, plan, ("batch", "seq", "ffn"))
    xs = constrain(xs, plan, ("batch", "seq", "ffn"))

    conv_in = jnp.concatenate([xs, bm.astype(xs.dtype), cm.astype(xs.dtype)], -1)
    conv_k = jnp.concatenate([p["conv_x"], p["conv_b"], p["conv_c"]], -1)
    conv_tail = None if state is None else state["conv"]
    conv_out, new_tail = _causal_conv(conv_in, conv_k, conv_tail)
    conv_out = jax.nn.silu(conv_out.astype(jnp.float32)).astype(xs.dtype)
    xs, bm, cm = (conv_out[..., :DI], conv_out[..., DI : DI + N],
                  conv_out[..., DI + N :])

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))     # [B,S,Hs] > 0
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                 # [Hs] < 0

    xh = xs.reshape(B, S, Hs, P)
    xh = constrain(xh, plan, ("batch", "seq", "ssm_heads", None))
    if state is None:
        y, new_ssd = ops.ssd_scan(xh, dt, a, bm, cm, chunk=cfg.ssm_chunk,
                                  return_state=True)
    else:
        y, new_ssd = ops.ssd_decode_step(xh, dt, a, bm, cm, state["ssd"])
    y = y + xh * p["d_skip"].astype(xh.dtype)[None, None, :, None]
    y = y.reshape(B, S, DI)

    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)   # gated
    y = LY.rmsnorm(y, p["gate_norm"], cfg.norm_eps, plan)
    y = constrain(y, plan, ("batch", "seq", "ffn"))
    out = jnp.einsum("be,ed->bd", y.reshape(B * S, DI),
                     p["out_proj"]).reshape(B, S, D)
    out = constrain(out, plan, ("batch", "seq", None))
    if return_state:
        return out, {"conv": new_tail, "ssd": new_ssd}
    return out


def abstract_ssm_state(cfg: ArchConfig, batch: int) -> dict:
    DI, N = cfg.d_inner, cfg.ssm_state
    W = cfg.ssm_conv_width
    return {
        "conv": jax.ShapeDtypeStruct((batch, W - 1, DI + 2 * N),
                                     jnp.dtype(cfg.dtype)),
        "ssd": jax.ShapeDtypeStruct((batch, cfg.ssm_heads, N, cfg.ssm_head_dim),
                                    jnp.float32),
    }


def init_ssm_state(cfg: ArchConfig, batch: int) -> dict:
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  abstract_ssm_state(cfg, batch))
