"""Flash attention (causal/windowed, GQA) as a Pallas TPU kernel.

TPU-native design:
  * head-major layout: q [B, H, Sq, D], k/v [B, K, Skv, D]. Batch and head block
    dims are squeezed (``None``), so every block's last two dims are
    (seq block, D) — tiling-aligned (blocks of 128, D a multiple of 128) or the
    whole array dim, as the TPU compiler requires.
  * grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the innermost grid dim is
    sequential on TPU, so VMEM scratch (acc/m/l) carries the online-softmax state
    across kv blocks — HBM→VMEM streams one (blk_q × d) q tile and one (blk_kv × d)
    k/v tile at a time.
  * m/l scratch and the lse output are (blk_q, 128) tiles with the value
    replicated across lanes: a 1-D or single-lane VMEM buffer does not tile.
  * GQA is expressed in the k/v BlockSpec index_map (q head h reads kv head h//group),
    so no repeat_kv materialization ever happens.
  * causal + sliding-window masks are computed from global block offsets (q token
    i sits at absolute position i + Skv - Sq); fully-masked blocks still occupy
    grid slots but short-circuit through pl.when.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, window: int, blk_q: int, blk_kv: int,
                 num_kv_blocks: int, offset: int, seq_kv: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    q_start = qi * blk_q + offset          # absolute position of the block's row 0
    k_start = kj * blk_kv

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level reachability: skip compute for blocks entirely outside the mask
    q_last = q_start + blk_q - 1
    reachable = jnp.asarray(True)
    if causal:
        reachable = jnp.logical_and(reachable, k_start <= q_last)
    if window > 0:
        reachable = jnp.logical_and(reachable, k_start + blk_kv - 1 >= q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale                 # [blk_q, d]
        k = k_ref[...].astype(jnp.float32)                         # [blk_kv, d]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))    # [blk_q, blk_kv]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_kv
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window > 0:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        if seq_kv % blk_kv:
            # rows past the end of a ragged last block hold whatever was in the
            # buffer: zero them so 0 * garbage cannot turn into NaN
            row = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < seq_kv, v, 0.0)

        m_prev = m_ref[...]                                        # [blk_q, 128]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, :1])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_cur

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           blk_q: int = 128, blk_kv: int = 128,
                           interpret: bool = False):
    """q: [B, H, Sq, D]; k, v: [B, K, Skv, D] with H % K == 0. D must be 128-aligned
    (ops.py pads). Returns (o [B, H, Sq, D], lse [B, H, Sq] float32)."""
    B, H, Sq, D = q.shape
    _, K, Skv, _ = k.shape
    assert H % K == 0, (H, K)
    group = H // K
    blk_q = min(blk_q, Sq)
    blk_kv = min(blk_kv, Skv)
    nq = pl.cdiv(Sq, blk_q)
    nkv = pl.cdiv(Skv, blk_kv)
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_kv=blk_kv, num_kv_blocks=nkv, offset=Skv - Sq,
        seq_kv=Skv)

    q_spec = pl.BlockSpec((None, None, blk_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, blk_kv, D),
                           lambda b, h, i, j, g=group: (b, h // g, j, 0))
    lse_spec = pl.BlockSpec((None, None, blk_q, LANES),
                            lambda b, h, i, j: (b, h, i, 0))
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, LANES), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q, LANES), jnp.float32),
            pltpu.VMEM((blk_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]
