"""Flash attention (causal/windowed, GQA) as a Pallas TPU kernel.

TPU-native design:
  * head-major layout: q [B, H, Sq, D], k/v [B, K, Skv, D]. Batch and head block
    dims are squeezed (``None``), so every block's last two dims are
    (seq block, D) — tiling-aligned (multiples of 128, D a multiple of 128) or
    the whole array dim, as the TPU compiler requires.
  * grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the innermost grid dim is
    sequential on TPU, so VMEM scratch (acc/m/l) carries the online-softmax state
    across kv blocks — HBM→VMEM streams one (blk_q × d) q tile and one (blk_kv × d)
    k/v tile at a time.
  * blocks are sized from the shape (``pick_blocks``): as large as a VMEM budget
    allows, since every grid step has a fixed cost that small tiles cannot hide.
  * q·k and p·v run on the MXU in the inputs' dtype with f32 accumulation (p is
    cast to v's dtype); the scale, the masks and the online-softmax state
    (m, l, acc) stay f32.
  * m/l scratch and the lse output are (blk_q, 128) tiles with the value
    replicated across lanes: a 1-D or single-lane VMEM buffer does not tile.
  * GQA is expressed in the k/v BlockSpec index_map (q head h reads kv head h//group),
    so no repeat_kv materialization ever happens.
  * causal + sliding-window masks are computed from global block offsets (q token
    i sits at absolute position i + Skv - Sq). A fully-masked block skips its
    compute through pl.when, and its index_map points at the nearest reachable
    block, so it issues no k/v copy either.
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
BLOCK = 1024                # the widest q and kv blocks
VMEM_BUDGET = 16 * 2 ** 20  # the scoped VMEM a v5e kernel gets by default


def _vmem_bytes(blk_q: int, blk_kv: int, d: int, itemsize: int) -> int:
    """What one grid step holds in VMEM: double-buffered q, o, k, v and lse
    tiles, the f32 scratch, and the (blk_q, blk_kv) scores and weights in f32
    with the weights again in the inputs' dtype."""
    tiles = 2 * (2 * (blk_q + blk_kv) * d * itemsize + blk_q * LANES * 4)
    scratch = blk_q * (d + 2 * LANES) * 4
    return tiles + scratch + blk_q * blk_kv * (8 + itemsize)


def _fit(n: int, cap: int) -> int:
    """A block over a dim of ``n``: the whole dim when it is at most 128, else
    the largest multiple of 128 that is at most ``cap`` and ``n``."""
    return n if n <= LANES else min(cap, n) // LANES * LANES


def pick_blocks(seq_q: int, seq_kv: int, d: int, itemsize: int):
    """(blk_q, blk_kv) for a call: up to ``BLOCK`` each, halving the larger
    until a grid step fits ``VMEM_BUDGET``."""
    blk_q, blk_kv = _fit(seq_q, BLOCK), _fit(seq_kv, BLOCK)
    while (_vmem_bytes(blk_q, blk_kv, d, itemsize) > VMEM_BUDGET
           and max(blk_q, blk_kv) > LANES):
        if blk_kv >= blk_q:
            blk_kv = _fit(seq_kv, blk_kv // 2)
        else:
            blk_q = _fit(seq_q, blk_q // 2)
    return blk_q, blk_kv


def kv_block_index(i, j, *, blk_q: int, blk_kv: int, offset: int,
                   causal: bool, window: int, num_kv_blocks: int):
    """The kv block that grid step (q block ``i``, kv step ``j``) reads: ``j``
    where q block ``i`` can reach it, else the nearest block it can reach. A
    dead step so names the block of the step before it, and Pallas issues no
    copy for it."""
    q_start = i * blk_q + offset
    hi = num_kv_blocks - 1
    if causal:
        hi = jnp.minimum(hi, jax.lax.div(jnp.maximum(q_start + blk_q - 1, 0),
                                         blk_kv))
    lo = 0
    if window > 0:
        lo = jax.lax.div(jnp.maximum(q_start - window + 1, 0), blk_kv)
    return jnp.minimum(jnp.maximum(j, lo), hi)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, window: int, blk_q: int, blk_kv: int,
                 num_kv_blocks: int, offset: int, seq_kv: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    q_start = qi * blk_q + offset          # absolute position of the block's row 0
    q_last = q_start + blk_q - 1
    k_start = kj * blk_kv
    k_last = k_start + blk_kv - 1
    ragged = seq_kv % blk_kv != 0

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level reachability: skip compute for blocks entirely outside the mask
    reachable = jnp.asarray(True)
    if causal:
        reachable = jnp.logical_and(reachable, k_start <= q_last)
    if window > 0:
        reachable = jnp.logical_and(reachable, k_last > q_start - window)
    masked = causal or window > 0 or ragged

    @pl.when(reachable)
    def _compute():
        s = jax.lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        v = v_ref[...]
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            conds = []
            if ragged:
                conds.append(k_pos < seq_kv)
            if causal:
                conds.append(k_pos <= q_pos)
            if window > 0:
                conds.append(q_pos - k_pos < window)
            mask = functools.reduce(jnp.logical_and, conds)
            s = jnp.where(mask, s, NEG_INF)
            if ragged:
                # rows past the end of a ragged last block hold whatever was in
                # the buffer: zero them so 0 * garbage cannot turn into NaN
                row = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
                v = jnp.where(row < seq_kv, v, jnp.zeros_like(v))

        m_prev = m_ref[...]                                        # [blk_q, 128]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, :1])
        if masked:
            # a row masked through this block, with nothing before it, has
            # m_cur = NEG_INF and would read exp(0) = 1
            p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           blk_q: int | None = None, blk_kv: int | None = None,
                           interpret: bool = False):
    """q: [B, H, Sq, D]; k, v: [B, K, Skv, D] with H % K == 0. D must be 128-aligned
    (ops.py pads). Blocks default to ``pick_blocks`` of the shape. Returns
    (o [B, H, Sq, D], lse [B, H, Sq] float32)."""
    B, H, Sq, D = q.shape
    _, K, Skv, _ = k.shape
    assert H % K == 0, (H, K)
    group = H // K
    auto_q, auto_kv = pick_blocks(Sq, Skv, D, q.dtype.itemsize)
    blk_q = min(blk_q or auto_q, Sq)
    blk_kv = min(blk_kv or auto_kv, Skv)
    nq = pl.cdiv(Sq, blk_q)
    nkv = pl.cdiv(Skv, blk_kv)
    scale = 1.0 / math.sqrt(D)
    offset = Skv - Sq

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_kv=blk_kv, num_kv_blocks=nkv, offset=offset,
        seq_kv=Skv)
    kv_index = functools.partial(
        kv_block_index, blk_q=blk_q, blk_kv=blk_kv, offset=offset,
        causal=causal, window=window, num_kv_blocks=nkv)

    q_spec = pl.BlockSpec((None, None, blk_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, blk_kv, D),
                           lambda b, h, i, j: (b, h // group, kv_index(i, j), 0))
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
