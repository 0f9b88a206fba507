"""Public kernel ops with platform dispatch.

impl resolution (``impl=None`` picks by backend, see ``resolve_impl``):
  * "pallas"  — pl.pallas_call TPU kernel: the default on a TPU, and only there
                (tests run it on the CPU with interpret=True)
  * "blocked" — pure-jnp block-streaming implementation of the same algorithm,
                in f32 throughout (the flash kernel multiplies bf16 inputs on
                the MXU), O(S)-memory: the default on every other backend (CPU,
                incl. the multi-pod dry-run)
  * "naive"   — ref.py oracle (small shapes / tests only)

Both ``flash_attention`` paths carry a custom VJP implementing the block-wise flash
backward (residuals are q, k, v, o, lse — O(S), never O(S^2)), so training at
4k–32k sequence lengths keeps linear attention memory on both forward and backward
passes; the Pallas forward emits its lse for it. ``rmsnorm``'s Pallas forward has a
jnp backward, since a pallas_call has no reverse-mode rule of its own.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

NEG_INF = -1e30


def resolve_impl(impl: Optional[str] = None) -> str:
    """The implementation a kernel op runs for ``impl`` (None: by backend)."""
    return impl or ("pallas" if jax.default_backend() == "tpu" else "blocked")


# --------------------------------------------------------------------------- attention
def _block_mask(q_start, blk_q, k_start, blk_kv, offset, causal, window, seq_kv):
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_kv), 0) + offset
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_kv), 1)
    mask = k_pos < seq_kv
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window > 0:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _flash_fwd_blocked(q, k, v, causal, window, blk_kv=512):
    """Online-softmax forward, scanning kv blocks. Returns (o, lse)."""
    B, Sq, H, D = q.shape
    _, Skv, K, _ = k.shape
    group = H // K
    scale = 1.0 / math.sqrt(D)
    offset = Skv - Sq  # q token i lives at absolute position i + offset
    blk = min(blk_kv, Skv)
    nkv = -(-Skv // blk)
    pad = nkv * blk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nkv, blk, K, D)
    vb = v.reshape(B, nkv, blk, K, D)
    qf = q.astype(jnp.float32) * scale

    def step(carry, inp):
        acc, m, l = carry
        j, kj, vj = inp
        kj = jnp.repeat(kj.astype(jnp.float32), group, axis=2)   # [B,blk,H,D]
        vj = jnp.repeat(vj.astype(jnp.float32), group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj)                # [B,H,Sq,blk]
        mask = _block_mask(0, Sq, j * blk, blk, offset, causal, window, Skv)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vj)
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    xs = (jnp.arange(nkv), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0))
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), xs)
    l = jnp.maximum(l, 1e-30)
    o = (acc / l[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)  # [B,Sq,H,D]
    lse = m + jnp.log(l)                                            # [B,H,Sq]
    return o, lse


def _flash_bwd_blocked(causal, window, blk_kv, res, do):
    q, k, v, o, lse = res
    B, Sq, H, D = q.shape
    _, Skv, K, _ = k.shape
    group = H // K
    scale = 1.0 / math.sqrt(D)
    offset = Skv - Sq
    blk = min(blk_kv, Skv)
    nkv = -(-Skv // blk)
    pad = nkv * blk - Skv
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    kb = jnp.moveaxis(kp.reshape(B, nkv, blk, K, D), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nkv, blk, K, D), 1, 0)

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.einsum("bqhd,bqhd->bhq", o.astype(jnp.float32), dof)  # [B,H,Sq]

    def step(dq, inp):
        j, kj, vj = inp
        kjr = jnp.repeat(kj.astype(jnp.float32), group, axis=2)
        vjr = jnp.repeat(vj.astype(jnp.float32), group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kjr) * scale
        mask = _block_mask(0, Sq, j * blk, blk, offset, causal, window, Skv)
        p = jnp.where(mask[None, None], jnp.exp(s - lse[..., None]), 0.0)
        dv_j = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vjr)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, kjr)
        dk_j = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        # fold GQA groups back onto kv heads
        dk_j = dk_j.reshape(B, blk, K, group, D).sum(axis=3)
        dv_j = dv_j.reshape(B, blk, K, group, D).sum(axis=3)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    xs = (jnp.arange(nkv), kb, vb)
    dq, (dkb, dvb) = jax.lax.scan(step, dq0, xs)
    dk = jnp.moveaxis(dkb, 0, 1).reshape(B, nkv * blk, K, D)[:, :Skv]
    dv = jnp.moveaxis(dvb, 0, 1).reshape(B, nkv * blk, K, D)[:, :Skv]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_blocked(q, k, v, causal, window, blk_kv):
    o, _ = _flash_fwd_blocked(q, k, v, causal, window, blk_kv)
    return o


def _flash_blocked_fwd(q, k, v, causal, window, blk_kv):
    o, lse = _flash_fwd_blocked(q, k, v, causal, window, blk_kv)
    return o, (q, k, v, o, lse)


_flash_blocked.defvjp(_flash_blocked_fwd, _flash_bwd_blocked)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_pallas(q, k, v, causal, window, interpret):
    return _flash_pallas_fwd(q, k, v, causal, window, interpret)[0]


def _flash_pallas_fwd(q, k, v, causal, window, interpret):
    """[B,S,H,D] in and out; the kernel runs head-major and also gives the
    lse the blocked backward needs."""
    hm = lambda t: t.transpose(0, 2, 1, 3)
    o, lse = flash_attention_pallas(hm(q), hm(k), hm(v), causal=causal,
                                    window=window, interpret=interpret)
    o = hm(o)
    return o, (q, k, v, o, lse)


def _flash_pallas_bwd(causal, window, interpret, res, do):
    return _flash_bwd_blocked(causal, window, 512, res, do)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


def _pad_head_dim(x, mult=128):
    D = x.shape[-1]
    pad = (-D) % mult
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, D


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: Optional[str] = None, blk_kv: int = 512,
                    interpret: bool = False):
    """q [B,Sq,H,D], k/v [B,Skv,K,D] -> [B,Sq,H,D]. GQA via H % K == 0."""
    impl = resolve_impl(impl)
    if impl == "naive":
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    if impl == "pallas":
        qp, D0 = _pad_head_dim(q)
        kp, _ = _pad_head_dim(k)
        vp, _ = _pad_head_dim(v)
        if qp.shape[-1] != D0:
            # keep the softmax scale of the true head dim
            qp = qp * math.sqrt(qp.shape[-1] / D0)
        out = _flash_pallas(qp, kp, vp, causal, window, interpret)
        return out[..., :D0]
    return _flash_blocked(q, k, v, causal, window, blk_kv)


def attend_cache(q, k_cache, v_cache, pos, *, window: int = 0,
                 packed: bool = False):
    """Decode-step attention: q [B,1,H,D] against a [B,Smax,K,D] cache where
    positions >= ``pos``+1 are not yet written. Plain einsum (q_len == 1).

    ``packed=True`` (§Perf decode lever): GQA grouped einsum directly against
    the bf16 cache — no ``jnp.repeat`` (group x) and no f32 cache copy (2x),
    i.e. up to 2·group x less cache read traffic; f32 happens only in the MXU
    accumulator (preferred_element_type)."""
    B, _, H, D = q.shape
    _, Smax, K, _ = k_cache.shape
    group = H // K
    if packed:
        qg = q.reshape(B, K, group, D)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                       preferred_element_type=jnp.float32) / math.sqrt(D)
        k_pos = jnp.arange(Smax)[None, None, None, :]
        mask = k_pos <= pos.reshape(B, 1, 1, 1)
        if window > 0:
            mask = jnp.logical_and(mask,
                                   pos.reshape(B, 1, 1, 1) - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v_cache,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, 1, H, D).astype(q.dtype)
    kk = jnp.repeat(k_cache.astype(jnp.float32), group, axis=2)
    vv = jnp.repeat(v_cache.astype(jnp.float32), group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kk) / math.sqrt(D)
    k_pos = jnp.arange(Smax)[None, None, None, :]
    mask = k_pos <= pos
    if window > 0:
        mask = jnp.logical_and(mask, pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
    return out.astype(q.dtype)


def attend_cache_ring(q, k_cache, v_cache, pos):
    """Decode attention against a ring-buffer window cache of size W.

    Slot s holds absolute position p_s = pos - ((pos - s) mod W); every live slot is
    inside the window by construction, so the only mask is p_s >= 0 (cold start).
    q [B,1,H,D]; k/v [B,W,K,D]; pos [B] (the position just written)."""
    B, _, H, D = q.shape
    _, W, K, _ = k_cache.shape
    group = H // K
    kk = jnp.repeat(k_cache.astype(jnp.float32), group, axis=2)
    vv = jnp.repeat(v_cache.astype(jnp.float32), group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kk) / math.sqrt(D)
    slots = jnp.arange(W)[None, :]
    p_slot = pos[:, None] - jnp.mod(pos[:, None] - slots, W)      # [B, W]
    mask = (p_slot >= 0)[:, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------- SSD scan
def _ssd_blocked(x, dt, a, bm, cm, chunk, init_state=None):
    """Chunked SSD in pure jnp (same math as the pallas kernel), vectorized over
    chunks with a lax.scan inter-chunk recurrence. Returns (y, final_state)."""
    B, S, H, P = x.shape
    N = bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q
    xf = x.reshape(B, nc, Q, H, P).astype(jnp.float32)
    dtf = dt.reshape(B, nc, Q, H).astype(jnp.float32)
    bf = bm.reshape(B, nc, Q, N).astype(jnp.float32)
    cf = cm.reshape(B, nc, Q, N).astype(jnp.float32)
    af = a.astype(jnp.float32)

    dta = dtf * af                                   # [B,nc,Q,H]
    cum = jnp.cumsum(dta, axis=2)
    seg = cum[:, :, -1, :]                           # [B,nc,H]

    # intra-chunk (dual quadratic form)
    li = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # [B,nc,Q,Q,H]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(tri[None, None, :, :, None], jnp.exp(li), 0.0)
    cb = jnp.einsum("bcin,bcjn->bcij", cf, bf)                # [B,nc,Q,Q]
    xdt = xf * dtf[..., None]                                 # [B,nc,Q,H,P]
    y_intra = jnp.einsum("bcij,bcijh,bcjhp->bcihp", cb, decay, xdt)

    # chunk states: S_c = sum_j exp(seg - cum_j) dt_j B_j (x_j)^T
    w = jnp.exp(seg[:, :, None, :] - cum) * dtf               # [B,nc,Q,H]
    states = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", bf, w, xf)  # [B,nc,H,N,P]

    # inter-chunk recurrence over c
    def step(h, inp):
        seg_c, st_c = inp                                     # [B,H], [B,H,N,P]
        h_out = h                                             # state entering chunk c
        h = h * jnp.exp(seg_c)[..., None, None] + st_c
        return h, h_out

    h0 = (jnp.zeros((B, H, N, P), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))
    hT, h_in = jax.lax.scan(step, h0, (jnp.moveaxis(seg, 1, 0),
                                       jnp.moveaxis(states, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                           # [B,nc,H,N,P]

    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp", cf, jnp.exp(cum), h_in)
    y = (y_intra + y_inter).reshape(B, Sp, H, P)[:, :S].astype(x.dtype)
    return y, hT


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_pallas(x, dt, a, bm, cm, chunk, interpret):
    """Head-major kernel call on [B,S,...] inputs; backward recomputes through
    the blocked path (same math)."""
    y = ssd_scan_pallas(x.transpose(0, 2, 1, 3),
                        dt.transpose(0, 2, 1)[:, :, None],
                        a.astype(jnp.float32), bm, cm, chunk=chunk,
                        interpret=interpret)
    return y.transpose(0, 2, 1, 3)


def _ssd_pallas_fwd(x, dt, a, bm, cm, chunk, interpret):
    return _ssd_pallas(x, dt, a, bm, cm, chunk, interpret), (x, dt, a, bm, cm)


def _ssd_pallas_bwd(chunk, interpret, res, dy):
    _, vjp = jax.vjp(lambda *t: _ssd_blocked(*t, chunk)[0], *res)
    return vjp(dy)


_ssd_pallas.defvjp(_ssd_pallas_fwd, _ssd_pallas_bwd)


def resolve_ssd_impl(impl: Optional[str], init_state, return_state: bool) -> str:
    """``resolve_impl`` for the SSD scan. The Pallas kernel starts from a zero
    state and returns no final state, so a call that passes one or asks for
    one runs "blocked" instead — on a TPU too. Every call the SSM and hybrid
    models make asks for the final state (ROADMAP 1.5)."""
    impl = resolve_impl(impl)
    if impl == "pallas" and (init_state is not None or return_state):
        return "blocked"
    return impl


def ssd_scan(x, dt, a, bm, cm, *, chunk: int = 256, impl: Optional[str] = None,
             init_state=None, return_state: bool = False, interpret: bool = False):
    impl = resolve_ssd_impl(impl, init_state, return_state)
    if impl == "naive":
        y, h = ref.ssd_ref(x, dt, a, bm, cm)
    elif impl == "pallas":
        # zero-padding the tail leaves every real position's output unchanged
        S = x.shape[1]
        Q = min(chunk, S)
        pad = (-S) % Q
        if pad:
            tail = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            x, dt, bm, cm = tail(x), tail(dt), tail(bm), tail(cm)
        y = _ssd_pallas(x, dt, a, bm, cm, Q, interpret)[:, :S]
        h = None
    else:
        y, h = _ssd_blocked(x, dt, a, bm, cm, chunk, init_state)
    return (y, h) if return_state else y


def ssd_decode_step(x, dt, a, bm, cm, state):
    """One-token SSD recurrence. x [B,1,H,P], dt [B,1,H], bm/cm [B,1,N],
    state [B,H,N,P] -> (y [B,1,H,P], new_state)."""
    xf = x[:, 0].astype(jnp.float32)
    dtf = dt[:, 0].astype(jnp.float32)
    bf = bm[:, 0].astype(jnp.float32)
    cf = cm[:, 0].astype(jnp.float32)
    decay = jnp.exp(dtf * a.astype(jnp.float32)[None, :])     # [B,H]
    inject = jnp.einsum("bn,bhp->bhnp", bf, xf * dtf[..., None])
    new_state = state.astype(jnp.float32) * decay[..., None, None] + inject
    y = jnp.einsum("bn,bhnp->bhp", cf, new_state)
    return y[:, None].astype(x.dtype), new_state.astype(state.dtype)


# --------------------------------------------------------------------------- rmsnorm
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm_pallas(x, scale, eps, interpret):
    return rmsnorm_pallas(x, scale, eps=eps, interpret=interpret)


def _rmsnorm_pallas_fwd(x, scale, eps, interpret):
    return _rmsnorm_pallas(x, scale, eps, interpret), (x, scale)


def _rmsnorm_pallas_bwd(eps, interpret, res, dy):
    x, scale = res
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    xhat = xf * r
    g = dy.astype(jnp.float32) * scale.astype(jnp.float32)
    dx = r * (g - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
    dscale = jnp.sum(dy.astype(jnp.float32) * xhat,
                     axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dscale.astype(scale.dtype)


_rmsnorm_pallas.defvjp(_rmsnorm_pallas_fwd, _rmsnorm_pallas_bwd)


def rmsnorm(x, scale, *, eps: float = 1e-6, impl: Optional[str] = None,
            interpret: bool = False):
    """Pallas on a TPU; anywhere else the jnp form (ref.py), which XLA
    fuses as well as any blocked variant would."""
    if resolve_impl(impl) == "pallas":
        return _rmsnorm_pallas(x, scale, eps, interpret)
    return ref.rmsnorm_ref(x, scale, eps=eps)
