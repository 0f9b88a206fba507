"""Model facade: one ``Model`` class covering all assigned architecture families.

Forward structure per family (all stacks are ``lax.scan``-rolled over stacked layer
params; patterned archs reshape to (groups, period) and unroll the period inside the
scan body so per-position static attributes — sliding window, cross-attn — stay
static):

  dense   : [attn -> mlp] x L        (gemma3: period = local:global pattern)
  moe     : [attn -> moe] x L        (+ aux load-balance loss through the scan carry)
  ssm     : [mamba2 SSD] x L
  hybrid  : [[ssd x k] -> shared attn+mlp block] x G, then tail ssd layers
  encdec  : encoder [attn -> mlp] x Le  ->  decoder [attn -> xattn -> mlp] x L
  vlm     : [[attn -> mlp] x (k-1) -> gated xattn -> mlp] x (L/k)

Three entry points per model: ``forward`` (train), ``prefill`` (KV/state cache
build + last-token logits) and ``decode_step`` (one token against the cache). Cache
layouts are declared once as ``TensorDef`` trees, giving abstract/materialized/
PartitionSpec views from the same declaration (mirroring models.params).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import layers as LY
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.params import (abstract_params, init_params, param_defs,
                                 partition_specs)
from repro.parallel.sharding import MeshPlan, constrain

tmap = jax.tree_util.tree_map


# ------------------------------------------------------------------- cache declaration
@dataclasses.dataclass(frozen=True)
class TensorDef:
    shape: Tuple[int, ...]
    dtype: Any
    logical: Tuple[Optional[str], ...]

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _is_tdef(x) -> bool:
    return isinstance(x, TensorDef)


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if mode == "dots" else jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(fn, policy=policy)


def _period(cfg: ArchConfig) -> int:
    return cfg.local_global_pattern + 1 if cfg.local_global_pattern else 1


def _window_for(cfg: ArchConfig, j: int) -> int:
    """Static sliding window for period position j (gemma3: j<pattern => local)."""
    if cfg.local_global_pattern and j < cfg.local_global_pattern:
        return cfg.sliding_window or 0
    return 0


def _ring_slice(k: jax.Array, W: int) -> jax.Array:
    """Convert full-sequence K/V [B,S,...] to ring layout [B,W,...] (slot = pos%W)."""
    S = k.shape[1]
    if S < W:
        pad = [(0, 0)] * k.ndim
        pad[1] = (0, W - S)
        return jnp.pad(k, pad)
    assert S % W == 0, f"prefill length {S} must be a multiple of window {W}"
    return k[:, -W:]


# ----------------------------------------------------------------------- layer blocks
def _self_attn(cfg: ArchConfig, plan: MeshPlan, p: dict, h: jax.Array,
               positions: jax.Array, window: int, causal: bool = True):
    q, k, v = LY.qkv_project(p, h, plan, positions=positions,
                             theta=cfg.rope_theta, eps=cfg.norm_eps)
    o = LY.flash_attention(plan, q, k, v, causal, window)
    o = constrain(o, plan, ("batch", "seq", "heads", None))
    return LY.attn_out(p, o, plan), k, v


def _cross_attn(cfg: ArchConfig, plan: MeshPlan, p: dict, h: jax.Array,
                memory: jax.Array):
    q, k, v = LY.qkv_project(p, h, plan, positions=None, theta=0.0,
                             eps=cfg.norm_eps, kv_from=memory)
    o = LY.flash_attention(plan, q, k, v, causal=False)
    o = constrain(o, plan, ("batch", "seq", "heads", None))
    return LY.attn_out(p, o, plan), k, v


def _cross_attn_cached(cfg: ArchConfig, plan: MeshPlan, p: dict, h: jax.Array,
                       k: jax.Array, v: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    M = k.shape[1]
    full = jnp.full((h.shape[0],), M - 1, jnp.int32)
    o = ops.attend_cache(q, k, v, full[:, None, None, None],
                         packed=cfg.packed_decode)
    return LY.attn_out(p, o, plan)


def _ff(cfg: ArchConfig, plan: MeshPlan, p: dict, h: jax.Array, decode: bool):
    """Feed-forward: SwiGLU or MoE (returns (y, aux))."""
    if cfg.family == "moe" and "moe" in p:
        if decode:
            return MOE.moe_block_decode(cfg, p["moe"], h, plan), 0.0
        return MOE.moe_block(cfg, p["moe"], h, plan)
    return LY.swiglu(p["mlp"], h, plan), 0.0


def _block(cfg: ArchConfig, plan: MeshPlan, p: dict, x: jax.Array,
           positions: jax.Array, window: int, want_kv: bool,
           memory: Optional[jax.Array] = None, causal: bool = True):
    """attn [-> xattn] -> ff. Returns (x, kv, xkv, aux)."""
    h = LY.rmsnorm(x, p["ln1"], cfg.norm_eps, plan)
    a, k, v = _self_attn(cfg, plan, p["attn"], h, positions, window, causal)
    x = x + a
    kv = {"k": k, "v": v} if want_kv else None
    xkv = None
    if "xattn" in p:
        h = LY.rmsnorm(x, p["ln3"], cfg.norm_eps, plan)
        a, xk, xv = _cross_attn(cfg, plan, p["xattn"], h, memory)
        x = x + a
        xkv = {"k": xk, "v": xv} if want_kv else None
    h = LY.rmsnorm(x, p["ln2"], cfg.norm_eps, plan)
    y, aux = _ff(cfg, plan, p, h, decode=False)
    return x + y, kv, xkv, aux


def _block_decode(cfg: ArchConfig, plan: MeshPlan, p: dict, x: jax.Array,
                  cache: dict, pos: jax.Array, window: int,
                  xkv: Optional[dict] = None):
    """Decode variant of ``_block``; cache is {"k","v"} (ring when window > 0)."""
    h = LY.rmsnorm(x, p["ln1"], cfg.norm_eps, plan)
    positions = pos[:, None]
    q, k_new, v_new = LY.qkv_project(p["attn"], h, plan, positions=positions,
                                     theta=cfg.rope_theta, eps=cfg.norm_eps)
    if window > 0:
        W = cache["k"].shape[1]
        slot = jnp.mod(pos, W)
        k_c = LY._cache_update(cache["k"], k_new, slot)
        v_c = LY._cache_update(cache["v"], v_new, slot)
        k_c = constrain(k_c, plan, ("batch", "cache_seq", "kv_heads", None))
        v_c = constrain(v_c, plan, ("batch", "cache_seq", "kv_heads", None))
        o = ops.attend_cache_ring(q, k_c, v_c, pos)
    else:
        k_c = LY._cache_update(cache["k"], k_new, pos)
        v_c = LY._cache_update(cache["v"], v_new, pos)
        k_c = constrain(k_c, plan, ("batch", "cache_seq", "kv_heads", None))
        v_c = constrain(v_c, plan, ("batch", "cache_seq", "kv_heads", None))
        o = ops.attend_cache(q, k_c, v_c, pos[:, None, None, None],
                             packed=cfg.packed_decode)
    o = constrain(o, plan, ("batch", "seq", "heads", None))
    x = x + LY.attn_out(p["attn"], o, plan)
    if "xattn" in p:
        h = LY.rmsnorm(x, p["ln3"], cfg.norm_eps, plan)
        x = x + _cross_attn_cached(cfg, plan, p["xattn"], h, xkv["k"], xkv["v"])
    h = LY.rmsnorm(x, p["ln2"], cfg.norm_eps, plan)
    y, _ = _ff(cfg, plan, p, h, decode=True)
    return x + y, {"k": k_c, "v": v_c}


# ----------------------------------------------------------- attention-family stacks
def _grouped(cfg: ArchConfig, params_layers: dict):
    period = _period(cfg)
    if period == 1:
        return params_layers
    G = cfg.num_layers // period
    assert G * period == cfg.num_layers
    return tmap(lambda a: a.reshape((G, period) + a.shape[1:]), params_layers)


def _stack_fwd(cfg: ArchConfig, plan: MeshPlan, params: dict, x: jax.Array,
               positions: jax.Array, memory: Optional[jax.Array] = None,
               want_kv: bool = False, causal: bool = True):
    """dense / moe / encdec-decoder stack. Returns (x, kvs, xkvs, aux)."""
    period = _period(cfg)
    lp = _grouped(cfg, params["layers"])
    windows = [_window_for(cfg, j) for j in range(period)]

    def body(carry, layer_p):
        x, aux = carry
        kvs, xkvs = [], []
        for j in range(period):
            pj = tmap(lambda a: a[j], layer_p) if period > 1 else layer_p
            x, kv, xkv, a = _block(cfg, plan, pj, x, positions, windows[j],
                                   want_kv, memory, causal)
            aux = aux + a
            if want_kv and windows[j] > 0:
                kv = tmap(lambda t: _ring_slice(t, windows[j]), kv)
            kvs.append(kv)
            xkvs.append(xkv)
        ys = (tuple(kvs), tuple(xkvs)) if want_kv else None
        return (x, aux), ys

    body = _remat(body, cfg.remat)
    (x, aux), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), lp)
    kvs, xkvs = ys if want_kv else (None, None)
    return x, kvs, xkvs, aux


def _stack_decode(cfg: ArchConfig, plan: MeshPlan, params: dict, x: jax.Array,
                  cache_layers: tuple, pos: jax.Array,
                  cross_kvs: Optional[tuple] = None):
    period = _period(cfg)
    lp = _grouped(cfg, params["layers"])
    windows = [_window_for(cfg, j) for j in range(period)]

    def body(x, inp):
        layer_p, caches, xkvs = inp
        new = []
        for j in range(period):
            pj = tmap(lambda a: a[j], layer_p) if period > 1 else layer_p
            xkv = None if xkvs is None else xkvs[j]
            x, nc = _block_decode(cfg, plan, pj, x, caches[j], pos, windows[j],
                                  xkv)
            new.append(nc)
        return x, tuple(new)

    xs = (lp, cache_layers, cross_kvs)
    x, new_cache = jax.lax.scan(body, x, xs)
    return x, new_cache


# ------------------------------------------------------------------------- ssm stacks
def _ssm_fwd(cfg: ArchConfig, plan: MeshPlan, params: dict, x: jax.Array,
             want_state: bool = False):
    def body(x, inp):
        lp = inp
        h = LY.rmsnorm(x, lp["ln1"], cfg.norm_eps, plan)
        if want_state:
            y, st = SSM.ssm_block(cfg, lp["ssm"], h, plan, return_state=True)
            return x + y, st
        return x + SSM.ssm_block(cfg, lp["ssm"], h, plan), None

    body = _remat(body, cfg.remat)
    x, states = jax.lax.scan(body, x, params["layers"])
    return x, states


def _ssm_decode(cfg: ArchConfig, plan: MeshPlan, params: dict, x: jax.Array,
                states: dict):
    def body(x, inp):
        lp, st = inp
        h = LY.rmsnorm(x, lp["ln1"], cfg.norm_eps, plan)
        y, new = SSM.ssm_block(cfg, lp["ssm"], h, plan, state=st,
                               return_state=True)
        return x + y, new

    x, new_states = jax.lax.scan(body, x, (params["layers"], states))
    return x, new_states


# ----------------------------------------------------------------------- hybrid stack
def _hybrid_split(cfg: ArchConfig, params: dict):
    k = cfg.shared_block_every
    G = cfg.num_layers // k
    main = tmap(lambda a: a[: G * k].reshape((G, k) + a.shape[1:]),
                params["layers"])
    tail = tmap(lambda a: a[G * k :], params["layers"])
    return main, tail, G, cfg.num_layers - G * k


def _shared_block_fwd(cfg, plan, shared, x, positions, want_kv):
    h = LY.rmsnorm(x, shared["ln1"], cfg.norm_eps, plan)
    a, k, v = _self_attn(cfg, plan, shared["attn"], h, positions, 0)
    x = x + a
    h = LY.rmsnorm(x, shared["ln2"], cfg.norm_eps, plan)
    x = x + LY.swiglu(shared["mlp"], h, plan)
    return x, ({"k": k, "v": v} if want_kv else None)


def _hybrid_fwd(cfg: ArchConfig, plan: MeshPlan, params: dict, x: jax.Array,
                positions: jax.Array, want_state: bool = False):
    main, tail, G, n_tail = _hybrid_split(cfg, params)
    shared = params["shared_block"]
    k = cfg.shared_block_every

    def group_body(x, lp):
        states, kvs = [], None
        for j in range(k):
            pj = tmap(lambda a: a[j], lp)
            h = LY.rmsnorm(x, pj["ln1"], cfg.norm_eps, plan)
            if want_state:
                y, st = SSM.ssm_block(cfg, pj["ssm"], h, plan, return_state=True)
                states.append(st)
            else:
                y = SSM.ssm_block(cfg, pj["ssm"], h, plan)
            x = x + y
        x, kv = _shared_block_fwd(cfg, plan, shared, x, positions, want_state)
        ys = ((tmap(lambda *s: jnp.stack(s), *states) if states else None), kv)
        return x, ys if want_state else None

    gb = _remat(group_body, cfg.remat)
    x, ys = jax.lax.scan(gb, x, main)
    main_states, shared_kv = ys if want_state else (None, None)

    def tail_body(x, lp):
        h = LY.rmsnorm(x, lp["ln1"], cfg.norm_eps, plan)
        if want_state:
            y, st = SSM.ssm_block(cfg, lp["ssm"], h, plan, return_state=True)
            return x + y, st
        return x + SSM.ssm_block(cfg, lp["ssm"], h, plan), None

    tb = _remat(tail_body, cfg.remat)
    x, tail_states = jax.lax.scan(tb, x, tail)
    return x, main_states, shared_kv, tail_states


def _hybrid_decode(cfg: ArchConfig, plan: MeshPlan, params: dict, x: jax.Array,
                   cache: dict, pos: jax.Array):
    main, tail, G, n_tail = _hybrid_split(cfg, params)
    shared = params["shared_block"]
    k = cfg.shared_block_every

    def group_body(x, inp):
        lp, sts, skv = inp
        new_states = []
        for j in range(k):
            pj = tmap(lambda a: a[j], lp)
            st = tmap(lambda a: a[j], sts)
            h = LY.rmsnorm(x, pj["ln1"], cfg.norm_eps, plan)
            y, new = SSM.ssm_block(cfg, pj["ssm"], h, plan, state=st,
                                   return_state=True)
            new_states.append(new)
            x = x + y
        x, new_skv = _shared_decode(cfg, plan, shared, x, skv, pos)
        return x, (tmap(lambda *s: jnp.stack(s), *new_states), new_skv)

    x, (new_main, new_skv) = jax.lax.scan(
        group_body, x, (main, cache["main"], cache["shared"]))

    def tail_body(x, inp):
        lp, st = inp
        h = LY.rmsnorm(x, lp["ln1"], cfg.norm_eps, plan)
        y, new = SSM.ssm_block(cfg, lp["ssm"], h, plan, state=st,
                               return_state=True)
        return x + y, new

    x, new_tail = jax.lax.scan(tail_body, x, (tail, cache["tail"]))
    return x, {"main": new_main, "shared": new_skv, "tail": new_tail}


def _shared_decode(cfg, plan, shared, x, skv, pos):
    h = LY.rmsnorm(x, shared["ln1"], cfg.norm_eps, plan)
    positions = pos[:, None]
    q, k_new, v_new = LY.qkv_project(shared["attn"], h, plan,
                                     positions=positions, theta=cfg.rope_theta,
                                     eps=cfg.norm_eps)
    k_c = LY._cache_update(skv["k"], k_new, pos)
    v_c = LY._cache_update(skv["v"], v_new, pos)
    k_c = constrain(k_c, plan, ("batch", "cache_seq", "kv_heads", None))
    v_c = constrain(v_c, plan, ("batch", "cache_seq", "kv_heads", None))
    o = ops.attend_cache(q, k_c, v_c, pos[:, None, None, None],
                         packed=cfg.packed_decode)
    x = x + LY.attn_out(shared["attn"], o, plan)
    h = LY.rmsnorm(x, shared["ln2"], cfg.norm_eps, plan)
    x = x + LY.swiglu(shared["mlp"], h, plan)
    return x, {"k": k_c, "v": v_c}


# -------------------------------------------------------------------------- vlm stack
def _vlm_cross_layer(cfg, plan, p, x, patches, want_kv):
    h = LY.rmsnorm(x, p["ln1"], cfg.norm_eps, plan)
    a, k, v = _cross_attn(cfg, plan, p["xattn"], h, patches)
    x = x + jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype) * a
    h = LY.rmsnorm(x, p["ln2"], cfg.norm_eps, plan)
    x = x + LY.swiglu(p["mlp"], h, plan)
    return x, ({"k": k, "v": v} if want_kv else None)


def _vlm_fwd(cfg: ArchConfig, plan: MeshPlan, params: dict, x: jax.Array,
             positions: jax.Array, patches: jax.Array, want_kv: bool = False):
    group = cfg.cross_attn_every - 1

    def body(x, inp):
        slp, clp = inp
        kvs = []
        for j in range(group):
            pj = tmap(lambda a: a[j], slp)
            x, kv, _, _ = _block(cfg, plan, pj, x, positions, 0, want_kv)
            kvs.append(kv)
        x, xkv = _vlm_cross_layer(cfg, plan, clp, x, patches, want_kv)
        return x, ((tuple(kvs), xkv) if want_kv else None)

    body = _remat(body, cfg.remat)
    x, ys = jax.lax.scan(body, x, (params["self_layers"], params["cross_layers"]))
    if not want_kv:
        return x, None, None
    kvs, xkvs = ys
    return x, kvs, xkvs


def _vlm_decode(cfg: ArchConfig, plan: MeshPlan, params: dict, x: jax.Array,
                cache: dict, pos: jax.Array):
    group = cfg.cross_attn_every - 1

    def body(x, inp):
        slp, clp, caches, xkv = inp
        new = []
        for j in range(group):
            pj = tmap(lambda a: a[j], slp)
            cj = tmap(lambda a: a[j], caches)
            x, nc = _block_decode(cfg, plan, pj, x, cj, pos, 0)
            new.append(nc)
        h = LY.rmsnorm(x, clp["ln1"], cfg.norm_eps, plan)
        a = _cross_attn_cached(cfg, plan, clp["xattn"], h, xkv["k"], xkv["v"])
        x = x + jnp.tanh(clp["gate"].astype(jnp.float32)).astype(x.dtype) * a
        h = LY.rmsnorm(x, clp["ln2"], cfg.norm_eps, plan)
        x = x + LY.swiglu(clp["mlp"], h, plan)
        return x, tmap(lambda *t: jnp.stack(t), *new)

    xs = (params["self_layers"], params["cross_layers"], cache["self"],
          cache["cross"])
    x, new_self = jax.lax.scan(body, x, xs)
    return x, new_self


# =============================================================================== Model
class Model:
    """Family-dispatched model bound to an ArchConfig and a MeshPlan."""

    def __init__(self, cfg: ArchConfig, plan: MeshPlan):
        self.cfg = cfg
        self.plan = plan

    # ------------------------------------------------------------------ params views
    def init_params(self, key) -> dict:
        return init_params(self.cfg, key)

    def abstract_params(self) -> dict:
        return abstract_params(self.cfg)

    def param_specs(self) -> dict:
        return partition_specs(self.cfg, self.plan)

    # --------------------------------------------------------------------- embedding
    def _embed(self, params: dict, tokens: jax.Array) -> jax.Array:
        x = params["embed"][tokens]
        return constrain(x, self.plan, ("batch", "seq", None))

    def _unembed(self, params: dict, x: jax.Array) -> jax.Array:
        x = LY.rmsnorm(x, params["final_norm"], self.cfg.norm_eps, self.plan)
        table = (params["embed"].T if self.cfg.tie_embeddings
                 else params["unembed"])
        logits = jnp.einsum("bsd,dv->bsv", x, table)
        return constrain(logits, self.plan, ("batch", "seq", "vocab"))

    def _encode(self, params: dict, frames: jax.Array) -> jax.Array:
        """Whisper-style encoder over stub frame embeddings [B, M, D]."""
        cfg, plan = self.cfg, self.plan
        M = frames.shape[1]
        positions = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32)[None],
                                     frames.shape[:2])
        x = frames

        def body(x, lp):
            x, _, _, _ = _block(cfg, plan, lp, x, positions, 0, False,
                                causal=False)
            return x, None

        body = _remat(body, cfg.remat)
        x, _ = jax.lax.scan(body, x, params["enc_layers"])
        return LY.rmsnorm(x, params["enc_norm"], cfg.norm_eps, plan)

    # ----------------------------------------------------------------------- forward
    def forward(self, params: dict, batch: Dict[str, jax.Array],
                return_hidden: bool = False):
        """Full-sequence forward. Returns (logits [B,S,V], aux_loss) — or the
        final-normed hidden states when ``return_hidden`` (chunked-CE path)."""
        cfg, plan = self.cfg, self.plan
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self._embed(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        aux = jnp.zeros((), jnp.float32)

        if cfg.family in ("dense", "moe"):
            x, _, _, aux = _stack_fwd(cfg, plan, params, x, positions)
        elif cfg.family == "ssm":
            x, _ = _ssm_fwd(cfg, plan, params, x)
        elif cfg.family == "hybrid":
            x, _, _, _ = _hybrid_fwd(cfg, plan, params, x, positions)
        elif cfg.family == "encdec":
            memory = self._encode(params, batch["frames"])
            x, _, _, aux = _stack_fwd(cfg, plan, params, x, positions,
                                      memory=memory)
        elif cfg.family == "vlm":
            x, _, _ = _vlm_fwd(cfg, plan, params, x, positions,
                               batch["patches"])
        else:
            raise ValueError(cfg.family)
        if return_hidden:
            return LY.rmsnorm(x, params["final_norm"], cfg.norm_eps, plan), aux
        return self._unembed(params, x), aux

    def loss_fn(self, params: dict, batch: Dict[str, jax.Array]):
        """Masked CE (+ MoE aux). Returns (loss, metrics).

        CE uses a gather (take_along_axis), NOT a one-hot einsum — the one-hot
        materializes a [B,S,V] f32 tensor whose HBM traffic rivals a layer's
        (measured in the roofline pass; see EXPERIMENTS.md §Perf iteration 1).

        With cfg.loss_chunk > 0 the full [B,S,V] logits are NEVER materialized:
        the sequence is processed in chunks under jax.checkpoint (per-chunk
        logits recomputed in the backward) — the memory lever that makes
        dp_only viable for small models (§Perf cell 3).
        """
        mask = batch["loss_mask"].astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        if self.cfg.loss_chunk:
            hidden, aux = self.forward(params, batch, return_hidden=True)
            ce = self._chunked_ce(params, hidden, batch["targets"],
                                  mask) / denom
        else:
            logits, aux = self.forward(params, batch)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(logp, batch["targets"][..., None],
                                     axis=-1)[..., 0]            # [B, S]
            ce = -(ll * mask).sum() / denom
        loss = ce + 0.01 * aux
        metrics = {"loss": ce, "aux_loss": aux, "tokens": mask.sum()}
        return loss, metrics

    def _chunked_ce(self, params: dict, hidden: jax.Array, targets: jax.Array,
                    mask: jax.Array) -> jax.Array:
        """Sum of masked -log p over [B,S] in sequence chunks of cfg.loss_chunk."""
        cfg, plan = self.cfg, self.plan
        table = (params["embed"].T if cfg.tie_embeddings
                 else params["unembed"])
        B, S, D = hidden.shape
        c = min(cfg.loss_chunk, S)
        n = S // c
        assert n * c == S, f"loss_chunk {c} must divide seq {S}"

        def body(args):
            xc, tc, mc = args                                   # [B,c,D] ...
            logits = jnp.einsum("bsd,dv->bsv", xc, table)
            logits = constrain(logits.astype(jnp.float32),
                               plan, ("batch", "seq", "vocab"))
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, tc[..., None],
                                     axis=-1)[..., 0] - lse
            return -(ll * mc).sum()

        body = jax.checkpoint(body)
        xs = (hidden.reshape(B, n, c, D).swapaxes(0, 1),
              targets.reshape(B, n, c).swapaxes(0, 1),
              mask.reshape(B, n, c).swapaxes(0, 1))
        return jnp.sum(jax.lax.map(body, xs))

    # ----------------------------------------------------------------------- prefill
    def prefill(self, params: dict, batch: Dict[str, jax.Array],
                max_len: Optional[int] = None):
        """Build the decode cache from a full prompt; returns (last_logits, cache)."""
        cfg, plan = self.cfg, self.plan
        tokens = batch["tokens"]
        B, S = tokens.shape
        max_len = max_len or S
        x = self._embed(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        pos = jnp.full((B,), S, jnp.int32)

        def pad_seq(t, target):
            if t.shape[2] == target:
                return t
            pad = [(0, 0)] * t.ndim
            pad[2] = (0, target - t.shape[2])
            return jnp.pad(t, pad)

        if cfg.family in ("dense", "moe"):
            x, kvs, _, _ = _stack_fwd(cfg, plan, params, x, positions,
                                      want_kv=True)
            layers = tuple(
                tmap(lambda t: t if _window_for(cfg, j) else pad_seq(t, max_len),
                     kvs[j])
                for j in range(_period(cfg)))
            cache = {"pos": pos, "layers": layers}
        elif cfg.family == "ssm":
            x, states = _ssm_fwd(cfg, plan, params, x, want_state=True)
            cache = {"pos": pos, "layers": states}
        elif cfg.family == "hybrid":
            x, main, skv, tail = _hybrid_fwd(cfg, plan, params, x, positions,
                                             want_state=True)
            cache = {"pos": pos, "main": main,
                     "shared": tmap(lambda t: pad_seq(t, max_len), skv),
                     "tail": tail}
        elif cfg.family == "encdec":
            memory = self._encode(params, batch["frames"])
            x, kvs, xkvs, _ = _stack_fwd(cfg, plan, params, x, positions,
                                         memory=memory, want_kv=True)
            cache = {"pos": pos,
                     "self": tmap(lambda t: pad_seq(t, max_len), kvs[0]),
                     "cross": xkvs[0]}
        elif cfg.family == "vlm":
            x, kvs, xkvs = _vlm_fwd(cfg, plan, params, x, positions,
                                    batch["patches"], want_kv=True)
            self_c = tmap(lambda *t: jnp.stack(t, axis=1),
                          *[tmap(lambda u: pad_seq(u, max_len), kv)
                            for kv in kvs])
            cache = {"pos": pos, "self": self_c, "cross": xkvs}
        else:
            raise ValueError(cfg.family)
        last_logits = self._unembed(params, x[:, -1:])[:, 0]
        return last_logits, cache

    # ------------------------------------------------------------------- decode step
    def decode_step(self, params: dict, tokens: jax.Array, cache: dict):
        """tokens [B, 1] -> (logits [B, V], new_cache)."""
        cfg, plan = self.cfg, self.plan
        pos = cache["pos"]
        x = self._embed(params, tokens)

        if cfg.family in ("dense", "moe"):
            x, new_layers = _stack_decode(cfg, plan, params, x,
                                          cache["layers"], pos)
            new_cache = {"pos": pos + 1, "layers": new_layers}
        elif cfg.family == "ssm":
            x, new_states = _ssm_decode(cfg, plan, params, x, cache["layers"])
            new_cache = {"pos": pos + 1, "layers": new_states}
        elif cfg.family == "hybrid":
            x, new = _hybrid_decode(cfg, plan, params, x, cache, pos)
            new_cache = dict(new, pos=pos + 1)
        elif cfg.family == "encdec":
            x, new_self = _stack_decode(cfg, plan, params, x,
                                        (cache["self"],), pos,
                                        cross_kvs=(cache["cross"],))
            new_cache = {"pos": pos + 1, "self": new_self[0],
                         "cross": cache["cross"]}
        elif cfg.family == "vlm":
            x, new_self = _vlm_decode(cfg, plan, params, x, cache, pos)
            new_cache = {"pos": pos + 1, "self": new_self,
                         "cross": cache["cross"]}
        else:
            raise ValueError(cfg.family)
        logits = self._unembed(params, x)[:, 0]
        return logits, new_cache

    # ------------------------------------------------------------------- cache views
    def cache_defs(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        K, hd = cfg.num_kv_heads, cfg.head_dim
        kv_log = (None, "batch", "cache_seq", "kv_heads", None)

        def kv(G, S):
            return {"k": TensorDef((G, batch, S, K, hd), dt, kv_log),
                    "v": TensorDef((G, batch, S, K, hd), dt, kv_log)}

        def ssm_state(*lead):
            DI, N = cfg.d_inner, cfg.ssm_state
            W = cfg.ssm_conv_width
            lead_log = (None,) * len(lead)
            return {
                "conv": TensorDef(lead + (batch, W - 1, DI + 2 * N), dt,
                                  lead_log + ("batch", None, "ffn")),
                "ssd": TensorDef(lead + (batch, cfg.ssm_heads, cfg.ssm_state,
                                         cfg.ssm_head_dim), jnp.float32,
                                 lead_log + ("batch", "ssm_heads", None, None)),
            }

        pos = TensorDef((batch,), jnp.int32, ("batch",))
        if cfg.family in ("dense", "moe"):
            period = _period(cfg)
            G = cfg.num_layers // period
            layers = tuple(
                kv(G, _window_for(cfg, j) or max_len) for j in range(period))
            return {"pos": pos, "layers": layers}
        if cfg.family == "ssm":
            return {"pos": pos, "layers": ssm_state(cfg.num_layers)}
        if cfg.family == "hybrid":
            k = cfg.shared_block_every
            G = cfg.num_layers // k
            return {"pos": pos, "main": ssm_state(G, k),
                    "shared": kv(G, max_len),
                    "tail": ssm_state(cfg.num_layers - G * k)}
        if cfg.family == "encdec":
            L = cfg.num_layers
            return {"pos": pos,
                    "self": {k_: v_ for k_, v_ in kv(L, max_len).items()},
                    "cross": kv(L, cfg.encoder_frames)}
        if cfg.family == "vlm":
            nc = cfg.num_layers // cfg.cross_attn_every
            grp = cfg.cross_attn_every - 1
            self_kv = {
                "k": TensorDef((nc, grp, batch, max_len, K, hd), dt,
                               (None,) + kv_log),
                "v": TensorDef((nc, grp, batch, max_len, K, hd), dt,
                               (None,) + kv_log)}
            return {"pos": pos, "self": self_kv,
                    "cross": kv(nc, cfg.num_patches)}
        raise ValueError(cfg.family)

    def abstract_cache(self, batch: int, max_len: int) -> dict:
        return tmap(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype),
                    self.cache_defs(batch, max_len), is_leaf=_is_tdef)

    def init_cache(self, batch: int, max_len: int) -> dict:
        return tmap(lambda d: jnp.zeros(d.shape, d.dtype),
                    self.cache_defs(batch, max_len), is_leaf=_is_tdef)

    def cache_specs(self, batch: int, max_len: int) -> dict:
        return tmap(lambda d: self.plan.spec(d.logical, d.shape),
                    self.cache_defs(batch, max_len), is_leaf=_is_tdef)
