"""Server: slot-based continuous batching over the decode cache.

Requests (prompt token arrays) queue up; each free slot prefills one request
(B=1) and splices its cache into the batched decode cache at the slot's batch
index; every tick runs ONE batched decode step for all active slots (inactive
slots compute masked garbage — the standard continuous-batching trade). Slots
free as requests hit EOS/max_new, so long and short generations coexist without
head-of-line blocking.

The batch axis of every cache leaf is located *generically* by diffing
``cache_defs(batch=1)`` against ``cache_defs(batch=2)`` — the same Server drives
dense KV caches, MoE, ring-buffer windows, SSM states and hybrid caches without
family-specific code.

Each ``step`` is a ``repro.serve.step`` span holding ``admit`` (per request:
``prefill`` with the prompt's ``length``, ``splice``, and ``sync`` for the
first token), ``decode`` (the dispatch) and ``sync`` (sampling and the
per-slot tokens brought to the host) (``repro.runtime.telemetry``).
``spans.host_transfers`` counts the arrays brought to the host;
``spans.compiles`` the programs loaded by span and step, so a new prompt
length shows as a compile under ``prefill``. Each
``Request`` carries ``time.perf_counter`` stamps: submitted, admitted (its
prefill's start) and first token (its first sync's end).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs import base as configs
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import named
from repro.models.model import Model
from repro.parallel.sharding import MeshPlan
from repro.runtime.telemetry import LoopSpans

tmap = jax.tree_util.tree_map


@dataclasses.dataclass
class Request:
    req_id: str
    prompt: List[int]
    max_new: int = 16
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None


@dataclasses.dataclass
class ServeJobConfig:
    arch: str = "qwen3-0.6b"
    reduced: bool = True
    slots: int = 4
    max_len: int = 256
    eos_id: Optional[int] = None
    greedy: bool = True
    seed: int = 0

    @classmethod
    def from_job(cls, job: dict) -> "ServeJobConfig":
        payload = dict(job.get("payload", {}))
        payload.setdefault("arch", job.get("arch") or "qwen3-0.6b")
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in payload.items() if k in known})


class Server:
    def __init__(self, cfg: ServeJobConfig, params: Optional[dict] = None,
                 mesh=None):
        self.cfg = cfg
        arch_cfg = configs.get(cfg.arch)
        if cfg.reduced:
            arch_cfg = arch_cfg.reduced()
        arch_cfg = dataclasses.replace(arch_cfg, remat="none")
        self.arch_cfg = arch_cfg
        mesh = mesh or make_test_mesh()
        self.model = Model(arch_cfg, MeshPlan(mesh=mesh, fsdp=False))
        # params and cache are built straight into their shardings on the mesh
        self._init_params_fn = jax.jit(
            self.model.init_params,
            out_shardings=named(mesh, self.model.param_specs()))
        self.params = params if params is not None else \
            self._init_params_fn(jax.random.PRNGKey(cfg.seed))

        B, L = cfg.slots, cfg.max_len
        self._init_cache_fn = jax.jit(
            functools.partial(self.model.init_cache, B, L),
            out_shardings=named(mesh, self.model.cache_specs(B, L)))
        self.cache = self._init_cache_fn()
        self._batch_axis = self._locate_batch_axes(L)
        self.slots: List[Optional[Request]] = [None] * B
        self.queue: Deque[Request] = deque()
        self._ids = itertools.count(1)
        self._decode = jax.jit(self.model.decode_step)
        self._prefill_cache: Dict[int, object] = {}
        self._rng = jax.random.PRNGKey(cfg.seed + 17)
        self.steps = 0
        self.spans = LoopSpans("serve")
        self._init_params = self.params
        self._init_seed = cfg.seed

    def rebind(self, cfg: ServeJobConfig) -> None:
        """Re-arm a warm server for a new task of the SAME compiled family
        (the step-cache hit path): fresh request/slot/cache state, same model
        and jitted decode/prefill functions. The caller guarantees the cache
        key (arch, reduced, slots, max_len) matches; eos/greedy/seed are
        host-side and may differ."""
        if cfg.seed == self._init_seed:
            self.params = self._init_params
        else:
            self.params = self._init_params_fn(jax.random.PRNGKey(cfg.seed))
            self._init_params = self.params
            self._init_seed = cfg.seed
        self.cfg = cfg
        self.cache = self._init_cache_fn()
        self.slots = [None] * cfg.slots
        self.queue = deque()
        self.requests: Dict[str, Request] = {}
        self._ids = itertools.count(1)
        self._rng = jax.random.PRNGKey(cfg.seed + 17)
        self.steps = 0
        self.spans = LoopSpans("serve")

    # ------------------------------------------------------------- batch-axis magic
    def _locate_batch_axes(self, L: int):
        d1 = self.model.cache_defs(1, L)
        d2 = self.model.cache_defs(2, L)

        def axis(a, b):
            diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                     if x != y]
            assert len(diffs) == 1, (a.shape, b.shape)
            return diffs[0]

        is_def = lambda x: hasattr(x, "logical")
        return tmap(axis, d1, d2, is_leaf=is_def)

    def _splice(self, slot: int, one_cache: dict) -> None:
        def put(full, one, ax):
            return jax.lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), slot, axis=ax)
        self.cache = tmap(put, self.cache, one_cache, self._batch_axis)

    # ----------------------------------------------------------------- request path
    def submit(self, prompt: List[int], max_new: int = 16) -> str:
        rid = f"req-{next(self._ids):04d}"
        req = Request(rid, list(prompt), max_new,
                      t_submit=time.perf_counter())
        self.queue.append(req)
        if not hasattr(self, "requests"):
            self.requests: Dict[str, Request] = {}
        self.requests[rid] = req
        return rid

    def _prefill_fn(self, length: int):
        if length not in self._prefill_cache:
            fn = lambda params, batch: self.model.prefill(
                params, batch, max_len=self.cfg.max_len)
            self._prefill_cache[length] = jax.jit(fn)
        return self._prefill_cache[length]

    def _aux_inputs(self, B: int) -> dict:
        c, out = self.arch_cfg, {}
        if c.family == "encdec":
            out["frames"] = jnp.zeros((B, c.encoder_frames, c.d_model),
                                      jnp.bfloat16)
        if c.family == "vlm":
            out["patches"] = jnp.zeros((B, c.num_patches, c.d_model),
                                       jnp.bfloat16)
        return out

    def _sample(self, logits: jax.Array) -> jax.Array:
        if self.cfg.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._rng, key = jax.random.split(self._rng)
        return jax.random.categorical(key, logits).astype(jnp.int32)

    def _admit(self) -> None:
        spans = self.spans
        for slot in range(self.cfg.slots):
            if self.slots[slot] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            with spans.span("prefill", length=len(req.prompt)) as prefill:
                toks = jnp.asarray([req.prompt], jnp.int32)
                batch = {"tokens": toks, **self._aux_inputs(1)}
                logits, one_cache = self._prefill_fn(len(req.prompt))(
                    self.params, batch)
            with spans.span("splice"):
                self._splice(slot, one_cache)
            with spans.span("sync") as sync:
                first = int(self._sample(logits)[0])
            req.t_admit, req.t_first = prefill.t0, sync.t1
            spans.host_transfers += 1
            req.generated.append(first)
            self.slots[slot] = req
            self._maybe_finish(slot)

    def _maybe_finish(self, slot: int) -> None:
        req = self.slots[slot]
        if req is None:
            return
        hit_eos = (self.cfg.eos_id is not None and req.generated
                   and req.generated[-1] == self.cfg.eos_id)
        total = len(req.prompt) + len(req.generated)
        if hit_eos or len(req.generated) >= req.max_new \
                or total >= self.cfg.max_len - 1:
            req.done = True
            self.slots[slot] = None

    # -------------------------------------------------------------------- main loop
    def step(self) -> int:
        """Admit + one batched decode step. Returns number of active slots."""
        spans = self.spans
        with spans.step(self.steps):
            with spans.span("admit"):
                self._admit()
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if not active:
                return 0
            with spans.span("decode"):
                last = [r.generated[-1] if r else 0 for r in self.slots]
                tokens = jnp.asarray(last, jnp.int32)[:, None]
                logits, self.cache = self._decode(self.params, tokens,
                                                  self.cache)
            with spans.span("sync"):
                nxt = self._sample(logits)
                for i in active:
                    self.slots[i].generated.append(int(nxt[i]))
                    self._maybe_finish(i)
            spans.host_transfers += len(active)
            self.steps += 1
        return len(active)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        for _ in range(max_steps):
            n = self.step()
            if n == 0 and not self.queue:
                break
        return [r for r in getattr(self, "requests", {}).values() if r.done]

    def pending(self) -> int:
        return len(self.queue) + sum(r is not None for r in self.slots)
