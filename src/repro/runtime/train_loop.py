"""Trainer: the real (JAX-executing) training loop behind a local control plane.

Two synchronization modes, selected per job:
  * "sync"      — per-step synchronous data parallelism (the baseline the paper's
                  thin-boundary argument is measured against);
  * "local_sgd" — the Titchener mode: H pod-local AdamW steps per round, one
                  int8+error-feedback compressed delta exchange across the pod
                  boundary (repro.optim.local_sgd) — the paper's "occasional
                  cross-boundary traffic" regime.

Deterministic restart: checkpoint = (train state, data step, RNG seed); the data
pipeline is a pure function of step, so kill/restore resumes bit-exact (validated
in tests/test_fault_tolerance.py).

Each ``step_once`` is a ``repro.train.step`` span holding, in order,
``batch`` (the feed), ``dispatch`` (the step program, which only enqueues),
``sync`` (the metrics brought to the host: the loop's one wait on the
device), ``log`` (the metrics row) and, when one is due, ``checkpoint``
(``repro.runtime.telemetry``). ``spans.host_transfers`` counts the arrays
brought to the host; ``spans.compiles`` the programs loaded by span and
step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs import base as configs
from repro.data.pipeline import SyntheticTokens
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import (init_train_state, make_train_step, named,
                                train_state_specs)
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig
from repro.optim.local_sgd import (LocalSGDConfig, init_local_sgd_state,
                                   make_round_fn, pod_free_plan)
from repro.parallel.sharding import MeshPlan
from repro.runtime.telemetry import LoopSpans, MetricsLog, StepTimer

tmap = jax.tree_util.tree_map


@dataclasses.dataclass
class TrainJobConfig:
    arch: str = "qwen3-0.6b"
    steps: int = 50
    seq_len: int = 64
    global_batch: int = 8
    reduced: bool = True             # reduced() config for CPU execution
    mode: str = "sync"               # sync | local_sgd
    n_pods: int = 2                  # local_sgd: pods emulated via the vmap dim
    microbatches: int = 1
    seed: int = 0
    data_task: str = "ramp"
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 25
    opt: AdamWConfig = dataclasses.field(default_factory=lambda: AdamWConfig(
        peak_lr=1e-2, warmup_steps=20, total_steps=2000, weight_decay=0.0))
    local_sgd: LocalSGDConfig = dataclasses.field(default_factory=LocalSGDConfig)

    @classmethod
    def from_job(cls, job: dict) -> "TrainJobConfig":
        payload = dict(job.get("payload", {}))
        payload.setdefault("arch", job.get("arch") or "qwen3-0.6b")
        payload.setdefault("steps", job.get("steps", 50))
        known = {f.name for f in dataclasses.fields(cls)}
        for key in ("opt", "local_sgd"):
            if key in payload and isinstance(payload[key], dict):
                klass = AdamWConfig if key == "opt" else LocalSGDConfig
                payload[key] = klass(**payload[key])
        return cls(**{k: v for k, v in payload.items() if k in known})


class Trainer:
    def __init__(self, cfg: TrainJobConfig, mesh=None,
                 on_checkpoint: Optional[Callable[[int, str], None]] = None):
        self.cfg = cfg
        arch_cfg = configs.get(cfg.arch)
        if cfg.reduced:
            arch_cfg = arch_cfg.reduced()
        arch_cfg = dataclasses.replace(arch_cfg, remat="none")
        self.arch_cfg = arch_cfg
        mesh = mesh or make_test_mesh()
        self.plan = MeshPlan(mesh=mesh, fsdp=False)
        self.step = 0

        if cfg.mode == "local_sgd":
            # pods are a leading vmapped dim; the model must not shard on "pod"
            self.model = Model(arch_cfg, pod_free_plan(self.plan))
            n_pods = cfg.n_pods

            def init(key):
                return init_local_sgd_state(self.model.init_params(key), n_pods)

            self._state_shardings = None
            spmd = "pod" if "pod" in mesh.shape else None
            self.round_fn = jax.jit(make_round_fn(
                self.model.loss_fn, cfg.opt, cfg.local_sgd, spmd_axis=spmd),
                donate_argnums=(0,))
        else:
            self.model = Model(arch_cfg, self.plan)
            init = functools.partial(init_train_state, self.model)
            self._state_shardings = named(
                mesh, train_state_specs(arch_cfg, self.plan))
            self.step_fn = jax.jit(make_train_step(self.model, cfg.opt,
                                                   cfg.microbatches),
                                   donate_argnums=(0,))

        # every step donates (frees) the state it is given, so the initial
        # state is never kept: ``rebind`` rebuilds it from the seed, which is
        # deterministic, and it is built straight into its shardings
        self._init_fn = jax.jit(init, out_shardings=self._state_shardings)
        self.state = self._init_fn(jax.random.PRNGKey(cfg.seed))
        self.data = SyntheticTokens(
            vocab_size=arch_cfg.vocab_size, seq_len=cfg.seq_len,
            global_batch=cfg.global_batch, seed=cfg.seed, task=cfg.data_task)
        self.metrics = MetricsLog()
        self.timer = StepTimer(tokens_per_step=cfg.global_batch * cfg.seq_len)
        self.spans = LoopSpans("train", self.timer)
        self.ckpt = (CheckpointManager(cfg.checkpoint_dir)
                     if cfg.checkpoint_dir else None)
        if self.ckpt and on_checkpoint:
            self.ckpt.on_commit(on_checkpoint)

    def rebind(self, cfg: TrainJobConfig,
               on_checkpoint: Optional[Callable[[int, str], None]] = None
               ) -> None:
        """Re-arm a warm trainer for a new task of the SAME compiled family
        (the step-cache hit path): reset step/state/data/metrics, point the
        checkpoint manager at the task's directory, and keep the model and
        jitted step function — the expensive part — untouched. The caller
        (``repro.runtime.step_cache``) guarantees the cache key (arch, shape,
        mode, ...) matches; only per-run knobs may differ here."""
        if self.ckpt:
            self.ckpt.wait()             # bound the previous task's async save
        self.state = None                # free it before building the next
        self.state = self._init_fn(jax.random.PRNGKey(cfg.seed))
        self.cfg = cfg
        self.step = 0
        self.data = SyntheticTokens(
            vocab_size=self.arch_cfg.vocab_size, seq_len=cfg.seq_len,
            global_batch=cfg.global_batch, seed=cfg.seed, task=cfg.data_task)
        self.metrics = MetricsLog()
        self.timer = StepTimer(tokens_per_step=cfg.global_batch * cfg.seq_len)
        self.spans = LoopSpans("train", self.timer)
        self.ckpt = (CheckpointManager(cfg.checkpoint_dir)
                     if cfg.checkpoint_dir else None)
        if self.ckpt and on_checkpoint:
            self.ckpt.on_commit(on_checkpoint)

    # ------------------------------------------------------------------ step logic
    def _sync_batch(self, step: int) -> Dict[str, jax.Array]:
        batch = self.data.global_batch_at(step)
        return self._with_aux_inputs(batch, self.cfg.global_batch)

    def _with_aux_inputs(self, batch: dict, B: int) -> dict:
        c = self.arch_cfg
        if c.family == "encdec":
            key = jax.random.fold_in(jax.random.PRNGKey(self.cfg.seed + 1), 0)
            batch["frames"] = jax.random.normal(
                key, (B, c.encoder_frames, c.d_model), jnp.bfloat16)
        if c.family == "vlm":
            key = jax.random.fold_in(jax.random.PRNGKey(self.cfg.seed + 2), 0)
            batch["patches"] = jax.random.normal(
                key, (B, c.num_patches, c.d_model), jnp.bfloat16)
        return batch

    def _round_batches(self, step: int) -> Dict[str, jax.Array]:
        """local_sgd: [H, n_pods, B/pods, ...] batch stack for one round."""
        H, P = self.cfg.local_sgd.inner_steps, self.cfg.n_pods
        Bp = self.cfg.global_batch // P
        rows = []
        for h in range(H):
            pods = [self._with_aux_inputs(
                self.data.batch_at(step + h, shard_id=p, batch=Bp), Bp)
                for p in range(P)]
            rows.append(tmap(lambda *x: jnp.stack(x), *pods))
        return tmap(lambda *x: jnp.stack(x), *rows)

    def step_once(self) -> Dict[str, float]:
        spans = self.spans
        with spans.step(self.step):
            if self.cfg.mode == "local_sgd":
                with spans.span("batch"):
                    batches = self._round_batches(self.step)
                with spans.span("dispatch"):
                    self.state, m = self.round_fn(self.state, batches)
                n = self.cfg.local_sgd.inner_steps
            else:
                with spans.span("batch"):
                    batch = self._sync_batch(self.step)
                with spans.span("dispatch"):
                    self.state, m = self.step_fn(self.state, batch)
                n = 1
            self.step += n
            with spans.span("sync"):
                m = {k: float(v) for k, v in m.items()}
            spans.host_transfers += len(m)
            with spans.span("log"):
                self.metrics.log(self.step, m)
            if (self.ckpt and self.step % self.cfg.checkpoint_every == 0):
                # non-blocking: the manager snapshots host leaves
                # synchronously, then writes on its thread while the next
                # steps run — periodic checkpointing leaves the hot loop
                # (save() itself serializes against a still-running
                # previous write)
                self.save_checkpoint(blocking=False)
        return m

    def run(self, steps: Optional[int] = None) -> Dict[str, float]:
        target = self.step + (steps if steps is not None else self.cfg.steps)
        last = {}
        while self.step < target:
            last = self.step_once()
        return last

    # ---------------------------------------------------------------- checkpointing
    def save_checkpoint(self, blocking: bool = True) -> Optional[dict]:
        """Snapshot the train state. ``blocking=False`` returns as soon as
        the host-side leaf snapshot is taken; the disk write overlaps the
        following steps and the next save (or ``restore``/``rebind``/an
        explicit blocking save) joins it."""
        if not self.ckpt:
            return None
        with self.spans.span("checkpoint"):
            self.ckpt.save(self.step, self.state,
                           extra={"data": self.data.state_dict(),
                                  "arch": self.cfg.arch,
                                  "mode": self.cfg.mode})
            if blocking:
                self.ckpt.wait()
        return {"step": self.step, "path": str(self.ckpt.directory)}

    def restore(self, manifest: Optional[dict] = None,
                strict: bool = False) -> int:
        """Restore from a manifest {step, path} (or latest in our own dir).

        Returns the restored step; 0 means "no checkpoint, fresh start" —
        the resume semantics a train task wants. ``strict=True`` raises
        instead (``FileNotFoundError``): an eval task told to restore MUST
        see a committed checkpoint, never silently score fresh params. All
        integrity checks (manifest-vs-directory staleness, missing leaves,
        torn writes) are ``CheckpointManager.restore``'s and always raise."""
        with self.spans.span("checkpoint"):
            if self.ckpt:
                self.ckpt.wait()         # our own async save is a valid source
            directory = (manifest or {}).get("path") or (
                self.cfg.checkpoint_dir if self.ckpt else None)
            if directory is None:
                if strict:
                    raise FileNotFoundError(
                        f"restore requested but no checkpoint directory in "
                        f"manifest or config: {manifest!r}")
                return 0
            mgr = CheckpointManager(directory)
            step = (manifest or {}).get("step") or mgr.latest_step()
            if step is None:
                if strict:
                    raise FileNotFoundError(
                        f"no committed checkpoint in {directory}")
                return 0
            like = jax.eval_shape(self._init_fn, jax.random.PRNGKey(0))
            # free the device copy before loading the saved one: two full
            # train states need not fit on the device (a failed load leaves
            # no state; ``rebind`` builds one)
            self.state = None
            self.state, step, extra = mgr.restore(
                like, step=step, shardings=self._state_shardings)
            self.data.load_state_dict(extra["data"])
            self.step = int(step)
            return self.step

    def release(self) -> None:
        """Free the device-resident train state between tasks: a finished
        task's state lives on in its checkpoint, and ``rebind`` builds the
        next task's state from its seed."""
        if self.ckpt:
            self.ckpt.wait()
        self.state = None

    # -------------------------------------------------------------------- inspection
    def loss(self) -> Optional[float]:
        row = self.metrics.latest()
        return row.get("loss") if row else None

    def params_for_eval(self) -> dict:
        if self.cfg.mode == "local_sgd":
            return tmap(lambda m: m.astype(jnp.dtype(self.arch_cfg.dtype)),
                        self.state["master"])
        return self.state["params"]
