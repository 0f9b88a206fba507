"""Keyed LRU caches of compiled workloads — the "warm worker" optimization.

Rebuilding a ``Trainer`` per pipeline task pays model construction plus jit
compilation of the train step (seconds) before the first real step runs
(milliseconds); a 12-stage same-family DAG re-pays it 12 times. A
:class:`TrainerCache` keys warm trainers by their *compiled family* — (arch,
reduced, mode, seq_len, global_batch, n_pods, microbatches, data_task, opt,
local_sgd) — everything the jitted step function's shapes and constants
depend on. A hit calls ``Trainer.rebind`` (reset step/state/data, keep the
model + compiled step); per-run knobs (steps, seed, checkpoint_dir/every)
are deliberately OUT of the key. :class:`ServerCache` is the serve-side
twin, keyed by (arch, reduced, slots, max_len).

``capacity=0`` disables caching (a fresh build per task — the cold baseline
``benchmarks/workloads.py`` measures against); eviction is LRU.

The ``run_*_task`` functions hold the actual task semantics shared by the
worker's cached handlers and the module-level cold fallbacks:

  * train — resume from the task's own ``checkpoint_dir`` (latest committed
    step; integrity-validated) and run only the REMAINING steps to the
    payload's target, so a task redelivered after a worker retire/crash
    continues instead of restarting: exactly-once step accounting rides the
    checkpoint, whatever the delivery count. Final checkpoint save blocks
    (the manifest it returns must be durable); the periodic in-loop saves
    overlap the next steps asynchronously.
  * eval — STRICT restore through ``CheckpointManager.restore``'s staleness/
    leaf checks: a missing or half-written checkpoint fails the task (and
    rides the retry machinery) instead of silently scoring fresh params.
  * serve — the payload's ``requests`` (or synthetic prompts) through the
    continuous-batching server.

A train or eval task releases its trainer's device state when it ends, so
a warm worker holds compiled steps, not a finished task's state, while its
next task (say, a serve stage) needs the device memory.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Optional, Tuple


def _freeze(v):
    if dataclasses.is_dataclass(v):
        return tuple(sorted(dataclasses.asdict(v).items()))
    return v


class _LRU:
    """Shared LRU mechanics; subclasses define key_of/build/rebind."""

    def __init__(self, capacity: int = 4):
        self.capacity = max(int(capacity), 0)
        self._lru: "OrderedDict[tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._lru)

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "size": len(self._lru)}

    def get(self, cfg):
        key = self.key_of(cfg)
        hit = self._lru.get(key)
        if hit is not None:
            self.hits += 1
            self._lru.move_to_end(key)
            self.rebind(hit, cfg)
            return hit
        self.misses += 1
        obj = self.build(cfg)
        if self.capacity:
            self._lru[key] = obj
            while len(self._lru) > self.capacity:
                self._lru.popitem(last=False)
                self.evictions += 1
        return obj


class TrainerCache(_LRU):
    @staticmethod
    def key_of(cfg) -> Tuple:
        return ("train", cfg.arch, cfg.reduced, cfg.mode, cfg.seq_len,
                cfg.global_batch, cfg.n_pods, cfg.microbatches,
                cfg.data_task, _freeze(cfg.opt), _freeze(cfg.local_sgd))

    @staticmethod
    def build(cfg):
        from repro.runtime.train_loop import Trainer
        return Trainer(cfg)

    @staticmethod
    def rebind(trainer, cfg) -> None:
        trainer.rebind(cfg)


class ServerCache(_LRU):
    @staticmethod
    def key_of(cfg) -> Tuple:
        return ("serve", cfg.arch, cfg.reduced, cfg.slots, cfg.max_len)

    @staticmethod
    def build(cfg):
        from repro.runtime.serve_loop import Server
        return Server(cfg)

    @staticmethod
    def rebind(server, cfg) -> None:
        server.rebind(cfg)


# ------------------------------------------------------------- task semantics
def run_train_task(cache: Optional[TrainerCache], payload: dict) -> dict:
    from repro.runtime.train_loop import TrainJobConfig
    cfg = TrainJobConfig.from_job({"payload": dict(payload)})
    # `is None`, not truthiness: an EMPTY cache is falsy (len 0) but must
    # still be used, or the first task of every family would build cold
    # without populating it
    tr = (TrainerCache(0) if cache is None else cache).get(cfg)
    resumed = 0
    if cfg.checkpoint_dir and payload.get("resume", True):
        # latest committed step in our own directory (0 = fresh start);
        # integrity failures (torn write, stale manifest) raise -> retry
        resumed = tr.restore()
    ran = max(cfg.steps - tr.step, 0)
    m = tr.run(ran) if ran else {}
    losses = tr.metrics.series("loss")
    out = {"steps": tr.step, "loss": m.get("loss", tr.loss()),
           "first_loss": losses[0] if losses else None,
           "ran_steps": ran, "resumed_from": resumed,
           # StepTimer's EMA step wall time and mean host ms per phase of
           # the step (batch, dispatch, sync, log, checkpoint): the flight
           # recorder folds them into the task's execute span so a trace
           # shows not just how long a train task took but how fast its
           # steps were going, and where their host time went
           "step_ema_s": tr.timer.ema_s, "phase_ms": tr.timer.phase_ms}
    if cfg.checkpoint_dir:
        out["checkpoint"] = tr.save_checkpoint()
    tr.release()        # a warm trainer keeps its compiled step, not its state
    return out


def run_eval_task(cache: Optional[TrainerCache], payload: dict) -> dict:
    from repro.runtime.train_loop import TrainJobConfig
    cfg = TrainJobConfig.from_job({"payload": dict(payload)})
    tr = (TrainerCache(0) if cache is None else cache).get(cfg)
    out = {}
    if payload.get("restore_from"):
        # strict: a missing/uncommitted/half-written checkpoint FAILS the
        # task — never a silently-fresh-params eval_loss
        out["restored_step"] = tr.restore(payload["restore_from"],
                                          strict=True)
    batch = tr._sync_batch(10_000)
    loss, _ = tr.model.loss_fn(tr.params_for_eval()
                               if cfg.mode == "local_sgd"
                               else tr.state["params"], batch)
    out["eval_loss"] = float(loss)
    tr.release()
    return out


def run_serve_task(cache: Optional[ServerCache], payload: dict) -> dict:
    from repro.runtime.serve_loop import ServeJobConfig
    cfg = ServeJobConfig.from_job({"payload": dict(payload)})
    srv = (ServerCache(0) if cache is None else cache).get(cfg)
    max_new = int(payload.get("max_new", 8))
    if "requests" in payload:
        # explicit prompts, as a plane serve job carries them
        for r in payload["requests"]:
            srv.submit(r["prompt"], max_new=int(r.get("max_new", max_new)))
    else:
        n = int(payload.get("n_requests", cfg.slots))
        prompt_len = max(int(payload.get("prompt_len", 4)), 1)
        vocab = srv.arch_cfg.vocab_size
        for i in range(n):
            srv.submit([(i + j) % vocab for j in range(prompt_len)],
                       max_new=max_new)
    done = srv.run()
    return {"requests": len(done),
            "generated_tokens": sum(len(r.generated) for r in done),
            "decode_steps": srv.steps}
