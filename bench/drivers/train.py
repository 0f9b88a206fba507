"""Closed-loop training: ``Trainer.step_once`` back to back, on the trainer
that the plane's ``TrainerCache.get`` hands a train task.

Set-up builds that trainer, puts the benchmark's weights (from the seed) in
its state and the benchmark's token rows in its feed, and drives it through
the first ``check.steps`` steps, which compile every program the window runs
and which the check reads: each step's loss, the first gradient as the
optimizer got it (from AdamW's first moment after one step and the step's
unclipped norm), and each leaf's change after those steps. The window then
runs further steps of the same object until ``--seconds`` have passed.
``train_tokens_per_s`` is the tokens of every step completed in the window
over the window's length, from its start to the end of its last step; each
step ends in the host sync ``step_once`` makes on its metrics.

Traffic keys: ``seq_len``, ``global_batch``, ``opt`` (the program's AdamW
settings), ``trace_start_s``/``trace_seconds`` (the part of the window the
traced run records) and ``check`` (``steps`` and the ``limits``).
"""
from __future__ import annotations

import functools
import gc
import statistics
import time

import jax
import jax.numpy as jnp

from bench import arch as A
from bench import flops as FL
from bench import harness as H
from bench import reference as R
from bench import trace_reduce
from bench import weights as W

TRAIN_PROGRAM = r"^jit_train_step\("
ADAMW_KEYS = ("peak_lr", "warmup_steps", "total_steps", "b1", "b2", "eps",
              "weight_decay", "grad_clip")


class Feed:
    """The benchmark's token rows, in the place of the trainer's own data
    pipeline: step ``s``'s batch is a function of the seed and ``s``."""

    def __init__(self, seed_k, batch: int, seq_len: int, vocab: int):
        self.seed_k, self.step = seed_k, 0
        self._rows = jax.jit(lambda k, s: W.token_rows(k, s, batch, seq_len,
                                                       vocab))

    def global_batch_at(self, step: int) -> dict:
        return self._rows(self.seed_k, jnp.int32(step))

    def state_dict(self) -> dict:
        return {"step": int(self.step)}

    def load_state_dict(self, state: dict) -> None:
        self.step = int(state["step"])


def leaf_norms(tree: dict) -> dict:
    """Per-leaf L2 norms of a tree in the benchmark's layout, by dotted name."""
    flat = {}
    for k, v in tree.items():
        if k == "layers":
            flat.update({f"layers.{n}": t for n, t in v.items()})
        else:
            flat[k] = v
    return {k: jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32))))
            for k, t in flat.items()}


def _floats(d: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(d).items()}


def build(run: H.Run):
    """The trainer as ``TrainerCache.get`` returns it, holding the
    benchmark's weights and feed."""
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.runtime.step_cache import TrainerCache
    from repro.runtime.train_loop import TrainJobConfig
    name, tr = run.cell["config"], run.traffic
    name = A.register(name, run.config).name
    arch = A.arch(run.config)
    job = TrainJobConfig(
        arch=name, reduced=False, steps=1 << 30, seq_len=tr["seq_len"],
        global_batch=tr["global_batch"], seed=run.seed % (1 << 31),
        opt=AdamWConfig(**{k: tr["opt"][k] for k in ADAMW_KEYS}))
    trainer = TrainerCache(1).get(job)
    seed_k = W.seed_key(run.seed)
    dtype = jnp.dtype(run.config["torch_dtype"])

    def state(k):
        params = W.to_program(W.all_weights(arch, k, dtype))
        return {"params": params, "opt": init_opt_state(params)}

    trainer.state = None                      # free the program's own draw
    trainer.state = jax.jit(state, out_shardings=trainer._state_shardings)(
        seed_k)
    trainer.data = Feed(seed_k, tr["global_batch"], tr["seq_len"],
                        arch["vocab_size"])
    return trainer, arch, seed_k


def first_steps(trainer, arch: dict, seed_k, opt: dict, n: int,
                step=None) -> dict:
    """Drive the trainer through its first ``n`` steps and read what the
    check compares. ``step`` replaces ``trainer.step_once`` (the window's
    own call, with its span)."""
    step = step or trainer.step_once
    dtype = jnp.dtype(trainer.arch_cfg.dtype)
    norms = jax.jit(lambda t: leaf_norms(W.from_program(t)))
    change = jax.jit(lambda master, k: leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b.astype(jnp.float32), W.from_program(master),
        W.all_weights(arch, k, dtype))))
    losses, grads = [], None
    for i in range(n):
        m = step()
        losses.append(m["loss"])
        if i == 0:
            clip = min(1.0, opt["grad_clip"] / max(m["grad_norm"], 1e-9))
            scale = 1.0 / ((1 - opt["b1"]) * clip)
            grads = {k: v * scale for k, v in
                     _floats(norms(trainer.state["opt"]["m"])).items()}
    deltas = _floats(change(trainer.state["opt"]["master"], seed_k))
    return {"loss": losses, "grad": grads, "change": deltas}


def reference_readings(arch: dict, seed: int, batch: int, seq_len: int,
                       opt: dict, n: int, dtype, compute_dtype=None,
                       rows=None) -> dict:
    """The reference's own ``n`` steps from the same weights and rows.
    ``rows`` limits each batch to its first rows (a planted fault)."""
    seed_k = W.seed_key(seed)
    params = W.reference_all(arch, seed, dtype)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    loss_grad = R.make_loss_and_grad(arch, compute_dtype)
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    step_fn = jax.jit(functools.partial(R.adamw_step, opt=opt))
    norms = jax.jit(leaf_norms)
    rows_fn = jax.jit(lambda k, s: W.token_rows(k, s, batch, seq_len,
                                                arch["vocab_size"]))
    losses, grads = [], None
    with jax.default_matmul_precision("highest"):
        for s in range(n):
            b = rows_fn(seed_k, jnp.int32(s))
            use = rows or batch
            n_tok = float(use * seq_len)
            loss, g = 0.0, None
            for r in range(use):
                lr, gr = loss_grad(params, b["tokens"][r], b["targets"][r],
                                   n_tok)
                loss += float(lr)
                g = gr if g is None else add(g, gr)
                del gr
            losses.append(loss)
            if s == 0:
                grads = _floats(norms(g))
            params, m, v, _ = step_fn(params, g, m, v, jnp.float32(s + 1))
            del g
    del m, v
    base = W.reference_all(arch, seed, dtype)
    deltas = _floats(jax.jit(lambda p, q: leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, p, q)))(params, base))
    return {"loss": losses, "grad": grads, "change": deltas}


def worst_leaf_gap(prog: dict, ref: dict, ref_grad: dict) -> tuple:
    """Largest |‖prog‖ - ‖ref‖| over leaves, each against the larger of the
    reference leaf's norm and the median leaf's. Leaves whose reference
    gradient is under a thousandth of the median leaf's gradient are left
    out (they move by round-off alone). Returns (gap, leaf)."""
    med_g = statistics.median(ref_grad.values())
    keep = [k for k in ref if ref_grad[k] >= 1e-3 * med_g]
    med = statistics.median(ref[k] for k in keep)
    worst = max(keep, key=lambda k: abs(prog[k] - ref[k]) / max(ref[k], med))
    return abs(prog[worst] - ref[worst]) / max(ref[worst], med), worst


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers the check holds to their limits."""
    g, g_leaf = worst_leaf_gap(prog["grad"], ref["grad"], ref["grad"])
    c, c_leaf = worst_leaf_gap(prog["change"], ref["change"], ref["grad"])
    loss = max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))
    return {"loss_gap": loss, "grad_norm_gap": g, "grad_leaf": g_leaf,
            "change_norm_gap": c, "change_leaf": c_leaf}


def run(run: H.Run) -> H.Outcome:
    tr = run.traffic
    B, S = tr["global_batch"], tr["seq_len"]
    n_check = tr["check"]["steps"]
    with run.spans.span("setup.build"):
        trainer, arch, seed_k = build(run)
    step = run.spans.wrap("train.step_once", trainer.step_once)
    with run.spans.span("setup.first_steps"):
        prog = first_steps(trainer, arch, seed_k, tr["opt"], n_check, step)
    setup_s = time.perf_counter() - run.t_process
    run.note(f"setup: {setup_s:.3f} s; {run.compile_log.describe()}")

    mark = run.compile_log.mark()
    traced = H.TracedWindow(run.trace)
    traced.open()
    steps, t0 = 0, time.perf_counter()
    now = t0
    try:
        while now - t0 < run.seconds:
            if now - t0 >= tr.get("trace_start_s", 0.0):
                traced.start()
            step()
            steps += 1
            now = time.perf_counter()
            if traced.active and now - traced.t0 >= tr["trace_seconds"]:
                traced.stop()
    finally:
        traced.stop()
    window = now - t0
    traced.close()
    in_window = run.compile_log.since(mark)
    peak = H.memory_peak_bytes(run.cell["chips"])
    traced_steps = len([r for r in run.spans.of("train.step_once")
                        if traced.covers(r[1]) and traced.covers(r[2])])
    run.note(f"window: {window:.3f} s, {steps} steps of {B}x{S} tokens, "
             f"{in_window['loads']} programs loaded in the window "
             f"({in_window['compiles']} compiled), "
             f"peak HBM {peak} B")
    observed = {}
    if run.trace:
        observed = traced_observations(run, traced, arch, B, S, traced_steps)

    trainer.state = None
    del trainer, step
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(arch, run.seed, B, S, tr["opt"], n_check,
                             jnp.dtype(run.config["torch_dtype"]))
    gaps = compare(prog, ref)
    run.note(f"reference: {time.perf_counter() - t_ref:.3f} s; losses "
             f"program {prog['loss']} reference {ref['loss']}; worst leaves "
             f"grad {gaps['grad_leaf']} change {gaps['change_leaf']}")
    limits = tr["check"]["limits"]
    checks = [H.check(k, gaps[k], limits[k]) for k in
              ("loss_gap", "grad_norm_gap", "change_norm_gap")]
    return H.Outcome(
        metrics={"train_tokens_per_s": steps * B * S / window,
                 "setup_s": setup_s},
        attempted=steps, failed=0, checks=checks, observed=observed,
        memory_peak_bytes=peak)


def traced_observations(run: H.Run, traced: H.TracedWindow, arch: dict,
                        B: int, S: int, traced_steps: int) -> dict:
    path = traced.path()
    try:
        summary = trace_reduce.reduce(
            path, programs={"train": TRAIN_PROGRAM},
            kernels={"flash.train": (trace_reduce.FLASH_FORWARD_OP,
                                      TRAIN_PROGRAM)})
    finally:
        traced.cleanup()
    kind = H.device_info(run.cell["chips"])["kind"]
    calls = summary["programs"]["train"]["count"]
    run.note(f"trace: {traced_steps} steps traced, {calls} train programs "
             f"on the device, busy {summary['busy_s']:.4f} s of "
             f"{summary['window_s']:.4f} s")
    fl = FL.flash_forward(B, arch["num_attention_heads"],
                          arch["num_key_value_heads"], S, arch["head_dim"])
    per_step_flash = arch["num_hidden_layers"]
    return {"trace": summary, "peak": FL.peaks(kind),
            "work": {"train": {"flops": FL.train_step(arch, B, S) * calls,
                               "calls": calls},
                     "flash.train": {
                         "ops": fl["ops"] * per_step_flash * calls,
                         "bytes": fl["bytes"] * per_step_flash * calls,
                         "calls": per_step_flash * calls}}}
