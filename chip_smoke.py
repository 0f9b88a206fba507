"""Drive the plane's model path once on a TPU, at qwen3-0.6b's published width
(28 layers, d_model 1024, 16/8 heads of 128, vocab 151,936), with random
weights made from a seed.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # only the four-chip comparison

One chip: a ManagementPlane with a HybridComposer runs the DAG
train -> eval -> serve, every task at full width (``reduced: False``). Train
takes a few steps at B=4, S=256 and checkpoints; eval restores that checkpoint
strictly; serve answers requests of mixed prompt length on 4 slots of 512
tokens. Then it checks on the chip that
  * the compiled train and prefill programs hold the Pallas flash kernel
    (``tpu_custom_call``), and whether compiling the train program again hit
    the persistent compilation cache;
  * the Pallas flash forward and gradient agree with the jnp blocked path;
  * prefill plus one greedy decode step agree with ``Model.forward``.

Four chips: the same serve and train programs on a (data=1, model=4) mesh,
against the same programs on ``jax.devices()[0]``, in one process: in bf16
as they run, and in f32 at full matmul precision, where rounding no longer
hides a sharding fault.

It runs only on a TPU: anywhere else, or without the repo's ``src/`` beside
it, it exits non-zero and prints no result. The last line of its output is
one JSON object naming the device, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import TYPE_CHECKING

import numpy as np

if TYPE_CHECKING:
    from repro.runtime.telemetry import CompileCounter

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-0.6b"
REDUCED = False           # the published width; only a CPU rehearsal cuts it
SEED = 0
TRAIN = {"steps": 8, "seq_len": 256, "global_batch": 4}
SERVE = {"slots": 4, "max_len": 512}
PROMPT_LENS = (5, 130, 17, 300, 64, 9)    # mixed: aligned, ragged, tiny
MAX_NEW = 8
FLASH_SEQS = (512, 5)     # the prefill sequence and a 5-token prompt, B=1
# max |pallas - blocked| / max(1, max |blocked|): both take and give bf16, so
# a few bf16 ulps (2^-8 each) of the output or gradient is the floor
FLASH_TOL = 3e-2
LOGIT_TOL = 0.08          # rtol = atol, as test_prefill_decode_matches_forward
LOSS_TOL = 2e-2           # nats, |loss on 4 chips - loss on one|
GRAD_TOL = 1e-3           # f32 gradients, 4 chips vs one, relative to max |g|


class Checks:
    def __init__(self):
        self.failed = []

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {name}: {'pass' if ok else 'FAIL'} {detail}".rstrip(),
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def arch_cfg():
    from repro.configs import base as configs
    cfg = configs.get(ARCH)
    cfg = cfg.reduced() if REDUCED else cfg
    # Trainer and Server build the model this way
    return dataclasses.replace(cfg, remat="none")


def prompts(vocab: int):
    rng = np.random.default_rng(SEED)
    return [rng.integers(1, vocab, n).tolist() for n in PROMPT_LENS]


# ----------------------------------------------------------------- one chip
def run_dag(ckpt_dir: str, log: CompileCounter, checks: Checks) -> None:
    """train -> eval -> serve through the plane, as a user submits it."""
    from repro.core.plane import ManagementPlane
    from repro.pipelines import DAG, HybridComposer, Task

    plane = ManagementPlane()
    plane.add_cluster("master", is_master=True)
    plane.add_cluster("onprem")
    comp = HybridComposer(plane, workers={"onprem": ["w-chip"]})
    phases = {}

    def timed(kind, fn):
        def run(payload):
            t0, mark = time.perf_counter(), log.mark()
            try:
                return fn(payload)
            finally:
                phases[kind] = dict(log.since(mark),
                                    wall_s=time.perf_counter() - t0)
        return run

    for w in comp.workers:
        for kind in ("train", "eval", "serve"):
            w.register(kind, timed(kind, w.handlers[kind]))

    common = {"arch": ARCH, "reduced": REDUCED, "seed": SEED,
              "seq_len": TRAIN["seq_len"],
              "global_batch": TRAIN["global_batch"]}
    reqs = [{"prompt": p, "max_new": MAX_NEW}
            for p in prompts(arch_cfg().vocab_size)]
    dag = DAG("chip_smoke", [
        Task("train", kind="train", retries=0,
             payload={**common, "steps": TRAIN["steps"],
                      "checkpoint_dir": ckpt_dir,
                      "checkpoint_every": 10 * TRAIN["steps"]}),
        Task("eval", kind="eval", upstream=("train",), retries=0,
             payload={**common, "restore_from": {"path": ckpt_dir}}),
        Task("serve", kind="serve", upstream=("eval",), retries=0,
             payload={"arch": ARCH, "reduced": REDUCED, "seed": SEED,
                      **SERVE, "requests": reqs}),
    ])
    comp.add_dag(dag)
    comp.run_dag("chip_smoke", max_ticks=200)
    rows = comp.taskdb.handle({"op": "dag_state", "dag": "chip_smoke"})["tasks"]
    for name in ("train", "eval", "serve"):
        row = rows.get(name, {})
        ph = phases.get(name, {})
        if ph:
            print(f"phase {name}: wall {ph['wall_s']:.3f} s, compile "
                  f"{ph['compile_s']:.3f} s ({ph['loads']} programs, "
                  f"{ph['cache_hits']} from cache), run "
                  f"{ph['wall_s'] - ph['compile_s']:.3f} s", flush=True)
        checks.check(f"taskdb {name}", row.get("status") == "success",
                     f"status={row.get('status')} "
                     f"{row.get('error') or row.get('result')}")
    if checks.failed:
        return
    tr, ev, sv = (rows[n]["result"] for n in ("train", "eval", "serve"))
    first, last = tr["first_loss"], tr["loss"]
    checks.check("train loss finite and falling",
                 math.isfinite(first) and math.isfinite(last) and last < first,
                 f"first step {first!r}, step {tr['steps']} {last!r}")
    checks.check("eval restored_step", ev["restored_step"] == TRAIN["steps"],
                 f"restored_step={ev['restored_step']} "
                 f"eval_loss={ev['eval_loss']!r}")
    checks.check("serve answered", sv["requests"] == len(reqs)
                 and sv["generated_tokens"] == len(reqs) * MAX_NEW,
                 f"requests={sv['requests']} generated_tokens="
                 f"{sv['generated_tokens']} decode_steps={sv['decode_steps']}")


def check_programs(mesh, log: CompileCounter, checks: Checks) -> None:
    """The train and prefill programs the tasks ran hold the Pallas kernel,
    and compiling the train program again is served by the cache."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.steps import (abstract_train_state, make_train_step,
                                    named, train_state_specs)
    from repro.models.model import Model
    from repro.parallel.sharding import MeshPlan
    from repro.runtime.train_loop import TrainJobConfig

    cfg = arch_cfg()
    plan = MeshPlan(mesh=mesh, fsdp=False)
    model = Model(cfg, plan)

    def shaped(tree, specs):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, named(mesh, specs))

    rep = NamedSharding(mesh, P())
    B, S = TRAIN["global_batch"], TRAIN["seq_len"]
    state = shaped(abstract_train_state(cfg), train_state_specs(cfg, plan))
    batch = {k: jax.ShapeDtypeStruct((B, S), dt, sharding=rep)
             for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                           ("loss_mask", jnp.bfloat16))}
    opt = TrainJobConfig().opt
    texts = []
    for attempt in ("first", "second"):
        t0, mark = time.perf_counter(), log.mark()
        step = jax.jit(make_train_step(model, opt, 1), donate_argnums=(0,))
        texts.append(step.lower(state, batch).compile().as_text())
        got = log.since(mark)
        print(f"compile cache: train step compiled ({attempt} time here) in "
              f"{time.perf_counter() - t0:.3f} s, cache hit: "
              f"{got['cache_hits'] > 0}", flush=True)
    checks.check("train program holds tpu_custom_call",
                 "tpu_custom_call" in texts[-1])
    params = shaped(model.abstract_params(), model.param_specs())
    for n in (PROMPT_LENS[0], PROMPT_LENS[1]):
        prefill = jax.jit(lambda p, b: model.prefill(
            p, b, max_len=SERVE["max_len"]))
        text = prefill.lower(params, {"tokens": jax.ShapeDtypeStruct(
            (1, n), jnp.int32, sharding=rep)}).compile().as_text()
        checks.check(f"prefill program (prompt {n}) holds tpu_custom_call",
                     "tpu_custom_call" in text)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(1.0, np.abs(want).max()))


def check_flash(checks: Checks) -> None:
    """Pallas flash forward and gradient against the jnp blocked path, at the
    config's heads and head_dim."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    cfg = arch_cfg()
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    print("flash backward: the jnp blocked backward on the Pallas forward's "
          "o and lse (there is no Pallas backward kernel)", flush=True)
    for S in FLASH_SEQS:
        ks = jax.random.split(jax.random.PRNGKey(SEED + S), 4)
        q = jax.random.normal(ks[0], (1, S, H, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, S, K, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, S, K, D), jnp.bfloat16)
        do = jax.random.normal(ks[3], (1, S, H, D), jnp.bfloat16)
        out = {}
        for impl in ("pallas", "blocked"):
            fn = jax.jit(lambda q, k, v, impl=impl: jax.vjp(
                lambda *a: ops.flash_attention(*a, impl=impl), q, k, v))
            o, vjp = fn(q, k, v)
            out[impl] = (o,) + tuple(vjp(do))
        errs = [rel_err(a, b) for a, b in zip(out["pallas"], out["blocked"])]
        checks.check(f"flash pallas vs blocked S={S}",
                     max(errs) <= FLASH_TOL,
                     "max rel err o/dq/dk/dv " + " ".join(f"{e:.3e}" for e in errs)
                     + f" (tol {FLASH_TOL})")


# bf16 is what the server and trainer run: two correct programs round
# differently there, and 28 layers grow it past LOGIT_TOL (decode vs forward
# differs by about 0.19 on one chip), so only greedy tokens must agree. In
# f32 with every matmul at full precision, rounding is out of the way and a
# wrong cache slot, mask, rope or sharding shows at any width.
PRECISIONS = (("bfloat16", None), ("float32", "highest"))


def model_logits(mesh, dtype: str, show_placement: bool = False) -> dict:
    """Logits of two sequences of k+1 tokens, weights from SEED in ``dtype``:
    "forward" holds forward(t[:k+1]) at rows k-1 and k, "served" holds
    prefill(t[:k])'s last logits and decode(t[k])'s, as the server runs them.
    """
    import jax
    from repro.launch.steps import named
    from repro.models.model import Model
    from repro.parallel.sharding import MeshPlan

    cfg = dataclasses.replace(arch_cfg(), dtype=dtype)
    model = Model(cfg, MeshPlan(mesh=mesh, fsdp=False))
    params = jax.jit(model.init_params, out_shardings=named(
        mesh, model.param_specs()))(jax.random.PRNGKey(SEED))
    if show_placement:
        print(f"params on {dict(mesh.shape)}:")
        leaf_placement(params)
    k = PROMPT_LENS[1]
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (2, k + 1), 1,
                                cfg.vocab_size)
    full, _ = jax.jit(model.forward)(params, {"tokens": tokens})
    last, cache = jax.jit(lambda p, b: model.prefill(
        p, b, max_len=SERVE["max_len"]))(params, {"tokens": tokens[:, :k]})
    step, _ = jax.jit(model.decode_step)(params, tokens[:, k:], cache)
    as32 = lambda a: np.asarray(a, np.float32)
    return {"forward": (as32(full[:, k - 1]), as32(full[:, k])),
            "served": (as32(last), as32(step))}


def check_logits(checks: Checks, what: str, dtype: str, got, want) -> None:
    """Prefill and decode logits ``got`` against ``want``: within
    rtol=atol=LOGIT_TOL, as test_prefill_decode_matches_forward, in f32;
    the same greedy tokens in bf16."""
    for name, a, b in zip(("prefill", "decode"), got, want):
        err = np.abs(a - b)
        within = bool(np.all(err <= LOGIT_TOL + LOGIT_TOL * np.abs(b)))
        same = float(np.mean(a.argmax(-1) == b.argmax(-1)))
        strict = dtype == "float32"
        checks.check(
            f"{name} logits {what} ({dtype})", within if strict else same == 1,
            f"max abs err {err.max():.4e} (rtol=atol={LOGIT_TOL}"
            f"{'' if strict else ', not required'}), max |logit| "
            f"{np.abs(b).max():.4e}, greedy token agreement {same:.2f}")


def check_decode(mesh, checks: Checks) -> None:
    """Prefill plus one decode step against Model.forward."""
    import jax

    for dtype, precision in PRECISIONS:
        with jax.default_matmul_precision(precision):
            out = model_logits(mesh, dtype)
        check_logits(checks, "vs forward", dtype, out["served"], out["forward"])
        gc.collect()


def one_chip(log: CompileCounter, checks: Checks) -> None:
    import jax
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh()               # (data=1, model=1) on devices()[0]
    print(f"mesh: {dict(mesh.shape)} on {list(mesh.devices.flat)}", flush=True)
    with tempfile.TemporaryDirectory(dir=ROOT, prefix=".smoke_ckpt_") as ck:
        run_dag(ck, log, checks)
    gc.collect()
    t0 = time.perf_counter()
    check_programs(mesh, log, checks)
    check_flash(checks)
    check_decode(mesh, checks)
    print(f"phase checks: wall {time.perf_counter() - t0:.3f} s", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
          f"(bytes_limit {stats.get('bytes_limit')})", flush=True)


# --------------------------------------------------------------- four chips
def leaf_placement(tree) -> None:
    """How many devices each leaf lives on, and the shard each one holds."""
    import jax
    counts = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        n = len(leaf.sharding.device_set)
        shard = leaf.sharding.shard_shape(leaf.shape)
        counts[n] = counts.get(n, 0) + 1
        print(f"  {jax.tree_util.keystr(path)} {tuple(leaf.shape)} on {n} "
              f"devices, shard {tuple(shard)}"
              f"{' (split)' if shard != leaf.shape else ''}")
    print(f"  leaves by device count: {counts}", flush=True)


def train_losses(mesh):
    """Per-step losses of a few Trainer steps (bf16 weights, f32 AdamW)."""
    from repro.runtime.train_loop import Trainer, TrainJobConfig

    tr = Trainer(TrainJobConfig(arch=ARCH, reduced=REDUCED, seed=SEED,
                                steps=3, seq_len=TRAIN["seq_len"],
                                global_batch=TRAIN["global_batch"]), mesh=mesh)
    if mesh.size > 1:
        print(f"train state on {dict(mesh.shape)}:")
        leaf_placement(tr.state["params"])
    tr.run()
    losses = tr.metrics.series("loss")
    tr.release()
    return losses


def loss_and_grads(mesh):
    """The train program's loss and gradients in f32 at full matmul
    precision, at the seed's weights, on one batch of B=4, S=256."""
    import jax
    import jax.numpy as jnp
    from repro.launch.steps import named
    from repro.models.model import Model
    from repro.parallel.sharding import MeshPlan

    cfg = dataclasses.replace(arch_cfg(), dtype="float32")
    model = Model(cfg, MeshPlan(mesh=mesh, fsdp=False))
    params = jax.jit(model.init_params, out_shardings=named(
        mesh, model.param_specs()))(jax.random.PRNGKey(SEED))
    tokens = jax.random.randint(
        jax.random.PRNGKey(SEED + 2),
        (TRAIN["global_batch"], TRAIN["seq_len"] + 1), 1, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:],
             "loss_mask": jnp.ones(tokens[:, 1:].shape, jnp.float32)}
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(jax.value_and_grad(
            model.loss_fn, has_aux=True))(params, batch)
    return float(loss), [np.asarray(g, np.float32)
                         for g in jax.tree_util.tree_leaves(grads)]


def four_chips(log: CompileCounter, checks: Checks) -> None:
    """The serve and train programs on (data=1, model=4) against one chip."""
    import jax
    from repro.launch.mesh import make_test_mesh

    if not checks.check("four devices", len(jax.devices()) >= 4,
                        f"found {len(jax.devices())}"):
        return
    meshes = {"4 chips": make_test_mesh((1, 4), ("data", "model")),
              "1 chip": make_test_mesh()}
    logits, losses, grads = {}, {}, {}
    for name, mesh in meshes.items():
        t0, mark = time.perf_counter(), log.mark()
        for dtype, precision in PRECISIONS:
            with jax.default_matmul_precision(precision):
                logits[name, dtype] = model_logits(
                    mesh, dtype, mesh.size > 1 and dtype == "bfloat16")["served"]
            gc.collect()
        losses[name] = train_losses(mesh)
        gc.collect()
        grads[name] = loss_and_grads(mesh)
        gc.collect()
        got = log.since(mark)
        print(f"phase {name}: wall {time.perf_counter() - t0:.3f} s, compile "
              f"{got['compile_s']:.3f} s, bf16 train losses {losses[name]}, "
              f"f32 loss {grads[name][0]!r}", flush=True)
    for dtype, _ in PRECISIONS:
        check_logits(checks, "4 chips vs 1", dtype, logits["4 chips", dtype],
                     logits["1 chip", dtype])
    # step 1 is a forward at the same weights; AdamW's first updates are
    # nearly sign(g), so later steps amplify bf16 rounding of small gradients
    four, one = losses["4 chips"], losses["1 chip"]
    diff = [abs(a - b) for a, b in zip(four, one)]
    falling = all(len(l) == 3 and all(map(math.isfinite, l)) and l[-1] < l[0]
                  for l in (four, one))
    checks.check("bf16 train loss 4 chips vs 1", falling and diff[0] <= LOSS_TOL,
                 f"step 1 |diff| {diff[0]!r} (tol {LOSS_TOL}); steps 2-3 "
                 f"{diff[1:]} (not required); finite and falling: {falling}")
    (loss4, g4), (loss1, g1) = grads["4 chips"], grads["1 chip"]
    errs = [float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))
            for a, b in zip(g4, g1)]
    checks.check("f32 loss and gradients 4 chips vs 1",
                 abs(loss4 - loss1) <= LOSS_TOL and max(errs) <= GRAD_TOL,
                 f"loss |diff| {abs(loss4 - loss1):.3e} (tol {LOSS_TOL}), "
                 f"max gradient rel err {max(errs):.3e} over {len(errs)} "
                 f"leaves (tol {GRAD_TOL})")


# ---------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime.telemetry import COMPILES

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}", flush=True)
    print(f"model: {ARCH} reduced={REDUCED} {arch_cfg()}", flush=True)
    log, checks = COMPILES, Checks()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(log, checks)
    print(f"total: {time.perf_counter() - t0:.3f} s, {log.loads} "
          f"programs compiled in {log.seconds:.3f} s, "
          f"{log.hits} from the cache", flush=True)
    if checks.failed:
        print(f"chip_smoke: failed: {checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
