"""Readings that set a cell's correctness limits, on the chip, in one process.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--seconds S] [--out FILE]

For each seed of ``--seeds`` it reads the numbers the cell's check compares
for the program (the lower readings). For each seed of ``--control-seeds``
it reads them for the control, the float32 reference put in the program's
place at the precision below the configuration's (float8 e4m3 for
bfloat16), and, for a training cell, for the planted fault "half the batch
left out, the mean taken over the rest" (the upper readings). A serving
cell's run uses ``--seconds`` of its traffic. The benchmark's own runs never
run this. It prints one JSON object as its last line.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

CONTROL_DTYPE = {"bfloat16": "float8_e4m3fn", "float16": "float8_e4m3fn",
                 "float32": "bfloat16"}


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def train_readings(cell, config, traffic, prog_seeds, control_seeds):
    import jax.numpy as jnp
    from bench import harness as H
    train = H.driver_module("train")
    dtype = jnp.dtype(config["torch_dtype"])
    low = jnp.dtype(CONTROL_DTYPE[config["torch_dtype"]])
    B, S, opt = traffic["global_batch"], traffic["seq_len"], traffic["opt"]
    n = traffic["check"]["steps"]
    prog = {}
    for seed in prog_seeds:
        run = H.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                    seconds=0, trace=False, t_process=time.perf_counter())
        trainer, arch, seed_k = train.build(run)
        prog[seed] = train.first_steps(trainer, arch, seed_k, opt, n)
        trainer.state = None
        del trainer
        gc.collect()
    out = {"program": {}, "control": {}, "half_batch": {}}
    for seed in prog_seeds:
        ref = train.reference_readings(arch, seed, B, S, opt, n, dtype)
        out["program"][seed] = train.compare(prog[seed], ref)
        print("program", seed, out["program"][seed], flush=True)
        if seed in control_seeds:
            ctl = train.reference_readings(arch, seed, B, S, opt, n, dtype,
                                           compute_dtype=low)
            out["control"][seed] = train.compare(ctl, ref)
            half = train.reference_readings(arch, seed, B, S, opt, n, dtype,
                                            rows=B // 2)
            out["half_batch"][seed] = train.compare(half, ref)
            print("control", seed, out["control"][seed], flush=True)
            print("half_batch", seed, out["half_batch"][seed], flush=True)
    return out


def serve_readings(cell, config, traffic, prog_seeds, control_seeds,
                   seconds):
    import jax.numpy as jnp
    from bench import harness as H
    serve = H.driver_module("serve")
    dtype = jnp.dtype(config["torch_dtype"])
    low = jnp.dtype(CONTROL_DTYPE[config["torch_dtype"]])
    log = H.CompileLog()
    out = {"program": {}, "control": {}}
    for seed in prog_seeds:
        run = H.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                    seconds=seconds, trace=False,
                    t_process=time.perf_counter(), compile_log=log)
        m = serve.measure(run)
        gap, n_tok = serve.served_gap(m["arch"], seed, m["sample"],
                                      traffic["max_len"], dtype)
        out["program"][seed] = {"served_logit_gap": gap, "tokens": n_tok,
                                "metrics": m["metrics"]}
        print("program", seed, out["program"][seed], flush=True)
        if seed in control_seeds:
            ctl = serve.control_gap(m["arch"], seed, m["sample"],
                                    traffic["max_len"], dtype, low)
            out["control"][seed] = {"served_logit_gap": ctl}
            print("control", seed, out["control"][seed], flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench import harness as H
    try:
        _, cell = H.open_cell(args.workload)
    except H.NoDevice as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 3
    config = H.config_file(cell["config"])
    traffic = H.traffic_file(cell["traffic"])
    seeds_all = list(dict.fromkeys(args.seeds + args.control_seeds))
    if traffic["driver"] == "train":
        out = train_readings(cell, config, traffic, seeds_all,
                             set(args.control_seeds))
    elif traffic["driver"] == "serve":
        out = serve_readings(cell, config, traffic, seeds_all,
                             set(args.control_seeds), args.seconds)
    else:
        print(f"no calibration for driver {traffic['driver']!r}",
              file=sys.stderr)
        return 2
    out["device"] = H.device_info(cell["chips"])
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
