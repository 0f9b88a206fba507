"""Where a training cell's device memory goes, on the chip, in one process:
the compiled train step's own account beside the allocator's.

    python bench/memory.py --workload <train cell> --seed N [--batches 4]
        [--out FILE]

It builds the cell's trainer as a run does and drives its first steps, then
prints ``memory_stats()`` of the chip (every key), the bytes of the arrays
alive, and ``memory_analysis()`` of the train step compiled at the cell's
batch (arguments, outputs, aliased, temporaries, code). Each batch of
``--batches`` is compiled too, from shapes alone, and a compile the chip's
memory refuses is reported with the compiler's message. The benchmark's own
runs never run this. It prints one JSON object as its last line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

ANALYSIS = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")


def analysis(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {k: int(getattr(ma, k)) for k in ANALYSIS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--batches", default="",
                    type=lambda s: [int(x) for x in s.split(",") if x])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench import harness as H
    try:
        _, cell = H.open_cell(args.workload)
    except H.NoDevice as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 3
    import jax
    traffic = H.traffic_file(cell["traffic"])
    if traffic["driver"] != "train":
        print("memory.py reads training cells only", file=sys.stderr)
        return 2
    train = H.driver_module("train")
    run = H.Run(cell=cell, config=H.config_file(cell["config"]),
                traffic=traffic, seed=args.seed, seconds=0, trace=False,
                t_process=time.perf_counter(), compile_log=H.CompileLog())
    dev = jax.devices()[0]
    out = {"workload": args.workload, "stats": {}}
    trainer, arch, seed_k = train.build(run)
    out["stats"]["after_build"] = dev.memory_stats()
    train.first_steps(trainer, arch, seed_k, traffic["opt"],
                      traffic["check"]["steps"])
    out["stats"]["after_steps"] = dev.memory_stats()
    out["live_array_bytes"] = sum(a.nbytes for a in jax.live_arrays())
    out["state_bytes"] = sum(a.nbytes for a in
                             jax.tree_util.tree_leaves(trainer.state))
    batch = trainer._sync_batch(trainer.step)
    out["step"] = {str(traffic["global_batch"]): analysis(
        trainer.step_fn.lower(trainer.state, batch).compile())}
    state_shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        trainer.state)
    for b in args.batches:
        shapes = {k: jax.ShapeDtypeStruct((b,) + v.shape[1:], v.dtype,
                                          sharding=v.sharding)
                  for k, v in batch.items()}
        try:
            out["step"][str(b)] = analysis(
                trainer.step_fn.lower(state_shapes, shapes).compile())
        except Exception as e:                       # noqa: BLE001
            out["step"][str(b)] = {"refused": str(e)[:600]}
    out["device"] = H.device_info(cell["chips"])
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
