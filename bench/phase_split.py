"""Split a train cell's device idle by the program's host phases.

    python3 bench/phase_split.py --workload qwen3-0.6b.train.s1024 --seed <n>

On a TPU, from the root of a checkout. It builds the cell's trainer, weights
and feed as its driver does (``bench/drivers/train.py``), takes the check's
first steps to compile, then steps as the driver's traced window does: the
profiler on, ``trace_start_s`` seconds untraced, then ``trace_seconds``
inside ``bench.traced``, each step inside ``bench.train.step_once``. The
trace is reduced by ``bench/span_reduce.py`` under ``repro.train.``.

It prints the reduction's note and the trainer's programs loaded by (span,
step) to standard error, and one JSON line last: the window's idle share,
the share of its idle that the program's spans cover, and the per-step
medians of ``span_reduce.step_medians``. No reference runs and nothing is
compared: the benchmark's own run (``bench/run.py``) decides ``correct``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
PREFIX = "repro.train."


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness as H
    from bench import span_reduce, trace_reduce
    try:
        _, cell = H.open_cell(args.workload)
    except H.NoDevice as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 3
    traffic = H.traffic_file(cell["traffic"])
    if traffic["driver"] != "train":
        print(f"{args.workload}: not a train cell", file=sys.stderr)
        return 2
    driver = H.driver_module("train")
    run = H.Run(cell=cell, config=H.config_file(cell["config"]),
                traffic=traffic, seed=args.seed, seconds=0.0, trace=True,
                t_process=time.perf_counter())
    trainer, arch, seed_k = driver.build(run)
    step = run.spans.wrap("train.step_once", trainer.step_once)
    driver.first_steps(trainer, arch, seed_k, traffic["opt"],
                       traffic["check"]["steps"], step)

    traced = H.TracedWindow(True)
    traced.open()
    t0 = time.perf_counter()
    try:
        while time.perf_counter() - t0 < traffic["trace_start_s"]:
            step()
        traced.start()
        while time.perf_counter() - traced.t0 < traffic["trace_seconds"]:
            step()
    finally:
        traced.close()
    try:
        path = traced.path()
        window = trace_reduce.reduce(path)
        summary = span_reduce.reduce(path, PREFIX, driver.TRAIN_PROGRAM)
    finally:
        traced.cleanup()
    run.note(span_reduce.describe(summary, PREFIX))
    run.note("programs loaded by (span, step): "
             f"{dict(trainer.spans.compiles)}")
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "steps": len(summary["steps"]),
                      "idle_share": window["idle_share"],
                      "coverage": summary["coverage"],
                      "per_step": span_reduce.step_medians(summary, PREFIX)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
