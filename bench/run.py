"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds the program under ``src/``. The cell
is looked up in ``BENCHMARK.json``; its configuration, traffic mix, driver
and per-layer readers are files under ``bench/`` named after it. The run
builds its weights and inputs from ``--seed``, warms up every shape its
traffic uses (that is ``setup_s``), measures for ``--seconds``, checks what
the timed path produced against the float32 reference, and prints one JSON
object as the last line of standard output. ``--trace 1`` traces part of the
window and reports the cell's per-layer metrics instead of its end-to-end
ones.

It runs only on a TPU with as many chips as the cell asks for: otherwise, or
without the program beside it, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

T_PROCESS = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    try:
        benchmark, cell = harness.open_cell(args.workload)
    except harness.NoDevice as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 3
    line = run_cell(benchmark, cell, args.seed, args.seconds, bool(args.trace),
                    T_PROCESS)
    print(json.dumps(line), flush=True)
    return 0


def run_cell(benchmark: dict, cell: dict, seed: int, seconds: float,
             trace: bool, t_process: float, root: Path = ROOT,
             config: dict = None, traffic: dict = None) -> dict:
    """Everything after the device check, with the cell's files looked up
    under ``root`` unless given; tests call it on the CPU."""
    from bench import harness
    config = config or harness.config_file(cell["config"], root)
    traffic = traffic or harness.traffic_file(cell["traffic"], root)
    run = harness.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                      seconds=seconds, trace=trace, t_process=t_process,
                      compile_log=harness.CompileLog())
    driver = harness.driver_module(traffic["driver"], root)
    out = driver.run(run)
    device = harness.device_info(cell["chips"])
    for c in out.checks:
        run.note(f"compared {c['name']}: {c['value']!r} limit {c['limit']!r} "
                 f"{'ok' if c['ok'] else 'FAIL'}")
    return harness.result_line(out, cell, benchmark, device, trace, root)


if __name__ == "__main__":
    sys.exit(main())
