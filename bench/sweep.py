"""Find a serving cell's knee once, on the chip: the highest arrival rate the
server holds without a growing backlog. The cell's traffic file then fixes
its rate below that; the benchmark's own runs never sweep.

    python bench/sweep.py --workload <name> --rates 1,2,3 [--seconds 30]
        [--seed 5] [--out FILE]

One process builds and warms the server once, then for each rate runs a
window of the cell's traffic at that rate, follows it for the grace period,
and lets the server drain before the next. For each rate it prints the
requests sent and finished, TTFT p50/p90, ITL p95, the queue's depth at each
fifth of the window, and the tokens served per second.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench import harness as H
    try:
        _, cell = H.open_cell(args.workload)
    except H.NoDevice as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 3
    serve = H.driver_module("serve")
    traffic = H.traffic_file(cell["traffic"])
    run = H.Run(cell=cell, config=H.config_file(cell["config"]),
                traffic=traffic, seed=args.seed, seconds=args.seconds,
                trace=False, t_process=time.perf_counter(),
                compile_log=H.CompileLog())
    srv, arch, tracker = serve.prepare(run)
    rows = []
    for rate in args.rates:
        reqs = serve.schedule(dict(traffic, rate_per_s=rate), args.seed,
                              args.seconds, arch["vocab_size"])
        depth = []
        step = tracker.step

        def sampled():
            step()
            depth.append((time.perf_counter(), len(srv.queue)))
        tracker.step = sampled
        tokens0 = sum(len(r.generated) for r in srv.requests.values())
        t0, t_end = serve.drive(tracker, reqs, args.seconds,
                                H.TracedWindow(False))
        tokens = sum(len(r.generated) for r in srv.requests.values()) - tokens0
        t_stop = serve.follow(tracker, traffic["grace_s"])
        tracker.step = step
        st = serve.summarize(list(tracker.recs.values()), t_end, t_stop)
        fifths = [max([d for t, d in depth if t <= t0 + k * args.seconds / 5],
                      default=0) for k in range(1, 6)]
        row = {"rate": rate, "sent": st["sent"], "failed": st["failed"],
               "finished": sum(srv.requests[rid].done
                               for rid in tracker.recs),
               "ttft_p50_ms": serve.percentile(st["ttft"], 50),
               "ttft_p90_ms": serve.percentile(st["ttft"], 90),
               "itl_p95_ms": serve.percentile(st["itl"], 95),
               "queue_depth_by_fifth": fifths,
               "tokens_per_s": tokens / (t_end - t0)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        serve.drain(tracker)
        tracker.recs.clear()
    out = {"workload": args.workload, "seconds": args.seconds,
           "rows": rows, "device": H.device_info(cell["chips"])}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
