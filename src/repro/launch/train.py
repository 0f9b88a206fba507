"""Training launcher.

Two modes:
  * ``--driver``   (default) — run a real (reduced-config on CPU, full on TPU)
    training job through the management plane: registers pods, dispatches a
    train job, ticks heartbeats, prints progress + the boundary byte ledger.
  * ``--direct``   — run the Trainer directly (no management plane), useful for
    quick loss-curve checks and the 100M end-to-end example.

On a real fleet this same file is the per-host entrypoint: jax.distributed
initializes from the scheduler-provided coordinator, make_production_mesh()
builds the (pod, data, model) mesh, and the control agent points at the real
overwatch endpoint instead of the in-process one.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --steps 30
  PYTHONPATH=src python -m repro.launch.train --direct --mode local_sgd
"""
from __future__ import annotations

import argparse
import json


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mode", choices=("sync", "local_sgd"), default="sync")
    ap.add_argument("--direct", action="store_true")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--clusters", type=int, default=2,
                    help="driver mode: number of private clusters")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    payload = {"arch": args.arch, "steps": args.steps, "seq_len": args.seq_len,
               "global_batch": args.global_batch, "mode": args.mode,
               "checkpoint_dir": args.checkpoint_dir}

    if args.direct:
        from repro.runtime.train_loop import Trainer, TrainJobConfig
        tr = Trainer(TrainJobConfig.from_job({"payload": payload}))
        for _ in range(args.steps):
            m = tr.step_once()
            if tr.step % 5 == 0 or tr.step == args.steps:
                print(f"step {tr.step:5d} loss {m.get('loss', m.get('delta_norm', 0)):.4f} "
                      f"({tr.timer.tokens_per_s:.0f} tok/s)")
        return

    from repro.core.plane import ManagementPlane
    from repro.runtime.local_plane import JaxLocalPlane

    plane = ManagementPlane()
    plane.add_cluster("master", is_master=True,
                      local_plane=JaxLocalPlane())
    for i in range(args.clusters):
        name = f"private-{i}"
        agent_holder = {}
        lp = JaxLocalPlane(
            publish=lambda jid, man, _n=name: plane.agents[_n].ow.put(
                f"/checkpoints/{jid}", man),
            checkpoint_root=args.checkpoint_dir or "/tmp/titchener_ckpt")
        plane.add_cluster(name, local_plane=lp)

    jid = plane.submit_job("train", arch=args.arch, steps=args.steps,
                           payload=payload)
    print(f"dispatched {jid}")
    done = plane.run_until_done([jid], max_ticks=10 * args.steps + 100)
    st = plane.job_status(jid)
    print(f"status: {json.dumps(st, indent=1)}")
    print("boundary:", json.dumps(plane.boundary_report()["cross_cluster_bytes"]))
    if not done:
        raise SystemExit("job did not finish in the tick budget")


if __name__ == "__main__":
    main()
