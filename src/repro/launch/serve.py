"""Serving launcher: batched requests against a small model.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --requests 6

Drives the continuous-batching Server either directly or as a managed job
through the ManagementPlane (``--driver``), mirroring the train launcher.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--driver", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    prompts = [[1 + (i % 7), 2, 3 + i % 5] + [4] * (i % 4)
               for i in range(args.requests)]

    if args.driver:
        from repro.core.plane import ManagementPlane
        from repro.runtime.local_plane import JaxLocalPlane
        plane = ManagementPlane()
        plane.add_cluster("master", is_master=True,
                          local_plane=JaxLocalPlane())
        plane.add_cluster("edge-0", local_plane=JaxLocalPlane())
        jid = plane.submit_job(
            "serve", arch=args.arch,
            payload={"arch": args.arch, "slots": args.slots,
                     "max_len": args.max_len,
                     "requests": [{"prompt": p, "max_new": args.max_new}
                                  for p in prompts]})
        ok = plane.run_until_done([jid], max_ticks=500)
        print("job:", plane.job_status(jid), "ok:", ok)
        if not ok:
            raise SystemExit("serve job did not finish in the tick budget")
        return

    from repro.runtime.serve_loop import Server, ServeJobConfig
    server = Server(ServeJobConfig(arch=args.arch, slots=args.slots,
                                   max_len=args.max_len))
    for p in prompts:
        server.submit(p, max_new=args.max_new)
    done = server.run()
    for r in done:
        print(f"{r.req_id}: {r.prompt} -> {r.generated}")
    print(f"{len(done)} requests in {server.steps} decode steps "
          f"(batched slots={args.slots})")


if __name__ == "__main__":
    main()
