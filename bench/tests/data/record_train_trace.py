"""Record the small device trace that ``test_bench_span_reduce.py`` reads.

Run on a machine with one TPU, from the root of the checkout:

    python bench/tests/data/record_train_trace.py [OUT_DIR]

A reduced ``Trainer`` (the registry's reduced qwen3-0.6b, 2 rows of 64
tokens) takes two steps to compile, then three more inside the benchmark's
window span ``bench.traced``, each inside ``bench.train.step_once`` as the
train driver calls it. The profiler records neither Python calls nor the
programs' HLO, and the plane of program descriptions is dropped, which
keeps the file near half a megabyte; the spans and the device's ops are as
the benchmark's traced runs hold them. The trace goes to
``OUT_DIR/train_spans.xplane.pb`` (default: this directory); the script
prints the program's ``repro.*`` spans and their reduction.
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


# a plane of program descriptions that the reductions do not read: most of
# the file's bytes (0.91 MB of 1.41 MB when first recorded)
DROP = ("/host:metadata",)


def drop_planes(path: Path) -> None:
    """Rewrite the trace without the planes named in ``DROP``. TensorFlow,
    installed beside JAX, holds the trace's protocol buffer classes."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    space.ParseFromString(path.read_bytes())
    keep = [p for p in space.planes if p.name not in DROP]
    del space.planes[:]
    space.planes.extend(keep)
    path.write_bytes(space.SerializeToString())


def main() -> int:
    import jax
    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData, TraceAnnotation
    from bench import span_reduce
    from repro.runtime.train_loop import Trainer, TrainJobConfig

    out_dir = Path(sys.argv[1]) if len(sys.argv) > 1 else Path(__file__).parent
    out_dir.mkdir(parents=True, exist_ok=True)
    out = out_dir / "train_spans.xplane.pb"
    tr = Trainer(TrainJobConfig(arch="qwen3-0.6b", reduced=True, seq_len=64,
                                global_batch=2, seed=0))
    tr.run(2)
    tmp = tempfile.mkdtemp()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with TraceAnnotation("bench.traced"):
            for _ in range(3):
                with TraceAnnotation("bench.train.step_once"):
                    tr.step_once()
        jax.profiler.stop_trace()
        path = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
        shutil.copy(path, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {out.stat().st_size} bytes as recorded")
    drop_planes(out)
    print(f"{out}: {out.stat().st_size} bytes without {DROP}")
    for plane in ProfileData.from_file(str(out)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("repro.", "bench.")):
                    print("   ", plane.name, "|", line.name, "|", e.name,
                          e.start_ns, e.duration_ns, dict(e.stats))
    summary = span_reduce.reduce(str(out), "repro.train.")
    print(span_reduce.describe(summary, "repro.train."))
    print(json.dumps(summary, indent=1))
    print("host_transfers", tr.spans.host_transfers,
          "programs loaded by (span, step)", dict(tr.spans.compiles))
    return 0


if __name__ == "__main__":
    sys.exit(main())
