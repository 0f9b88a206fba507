"""Record the small device trace that ``test_bench_trace_reduce.py`` reads.

Run on a machine with one TPU, from the root of the checkout:

    python bench/tests/data/record_trace.py [OUT_DIR]

It traces, under the benchmark's own host spans, three matrix products of a
jitted program, a 30 ms host pause between device calls, and one call of the
Pallas flash kernel, then copies the ``.xplane.pb`` to
``OUT_DIR/small.xplane.pb`` (default: this directory) and prints the planes,
lines and first events, so a reader can see how the device names its work.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    from repro.kernels import ops

    out_dir = Path(sys.argv[1]) if len(sys.argv) > 1 else Path(__file__).parent
    out_dir.mkdir(parents=True, exist_ok=True)
    matmul = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    flash = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True))
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1024, 8, 128), jnp.bfloat16)
    kv = jax.random.normal(jax.random.PRNGKey(1), (1, 1024, 1, 128), jnp.bfloat16)
    matmul(a, a).block_until_ready()
    flash(q, kv, kv).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    matmul(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_pause"):
                time.sleep(0.03)
            with jax.profiler.TraceAnnotation("bench.prefill"):
                flash(q, kv, kv).block_until_ready()
        jax.profiler.stop_trace()
        path = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
        shutil.copy(path, out_dir / "small.xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(out_dir / "small.xplane.pb"))
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), [(l.name, len(list(l.events)))
                                          for l in lines])
        for line in lines:
            for e in list(line.events)[:12]:
                stats = {k: str(v)[:60] for k, v in e.stats}
                print("   ", repr(line.name), "|", repr(e.name)[:100],
                      e.start_ns, e.duration_ns, stats)
    print("devices", jax.devices())
    return 0


if __name__ == "__main__":
    sys.exit(main())
