"""Elastic scaling: membership watch, state re-mesh, loss continuity.

The multi-device re-mesh runs in a subprocess with 8 forced host devices
(tests themselves must keep the default single device — see conftest)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.elastic import ElasticController, divisors_mesh
from tests.conftest import make_plane


def test_divisors_mesh():
    assert divisors_mesh(256) == (16, 16)
    assert divisors_mesh(12) == (4, 3)
    assert divisors_mesh(7) == (7, 1)


def test_controller_sees_join_and_leave():
    plane = make_plane(1)
    changes = []
    ElasticController(plane.overwatch, lambda m: changes.append(tuple(m)))
    plane.add_cluster("onprem-9")                      # join
    assert changes and "onprem-9" in changes[-1]
    plane.fabric.partition_cluster("onprem-9")         # leave (lease expiry)
    plane.tick(n=8)
    assert "onprem-9" not in changes[-1]
    assert "master" in changes[-1]


def test_trainer_continues_after_remesh_same_device():
    """Single-device 'remesh' (device_put round-trip) preserves training."""
    from repro.launch.mesh import make_test_mesh
    from repro.parallel.sharding import MeshPlan
    from repro.runtime.elastic import remesh_state
    from repro.runtime.train_loop import Trainer, TrainJobConfig
    tr = Trainer(TrainJobConfig(arch="qwen3-0.6b", steps=4, seq_len=8,
                                global_batch=2))
    tr.run(2)
    loss_before = tr.loss()
    new_plan = MeshPlan(mesh=make_test_mesh(), fsdp=False)
    from repro.models.params import partition_specs
    from repro.optim.adamw import opt_state_specs
    tr.state = remesh_state(
        tr.state, tr.plan, new_plan,
        lambda p: {"params": partition_specs(tr.arch_cfg, p),
                   "opt": opt_state_specs(tr.arch_cfg, p)})
    tr.run(2)
    assert tr.step == 4 and np.isfinite(tr.loss())


SUBPROCESS_REMESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import base as configs
    from repro.models.model import Model
    from repro.models.params import partition_specs
    from repro.parallel.sharding import MeshPlan
    from repro.runtime.elastic import remesh_state

    cfg = dataclasses.replace(configs.get("qwen3-0.6b").reduced(), remat="none")
    auto = (jax.sharding.AxisType.Auto,) * 2
    mesh8 = jax.make_mesh((4, 2), ("data", "model"), axis_types=auto)
    mesh4 = jax.make_mesh((2, 2), ("data", "model"), axis_types=auto,
                          devices=jax.devices()[:4])
    plan8, plan4 = MeshPlan(mesh=mesh8), MeshPlan(mesh=mesh4)
    model = Model(cfg, plan8)
    params = model.init_params(jax.random.PRNGKey(0))
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, jax.sharding.NamedSharding(mesh8, s)),
        params, partition_specs(cfg, plan8))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    logits8, _ = jax.jit(Model(cfg, plan8).forward)(sharded, batch)

    # pod shrink: 8 -> 4 devices
    moved = remesh_state(sharded, plan8, plan4,
                         lambda p: partition_specs(cfg, p))
    assert len({d for l in jax.tree_util.tree_leaves(moved)
                for d in l.devices()}) == 4
    logits4, _ = jax.jit(Model(cfg, plan4).forward)(moved, batch)
    np.testing.assert_allclose(np.asarray(logits8, np.float32),
                               np.asarray(logits4, np.float32),
                               rtol=2e-2, atol=2e-2)
    print("REMESH_OK")
""")


def test_remesh_shrink_preserves_function(tmp_path):
    script = tmp_path / "remesh.py"
    script.write_text(SUBPROCESS_REMESH)
    out = subprocess.run([sys.executable, str(script)],
                         cwd=str(Path(__file__).resolve().parents[1]),
                         capture_output=True, text=True, timeout=420)
    assert "REMESH_OK" in out.stdout, out.stderr[-2000:]
