"""Mesh construction for the production fleet and for CPU tests.

Everything is a FUNCTION (never module-level mesh state) so importing this module
never touches jax device state — required because the dry-run forces 512 host
devices via XLA_FLAGS while tests and benches must see the real single device.

Topology (TPU v5e target):
  * single pod  : (data=16, model=16) = 256 chips, all axes on ICI.
  * multi pod   : (pod=2, data=16, model=16) = 512 chips; the "pod" axis is DCN —
    the thin boundary of the paper. Sharding rules (repro.parallel.sharding) keep
    every per-layer collective off the pod axis; only batch parallelism (gradient
    reduction / Titchener local-sync deltas) crosses it.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

# hardware constants (TPU v5e) used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link (in-pod)
DCN_BW = 6.25e9                # bytes/s per host pair (cross-pod, ~50 Gbit)
CHIPS_PER_POD = 256


def _auto_mesh(shape, axes, devices=None) -> Mesh:
    # Auto axes: the model code places tensors with with_sharding_constraint,
    # which rejects the Explicit axes jax.make_mesh defaults to
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape: Optional[Tuple[int, ...]] = None,
                   axes: Optional[Tuple[str, ...]] = None) -> Mesh:
    """The Trainer/Server default mesh. Without ``shape`` it is (data=1,
    model=1) on ``jax.devices()[0]``: one chip, however many the host holds —
    more devices are used only when a caller asks for that shape."""
    if shape is None:
        shape, axes = (1, 1), ("data", "model")
    return _auto_mesh(shape, axes, jax.devices()[:math.prod(shape)])


def mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def n_pods(mesh: Mesh) -> int:
    return mesh.shape.get("pod", 1)


def chips(mesh: Mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
