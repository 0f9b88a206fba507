"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

Per (batch, head) the sequence is split into chunks of Q tokens. Within a chunk the
"dual" quadratic form runs on the MXU (a [Q, Q] decay-masked score matmul); across
chunks a [N, P] state recurrence is carried in VMEM scratch — the innermost grid dim
(chunk index) is sequential on TPU, so the scratch state plays the role of the
recurrent carry with zero HBM round-trips.

Inputs (single B/C group, as mamba2 uses G=1), head-major so that every block's
last two dims are tiling-aligned or whole array dims:
  x  [B, H, S, P]   token inputs per head
  dt [B, H, 1, S]   softplus-activated timestep (>0), one row per head
  A  [H]            negative decay rate per head (A < 0), read from SMEM
  Bm [B, S, N]      input projection onto state
  Cm [B, S, N]      state readout
Output: y [B, H, S, P]. Per-token quantities live in (1, Q) rows; the one column
the chunk needs (its cumulative decay) comes from a [Q, Q] broadcast transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)               # [Q, P]
    dt = dt_ref[...].astype(jnp.float32)             # [1, Q]
    a = a_ref[pl.program_id(1)]                      # scalar (this head)
    bm = b_ref[...].astype(jnp.float32)              # [Q, N]
    cm = c_ref[...].astype(jnp.float32)              # [Q, N]

    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_i >= iota_j
    # inclusive cumsum of dt*a along the chunk as a row: cum[j] = sum_{k<=j}
    upper = jnp.where(iota_i <= iota_j, 1.0, 0.0)
    cum = jax.lax.dot_general(dt * a, upper, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST)    # [1, Q]
    cum_rows = jnp.broadcast_to(cum, (chunk, chunk))          # [i, j] = cum[j]
    cum_cols = cum_rows.T                                     # [i, j] = cum[i]
    seg_total = cum_cols[chunk - 1:, :]                       # [1, Q] = cum[-1]
    P = x.shape[1]

    # intra-chunk dual form: L[i, j] = exp(cum[i] - cum[j]) for i >= j
    decay = jnp.where(causal, jnp.exp(cum_cols - cum_rows), 0.0)      # [Q, Q]
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))    # [Q, Q]
    y_intra = jnp.dot(scores * decay * dt, x)                         # [Q, P]

    # inter-chunk: contribution of the carried state. Columns of cum_cols are
    # all equal, so slicing P of them gives exp(cum[i]) per row without a
    # scalar broadcast, which Mosaic cannot do into both sublanes and lanes.
    state = state_ref[...]                                            # [N, P]
    y_inter = jnp.exp(cum_cols[:, :P]) * jnp.dot(cm, state)           # [Q, P]

    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = exp(sum dta) h + sum_j exp(cum[-1]-cum[j]) dt_j B_j x_j^T
    w = jnp.exp(seg_total - cum) * dt                                 # [1, Q]
    state_decay = jnp.exp(seg_total[:, :P])                           # [1, P]
    state_ref[...] = state_decay * state + jnp.dot(bm.T * w, x)


def ssd_scan_pallas(x, dt, a, bm, cm, *, chunk: int = 256, interpret: bool = False):
    """See module docstring. S must be divisible by ``chunk`` (ops.py pads) and
    the head dim P may not exceed ``chunk``."""
    B, H, S, P = x.shape
    N = bm.shape[-1]
    assert S % chunk == 0 and P <= chunk, (S, P, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    seq_spec = pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            seq_spec,
            seq_spec,
        ],
        out_specs=pl.BlockSpec((None, None, chunk, P),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, bm, cm)
