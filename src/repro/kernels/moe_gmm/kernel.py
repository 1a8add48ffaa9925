"""Grouped (per-expert) matmul Pallas kernel — the MoE compute hot spot.

After capacity dispatch the expert computation is E independent matmuls
y[e] = x[e] @ w[e], x: (E, C, D), w: (E, D, F).  A plain XLA batched dot
treats E as a batch dim and tiles (C, F) generically; the kernel instead
makes the expert dim the outermost (parallel) grid axis so one expert's
weight panel streams through VMEM exactly once per (C-tile row), with
MXU-aligned (bc × D)·(D × bf) dots.

VMEM per grid step at bc = bf = 128, D = 7168 (deepseek experts):
x (128·7168·2B) + w (7168·128·2B) + y (128·128·4B) ≈ 3.7 MiB — double-
bufferable in the ~16 MiB v5e VMEM.  A capacity mask zeroes the padded
rows so dropped-token slots never contribute garbage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, nvalid_ref, y_ref, *, bc):
    ci = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)                     # (bc, D)
    w = w_ref[0].astype(jnp.float32)                     # (D, bf)
    y = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    rows = ci * bc + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    valid = rows < nvalid_ref[0, 0]
    y_ref[0] = jnp.where(valid, y, 0.0).astype(y_ref.dtype)


def moe_gmm_ecd(x, w, n_valid=None, *, bc=128, bf=128, interpret=False):
    """x: (E, C, D); w: (E, D, F); n_valid: (E,) valid rows per expert
    (None = all).  Returns (E, C, F) with invalid rows zeroed."""
    e, c, d = x.shape
    f = w.shape[-1]
    bc = min(bc, c)
    bf = min(bf, f)
    nc, nf = -(-c // bc), -(-f // bf)
    if c % bc:
        x = jnp.pad(x, ((0, 0), (0, nc * bc - c), (0, 0)))
    if f % bf:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, nf * bf - f)))
    nv = (jnp.full((e,), c, jnp.int32) if n_valid is None
          else n_valid.astype(jnp.int32)).reshape(e, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, bc=bc),
        grid=(e, nc, nf),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda ei, ci, fi: (ei, ci, 0)),
            pl.BlockSpec((1, d, bf), lambda ei, ci, fi: (ei, 0, fi)),
            pl.BlockSpec((1, 1), lambda ei, ci, fi: (ei, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ei, ci, fi: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, nc * bc, nf * bf), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(x, w, nv)
    return out[:, :c, :f]
