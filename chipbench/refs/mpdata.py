"""Plain float32 reference for 2-D MPDATA on a periodic grid.

Nothing here imports the program.  One time step, for a field psi with
axis 0 as y (rows) and axis 1 as x (columns), constant Courant numbers
(cx, cy) and n_iters passes:

    upwind flux through a face:  F(l, r, C) = max(C, 0) l + min(C, 0) r
    one pass:  psi' = psi - (Fx_e - Fx_w) - (Fy_s - Fy_n)
               with Fx_e = F(psi, east(psi), Cx_e), Fx_w = Fx_e of the
               western neighbour, and likewise in y;
    corrective pass k >= 2 (Smolarkiewicz's antidiffusive velocity):
               Cx_e = (|cx| - cx^2) (east - psi) / (east + psi + eps)
               applied to the previous pass's result.

Neighbours come from ``jnp.roll``; the grid is periodic in both axes.
``dtype`` lets the control run the same arithmetic in bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _flux(left, right, c):
    return jnp.maximum(c, 0.0) * left + jnp.minimum(c, 0.0) * right


def _pass(psi, cx_e, cy_s):
    east = jnp.roll(psi, -1, axis=1)
    south = jnp.roll(psi, -1, axis=0)
    fx_e = _flux(psi, east, cx_e)
    fy_s = _flux(psi, south, cy_s)
    fx_w = jnp.roll(fx_e, 1, axis=1)
    fy_n = jnp.roll(fy_s, 1, axis=0)
    return psi - (fx_e - fx_w) - (fy_s - fy_n)


def step(psi, courant=(0.2, 0.2), n_iters=2, eps=1e-10):
    """One MPDATA time step of the whole periodic field."""
    cx, cy = (jnp.asarray(c, psi.dtype) for c in courant)
    out = _pass(psi, cx, cy)
    for _ in range(n_iters - 1):
        east = jnp.roll(out, -1, axis=1)
        south = jnp.roll(out, -1, axis=0)
        ax = (east - out) / (east + out + eps)
        ay = (south - out) / (south + out + eps)
        out = _pass(out, (jnp.abs(cx) - cx * cx) * ax,
                    (jnp.abs(cy) - cy * cy) * ay)
    return out


@functools.partial(jax.jit, static_argnames=("steps", "courant", "n_iters",
                                             "dtype"))
def advance(psi, *, steps, courant, n_iters, dtype="float32"):
    """``steps`` time steps in ``dtype``; the result in float32."""
    p = psi.astype(dtype)
    p = jax.lax.fori_loop(0, steps,
                          lambda i, f: step(f, courant, n_iters), p)
    return p.astype(jnp.float32)
