"""Mamba2 SSD (state-space dual) chunked-scan Pallas kernel.

The SSD form turns the selective-scan recurrence into per-chunk matmuls
(MXU work) plus an O(n_chunks) state carry.  Grid = (B, H, n_chunks) with
the chunk axis innermost & sequential: the (P, N) state lives in VMEM
scratch across chunk steps — the inter-chunk recurrence never touches HBM.

Per grid step the VMEM working set at L=chunk=128, P=64, N=128:
x (L·P) + B,C (2·L·N) + dt (L) + masks (L·L) + state (P·N fp32)
≈ (128·64 + 2·128·128 + 128·128)·4B + 64·128·4B ≈ 0.4 MiB — small; the
kernel is compute-dense (three L×L / L×N / L×P matmuls per chunk).

Numerics follow repro.models.ssm.ssd_chunked exactly (fp32 segment sums,
exp-of-negative decays), so kernel↔model↔oracle agree to float tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, hout_ref,
            state_ref, *, chunk, n_chunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    L = chunk
    x = x_ref[0, 0].astype(jnp.float32)                   # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                 # (L,) as (L,1)? ->
    dt = dt.reshape(L)
    Bm = b_ref[0].astype(jnp.float32)                     # (L, N)
    Cm = c_ref[0].astype(jnp.float32)                     # (L, N)
    A = a_ref[0, 0]                                       # scalar (negative)
    D = d_ref[0, 0]

    dA = dt * A                                           # (L,)
    seg = jnp.cumsum(dA)                                  # (L,)
    rel = seg[:, None] - seg[None, :]                     # (L, L)
    tril = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    M = jnp.where(tril, jnp.exp(rel), 0.0)
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L,L)
    W = G * M * dt[None, :]
    y_intra = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # carried-state contribution: exp(seg_t) · C_t · h_prev^T  -> (L, P)
    ch = jax.lax.dot_general(Cm, state_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, P)
    y = y_intra + jnp.exp(seg)[:, None] * ch + D * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h = exp(seg_L)·h + Σ_u exp(seg_L − seg_u)·dt_u·x_u⊗B_u
    segL = seg[L - 1]
    wk = jnp.exp(segL - seg) * dt                         # (L,)
    xw = x * wk[:, None]                                  # (L, P)
    upd = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = state_ref[...] * jnp.exp(segL) + upd

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hout_ref[0, 0] = state_ref[...]


def mamba2_ssd_bhlp(x, dt, B, C, A, D, *, chunk=128, interpret=False):
    """x: (b,H,S,P); dt: (b,H,S); B,C: (b,S,N); A,D: (H,).

    Returns (y (b,H,S,P), h_final (b,H,P,N)). fp32 state math.
    """
    b, H, s, P = x.shape
    N = B.shape[-1]
    L = min(chunk, s)
    nc = -(-s // L)
    if s % L:
        pad = nc * L - s
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(_kernel, chunk=L, n_chunks=nc)
    y, hout = pl.pallas_call(
        kernel,
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda bi, h, c: (bi, h, c, 0)),
            pl.BlockSpec((1, 1, L), lambda bi, h, c: (bi, h, c)),
            pl.BlockSpec((1, L, N), lambda bi, h, c: (bi, c, 0)),
            pl.BlockSpec((1, L, N), lambda bi, h, c: (bi, c, 0)),
            pl.BlockSpec((1, 1), lambda bi, h, c: (h, 0)),
            pl.BlockSpec((1, 1), lambda bi, h, c: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda bi, h, c: (bi, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, c: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, nc * L, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, B, C, A.reshape(H, 1), D.reshape(H, 1))
    return y[:, :, :s], hout
