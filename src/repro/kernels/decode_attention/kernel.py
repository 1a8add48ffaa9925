"""Single-token decode attention Pallas kernel (memory-bound regime).

Decode attends one query token per sequence against a long KV cache: the
working set is the cache itself, so the kernel's job is to stream K/V
through VMEM exactly once at full HBM bandwidth while the online-softmax
state stays resident.

Tiling: grid = (B·KH, n_kv_blocks); one q block holds the G = H/KH query
heads of one kv group (rows ≤ 8 sublanes for small G — padded by Mosaic),
K/V blocks are (bk, D) slabs; slot-validity (ring caches, partially filled
caches) arrives as a precomputed int32 mask — one row shared across the
batch, or one row per sequence for paged/continuous batching — so the
kernel needs no scalar prefetch.  The mask is carried as (rows, 1, S)
with (1, 1, bk) blocks: the last two block dims then equal the array's
(1) or are a multiple of 128 (bk), as Mosaic's tiling rule requires, and
the kernel only ever sees a 2-D (1, bk) row, never a 1-D vector.

VMEM per step ≈ bk·D·2·2B + G·D·4B ≈ 0.27 MiB at bk=1024, D=128 —
double-buffering the K/V stream dominates, as it should for a
bandwidth-bound kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
MINLANE = 128


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, n_kv):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                      # (G, d)
    k = k_ref[0].astype(jnp.float32)                      # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = mask_ref[0] > 0                               # (1, bk)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * corr + p.sum(axis=1)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def decode_attention_grouped(q, k, v, valid_mask, *, scale=None, bk=1024,
                             interpret=False):
    """q: (B,KH,G,D) one token per sequence; k/v: (B,KH,S,D);
    valid_mask: (S,) bool/int — which cache slots may be attended — or
    (B,S) with one validity row per sequence (paged/continuous batching,
    where slots advance at per-sequence positions).
    Returns (B,KH,G,D)."""
    b, kh, g, d = q.shape
    s = k.shape[2]
    bk = min(bk, s)
    nk = -(-s // bk)
    per_seq = valid_mask.ndim == 2
    if s % bk:
        pad = nk * bk - s
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        valid_mask = jnp.pad(valid_mask.astype(jnp.int32),
                             ((0, 0), (0, pad)) if per_seq else (0, pad))
    qf = q.reshape(b * kh, g, d)
    kf = k.reshape(b * kh, nk * bk, d)
    vf = v.reshape(b * kh, nk * bk, d)
    maskf = valid_mask.astype(jnp.int32).reshape(b if per_seq else 1, 1,
                                                 nk * bk)
    # grid axis 0 is b*kh with kh minor, so sequence = bh // kh
    mask_idx = ((lambda bh, ki: (bh // kh, 0, ki)) if per_seq
                else (lambda bh, ki: (0, 0, ki)))
    sc = scale if scale is not None else 1.0 / (d ** 0.5)

    kernel = functools.partial(_kernel, scale=sc, n_kv=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b * kh, nk),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, 1, bk), mask_idx),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kh, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, MINLANE), jnp.float32),
            pltpu.VMEM((g, MINLANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, maskf)
    return out.reshape(b, kh, g, d)
