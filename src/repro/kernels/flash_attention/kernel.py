"""Flash attention (forward) Pallas TPU kernel: causal, GQA, sliding window.

Tiling: grid = (B·H, n_q_blocks, n_kv_blocks); the kv axis is the innermost
(sequential) grid dim so the online-softmax state lives in VMEM scratch
across kv steps.  Per grid step the VMEM working set is

    q (bq·D) + k,v (2·bk·D) + acc (bq·D fp32) + m,l (2·bq·MINLANE fp32)

≈ (512·128·2 + 2·512·128·2 + 512·128·4 + 2·512·128·4)B ≈ 0.9 MiB at the
default bq=bk=512, D=128 — comfortably under the ~16 MiB v5e VMEM, leaving
headroom for double buffering.  Block shapes keep the MXU-aligned 128 lane
dim; bq/bk are multiples of 8 (sublane).  GQA is handled in the index maps
(kv head = q head // group) — K/V are never physically expanded.

Fully-masked kv blocks (beyond the causal diagonal or left of the sliding
window) are skipped with pl.when: the MXU does no work for them, matching
the causal-optimal FLOP count of the XLA twin
(repro.models.attention.blockwise_sdpa).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
MINLANE = 128  # lane-aligned second dim for the m/l scratch


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, bq, bk, n_kv, seq_len, window, diag_offset):
    """One (bh, qi, ki) grid step."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # query rows qi*bq..+bq attend keys <= row + diag_offset (and window)
    q_lo = qi * bq + diag_offset
    q_hi = q_lo + bq - 1
    k_lo = ki * bk
    in_causal = k_lo <= q_hi
    in_window = True if window is None else (ki + 1) * bk - 1 >= q_lo - window + 1

    @pl.when(jnp.logical_and(in_causal, in_window))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (kpos <= qpos) & (kpos < seq_len)
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                              # (bq,)
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


def flash_attention_bhsd(q, k, v, *, n_kv_heads, window=None, scale=None,
                         bq=512, bk=512, interpret=False):
    """q: (B,H,S,D); k/v: (B,KH,T,D). Returns (B,H,S,D).

    Causal with diagonal offset T−S (so S<T suffix-decode works).
    """
    b, h, s, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    g = h // n_kv_heads
    bq = min(bq, s)
    bk = min(bk, t)
    nq, nk = -(-s // bq), -(-t // bk)
    if s % bq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - s), (0, 0)))
    if t % bk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - t), (0, 0)))
    qf = q.reshape(b * h, nq * bq, d)
    kf = k.reshape(b * kh, nk * bk, d)
    vf = v.reshape(b * kh, nk * bk, d)
    sc = scale if scale is not None else 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _kernel, scale=sc, bq=bq, bk=bk, n_kv=nk, seq_len=t,
        window=window, diag_offset=t - s)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, qi, ki, g=g, kh=kh: (
                             (bh // (g * kh)) * kh + (bh % (g * kh)) // g,
                             ki, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, qi, ki, g=g, kh=kh: (
                             (bh // (g * kh)) * kh + (bh % (g * kh)) // g,
                             ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nq * bq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, MINLANE), jnp.float32),
            pltpu.VMEM((bq, MINLANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, nq * bq, d)[:, :, :s]
