"""Plain float32 reference for a Llama-style decoder (Yi-6B's block), and
the weights that the benchmark makes for it and for the program.

Nothing here imports the program.  The weights are made by the benchmark
from the seed, in the layout the program's serving path takes (a dict of
stacked layers, documented in ``make_params``), and in float32, the type the
configuration serves them in.  The reference reads the same arrays.

The block, per layer, in float32 with ``precision="highest"``:

    h = rmsnorm(x) * attn_norm;  q, k, v = h Wq, h Wk, h Wv
    q, k = rope(q), rope(k)        (rotation of adjacent pairs (2i, 2i+1))
    x += softmax(q k^T / sqrt(d_head), causal) v Wo      (grouped KV heads)
    h = rmsnorm(x) * mlp_norm;  x += (silu(h Wgate) * h Win) Wout
    logits = (rmsnorm(x) * final_norm) Head^T

Published Llama code rotates the two halves of each head instead of
adjacent pairs; the two are the same model under a fixed permutation of
the q and k columns, which a checkpoint converter applies.  The reference
follows the pairing the program's weights are laid out for.

``quant="fp8"`` computes every matrix product from float8 (e4m3) operands,
each tensor scaled by its largest magnitude: the control, one precision
below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def make_params(m: dict, seed: int):
    """Weights from the seed, on the device, in one jitted call.

    Layout (L layers stacked on the leading axis, d = d_model,
    H/KH heads, D head size, F d_ff, V vocab):
    ``embed.table`` (V, d); ``head.table`` (V, d); ``final_norm.scale``
    (d,); ``main.attn_norm.scale`` (L, d); ``main.attn.wq`` (L, d, H, D),
    ``wk``/``wv`` (L, d, KH, D), ``wo`` (L, H, D, d);
    ``main.mlp_norm.scale`` (L, d); ``main.mlp.w_in``/``w_gate`` (L, d, F),
    ``w_out`` (L, F, d).
    """
    L, d, H, KH, D, F, V = (m["n_layers"], m["d_model"], m["n_heads"],
                            m["n_kv_heads"], m["head_dim"], m["d_ff"],
                            m["vocab_size"])

    def make(key):
        ks = iter(jax.random.split(key, 16))
        nrm = lambda shape, fan: (jax.random.normal(next(ks), shape,
                                                    jnp.float32)
                                  / np.sqrt(fan))
        scale = lambda shape: 1.0 + 0.1 * jax.random.normal(
            next(ks), shape, jnp.float32)
        return {
            "embed": {"table": jax.random.normal(next(ks), (V, d),
                                                 jnp.float32)},
            "head": {"table": nrm((V, d), d)},
            "final_norm": {"scale": scale((d,))},
            "main": {
                "attn_norm": {"scale": scale((L, d))},
                "attn": {"wq": nrm((L, d, H, D), d),
                         "wk": nrm((L, d, KH, D), d),
                         "wv": nrm((L, d, KH, D), d),
                         "wo": nrm((L, H, D, d), H * D)},
                "mlp_norm": {"scale": scale((L, d))},
                "mlp": {"w_in": nrm((L, d, F), d),
                        "w_gate": nrm((L, d, F), d),
                        "w_out": nrm((L, F, d), F)},
            },
        }

    return jax.jit(make)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _q8(a):
    """Round to float8 e4m3 with a per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, quant):
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs      # (S, 1, d/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def logits_at(params, tokens, rows, *, m, quant=None):
    """Logits (R, V) at positions ``rows`` of one causal sequence
    ``tokens`` (S,); positions past the real length may hold anything."""
    m = dict(m)
    eps, theta = m["norm_eps"], m["rope_theta"]
    KH, D = m["n_kv_heads"], m["head_dim"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    causal = pos[None, :] <= pos[:, None]                     # (S, T)
    for i in range(m["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["main"])
        h = _rms(x, lp["attn_norm"]["scale"], eps)
        q = _rope(_mm("sd,dhk->shk", h, lp["attn"]["wq"], quant), pos, theta)
        k = _rope(_mm("sd,dhk->shk", h, lp["attn"]["wk"], quant), pos, theta)
        v = _mm("sd,dhk->shk", h, lp["attn"]["wv"], quant)
        qg = q.reshape(S, KH, -1, D)
        s = _mm("skgd,tkd->kgst", qg, k, quant) / np.sqrt(D)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = _mm("kgst,tkd->skgd", w, v, quant).reshape(S, -1, D)
        x = x + _mm("shk,hkd->sd", o, lp["attn"]["wo"], quant)
        h = _rms(x, lp["mlp_norm"]["scale"], eps)
        g = _mm("sd,df->sf", h, lp["mlp"]["w_gate"], quant)
        u = _mm("sd,df->sf", h, lp["mlp"]["w_in"], quant)
        x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, lp["mlp"]["w_out"],
                    quant)
    hx = _rms(jnp.take(x, rows, axis=0), params["final_norm"]["scale"], eps)
    return _mm("rd,vd->rv", hx, params["head"]["table"], quant)


def _bucket(n: int, step: int) -> int:
    return -(-n // step) * step


def served_gaps(params, m: dict, seqs, *, bucket: int = 512,
                quant=None) -> list:
    """Per request, the gaps by which each served token's reference logit
    lies below the reference's best at its position.

    ``seqs`` is [(prompt, served)] of int arrays.  With ``quant`` set, the
    tokens judged are not the served ones but those the quantized forward
    puts first at each position, still measured on the float32 logits.
    """
    mt = tuple(sorted(m.items()))
    out = []
    for prompt, served in seqs:
        prompt, served = np.asarray(prompt), np.asarray(served)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n_rows = len(served)
        toks = np.zeros(_bucket(len(seq), bucket), np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(_bucket(n_rows, bucket), np.int32)
        rows[:n_rows] = len(prompt) - 1 + np.arange(n_rows)
        ref = np.asarray(logits_at(params, toks, rows, m=mt))[:n_rows]
        judged = served
        if quant is not None:
            low = np.asarray(logits_at(params, toks, rows, m=mt,
                                       quant=quant))[:n_rows]
            judged = low.argmax(axis=1)
        best = ref.max(axis=1)
        out.append(best - ref[np.arange(n_rows), judged])
    return out
