"""Each cell's control fails the cell's limit, at a size a test run holds.

The control is the plain reference put in the program's place one
precision below the configuration's: bfloat16 for the float32 MPDATA
field, float8 (e4m3, per-tensor scale) matrix products for the bfloat16
model.  On the chip, ``calibrate.py readings`` reads it at each cell's own
size (PERF.md gives those readings); here it runs on the CPU at a small
size, and still has to come out above the limit that the chip's readings
set.
"""

import json
import os

import numpy as np
import pytest

from chipbench.refs import llama, mpdata

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def limit(cell: str, name: str) -> float:
    with open(os.path.join(HERE, "workloads", cell + ".json")) as f:
        return float(json.load(f)["limits"][name])


@pytest.mark.parametrize("cell", ["mpdata.16k.1chip", "mpdata.16k.2x2"])
def test_mpdata_bf16_control_fails(cell):
    import jax
    psi = 1.0 + jax.random.uniform(llama.seed_key(3), (256, 256))
    kw = dict(steps=10, courant=(0.2, 0.2), n_iters=2)
    want = mpdata.advance(psi, **kw)
    low = mpdata.advance(psi, dtype="bfloat16", **kw)
    gap = float(np.abs(np.asarray(low) - np.asarray(want)).max())
    assert gap > limit(cell, "max_abs_diff")


@pytest.mark.parametrize("cell", ["yi6b.chat", "yi6b.docqa"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_llama_fp8_control_fails(cell, seed):
    m = dict(n_layers=2, d_model=256, n_heads=8, n_kv_heads=2, head_dim=32,
             d_ff=512, vocab_size=2048, rope_theta=5e6, norm_eps=1e-5)
    params = llama.make_params(m, seed)
    rng = np.random.default_rng(seed)
    seqs = [(rng.integers(0, m["vocab_size"], 100),
             rng.integers(0, m["vocab_size"], 40)) for _ in range(4)]
    gaps = llama.served_gaps(params, m, seqs, bucket=64, quant="fp8")
    assert max(g.max() for g in gaps) > limit(cell, "max_logit_gap")
