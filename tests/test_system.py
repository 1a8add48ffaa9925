"""End-to-end behaviour tests: the trainer learns, the engine serves, the
dry-run machinery lowers/compiles, and the HLO cost model is calibrated."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny
from repro.configs.base import RunConfig, ShapeCell
from repro.launch.mesh import make_host_mesh
from repro.models import lm as lm_lib
from repro.serve.engine import Engine, ServeConfig
from repro.train import optim
from repro.train.data import SyntheticLM
from repro.train.trainer import build_train_step


def test_trainer_learns_synthetic_bigrams():
    """30 steps on the synthetic bigram stream must cut the loss clearly
    below ln(vocab) (the data is ~86% deterministic next-token)."""
    cfg = get_tiny("yi-6b")
    cfg.dtype = "float32"
    mesh = make_host_mesh(1, axes=("data",))
    cell = ShapeCell("t", 64, 8, "train")
    rc = RunConfig(learning_rate=3e-3)
    step = build_train_step(cfg, rc, mesh, cell).jitted()
    params = lm_lib.init_params(cfg, jax.random.PRNGKey(0))
    opt = optim.init(params, rc)
    data = SyntheticLM(cfg, 8, 64)
    losses = []
    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses[::6]


def test_engine_generates_batched():
    cfg = get_tiny("h2o-danube-3-4b")   # exercises the SWA ring cache
    params = lm_lib.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(max_prompt=16, max_new_tokens=8))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 12), dtype=np.int32)
    out = eng.generate(prompts)
    assert out.shape == (4, 8)
    assert (out >= 0).all() and (out < cfg.vocab_size).all()


def test_microbatched_step_matches_plain():
    """Gradient accumulation (k=4) must match the single-shot step."""
    cfg = get_tiny("qwen2-1.5b")
    cfg.dtype = "float32"
    mesh = make_host_mesh(1, axes=("data",))
    cell = ShapeCell("t", 32, 8, "train")
    params = lm_lib.init_params(cfg, jax.random.PRNGKey(0))
    data = SyntheticLM(cfg, 8, 32)
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

    outs = []
    for k in (0, 4):
        rc = RunConfig(learning_rate=1e-3, microbatch=k)
        step = build_train_step(cfg, rc, mesh, cell).jitted()
        p0 = jax.tree.map(jnp.copy, params)   # step donates its inputs
        opt = optim.init(p0, rc)
        p, o, m = step(p0, opt, batch)
        outs.append((p, float(m["loss"])))
    for a, b in zip(jax.tree.leaves(outs[0][0]), jax.tree.leaves(outs[1][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-4)


def test_dryrun_machinery_tiny():
    """lower+compile+analyze a tiny cell on the host mesh (the same path
    the 512-device dry-run runs; device count is the only difference)."""
    from repro.launch import hlo_cost
    from repro.launch.roofline import model_flops_for, roofline_terms

    cfg = get_tiny("yi-6b")
    mesh = make_host_mesh(1, axes=("data",))
    cell = ShapeCell("t", 64, 4, "train")
    bundle = build_train_step(cfg, RunConfig(microbatch=2), mesh, cell)
    compiled = bundle.lower().compile()
    cost = hlo_cost.analyze(compiled.as_text())
    assert cost["flops"] > 0 and cost["bytes"] > 0
    terms = roofline_terms(
        cost, {"total_bytes": cost["collective_bytes"]}, 1,
        model_flops=model_flops_for(cfg, cell))
    assert terms["dominant"] in ("compute", "memory", "collective")
    # analyzer flops within 3x of 6ND (remat + attention overhead band)
    assert 0.5 < cost["flops"] / terms["model_flops"] < 3.0


def test_hlo_cost_trip_counts():
    """The analyzer multiplies while-loop bodies by their trip counts."""
    from repro.launch import hlo_cost

    def g(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, ws)[0]

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((17, 128, 128), jnp.float32)
    hlo = jax.jit(g).lower(x, ws).compile().as_text()
    r = hlo_cost.analyze(hlo)
    expected = 17 * 2 * 64 * 128 * 128
    assert 0.95 < r["flops"] / expected < 1.1


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_location(env_set, monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper leaves JAX's own
    setting alone; unset, it points the cache at <repo>/.jax_cache."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.compile_cache import use_compile_cache

    repo_cache = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = use_compile_cache()
        if env_set:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(repo_cache)
            assert jax.config.jax_compilation_cache_dir == str(repo_cache)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
