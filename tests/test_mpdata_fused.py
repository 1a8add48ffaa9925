"""The fused MPDATA step (one Pallas kernel per time step) on one periodic
block, in interpret mode on the CPU, against the single-device oracle and
the jnp step; and which path the solver takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compat
from repro.pde import mpdata


def _field(n, m, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(1.0 + rng.random((n, m)), jnp.float32)


@pytest.fixture
def tile_rows(monkeypatch):
    """Tiles of fewer rows than the block, so that a step spans several
    grid steps and each reads its neighbours' rows."""
    def use(rows):
        monkeypatch.setattr(mpdata, "TILE_ROWS", rows)
    return use


def _fused(psi, steps=1, courant=(0.2, 0.2)):
    run = jax.jit(lambda p: jax.lax.fori_loop(
        0, steps, lambda i, q: mpdata.fused_step(
            q, courant=courant, interpret=True), p))
    return run(psi)


@pytest.mark.parametrize("n,m,rows", [(64, 256, 16), (128, 384, 32),
                                      (48, 128, 16), (32, 1024, 32)])
def test_fused_step_matches_reference_and_jnp_step(tile_rows, n, m, rows):
    """Several row tiles and lane chunks: the kernel's step equals the
    oracle's and the jnp path's (make_solver on a one-device CPU mesh)."""
    tile_rows(rows)
    psi = _field(n, m)
    got = _fused(psi)
    want = mpdata.reference_step(psi)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=0)
    jnp_step = mpdata.make_solver(compat.make_mesh((1, 1), ("x", "y")),
                                  inner_steps=1)(psi)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp_step),
                               atol=1e-6, rtol=0)


@pytest.mark.parametrize("courant", [(0.2, 0.2), (-0.3, 0.1)])
def test_fused_steps_match_reference_over_time(tile_rows, courant):
    """Ten steps, also with a negative (westward) Courant number, so both
    branches of every donor-cell flux carry weight."""
    tile_rows(16)
    psi = _field(64, 256, seed=1)
    got = _fused(psi, steps=10, courant=courant)
    want = psi
    for _ in range(10):
        want = mpdata.reference_step(want, courant=courant)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)


def test_fused_step_conserves_mass_and_positivity(tile_rows):
    tile_rows(32)
    rng = np.random.default_rng(2)
    psi = jnp.asarray(np.abs(rng.standard_normal((64, 256))) + 0.1,
                      jnp.float32)
    out = _fused(psi, steps=25)
    np.testing.assert_allclose(float(jnp.sum(out)), float(jnp.sum(psi)),
                               rtol=1e-5)
    assert float(out.min()) >= 0.0


def test_path_stats_count_each_path(tile_rows):
    tile_rows(16)
    mpdata.path_stats_clear()
    _fused(_field(64, 256))
    _fused(_field(128, 384))
    assert mpdata.path_stats() == {"fused": 2, "jnp": 0}
    # a CPU mesh, and a 32^2 block, take the jnp path
    mpdata.make_solver(compat.make_mesh((1, 1), ("x", "y")),
                       inner_steps=3)(_field(32, 32))
    assert mpdata.path_stats() == {"fused": 2, "jnp": 1}
    mpdata.path_stats_clear()
    assert mpdata.path_stats() == {"fused": 0, "jnp": 0}


@pytest.mark.parametrize("shape,rows", [((16384, 16384), 128),
                                        ((8192, 8192), 128), ((48, 128), 48),
                                        ((96, 65536), 32), ((32, 32), 0),
                                        ((40, 256), 0), ((64, 200), 0)])
def test_tile_rows_follow_the_block(shape, rows):
    """T divides the rows, is a multiple of 16 and keeps one tile within
    the VMEM cap; blocks off the (16, 128) granules take the jnp path."""
    assert mpdata._tile_rows(shape) == rows


def test_fused_step_refuses_blocks_that_do_not_tile():
    with pytest.raises(ValueError, match="cannot tile"):
        mpdata.fused_step(_field(40, 256), interpret=True)
