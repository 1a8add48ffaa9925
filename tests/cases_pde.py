"""Multi-rank PDE cases (paper §3): halo exchange correctness, distributed
Cahn–Hilliard vs single-device oracle, MPDATA vs oracle across decomposition
layouts + conservation/positivity properties.  Run under 8 emulated devices.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.core as jmpi
from repro.core import compat
from repro.pde import cahn_hilliard as ch
from repro.pde import mpdata
from repro.pde.stencil import halo_exchange_2d, halo_strips_2d


def mesh2d(rows, cols, axes=("px", "py")):
    return compat.make_mesh((rows, cols), axes)


def case_halo_exchange_matches_roll():
    """Halo-padded blocks must reproduce the globally-rolled array."""
    for rows, cols in ((2, 4), (4, 2), (1, 8), (8, 1)):
        mesh = mesh2d(rows, cols)
        n = 16
        x = jnp.arange(n * n, dtype=jnp.float32).reshape(n, n)

        @jmpi.spmd(mesh, in_specs=P("px", "py"), out_specs=P("px", "py"))
        def f(blk):
            world = jmpi.world()
            cart = world.cart_create((rows, cols), periods=(True, True))
            h = halo_exchange_2d(blk, cart, halo=1)
            # interior of padded block must equal block; check neighbours by
            # reconstructing the shifted field
            up = h[0:blk.shape[0], 1:1 + blk.shape[1]]
            return up  # block shifted down by one row (periodic)

        got = f(x)
        want = jnp.roll(x, 1, axis=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   err_msg=f"decomp {rows}x{cols}")


def case_halo_strips_match_padded_block():
    """halo_exchange_2d is its strips concatenated, and bitwise the block of
    the periodically padded global field, at widths 1 and 2, under every
    layout (size-1 dims wrap locally)."""
    n = 32
    x = np.arange(n * n, dtype=np.float32).reshape(n, n) * 0.37 - 11.0
    for rows, cols in ((2, 2), (1, 4), (4, 1), (2, 4)):
        for h in (1, 2):
            mesh = mesh2d(rows, cols)

            @jmpi.spmd(mesh, in_specs=P("px", "py"),
                       out_specs=(P("px", "py"),) * 5)
            def f(blk):
                cart = jmpi.world().cart_create((rows, cols),
                                                periods=(True, True))
                top, bot, left, right = halo_strips_2d(blk, cart, halo=h)
                padded = halo_exchange_2d(blk, cart, halo=h)
                # strips as (1, ...) blocks so the global result keeps
                # every rank's own
                return padded, top, bot, left, right

            padded, top, bot, left, right = (np.asarray(a) for a in f(x))
            bn, bm = n // rows, n // cols
            full = np.pad(x, h, mode="wrap")
            for r in range(rows):
                for c in range(cols):
                    want = full[r * bn:r * bn + bn + 2 * h,
                                c * bm:c * bm + bm + 2 * h]
                    pr = slice(r * (bn + 2 * h), (r + 1) * (bn + 2 * h))
                    pc = slice(c * (bm + 2 * h), (c + 1) * (bm + 2 * h))
                    got = padded[pr, pc]
                    tag = f"decomp {rows}x{cols} halo {h} block {r},{c}"
                    np.testing.assert_array_equal(got, want, err_msg=tag)
                    rs = slice(r * h, (r + 1) * h)
                    cs = slice(c * bm, (c + 1) * bm)
                    np.testing.assert_array_equal(top[rs, cs], want[:h, h:-h])
                    np.testing.assert_array_equal(bot[rs, cs], want[-h:, h:-h])
                    rs = slice(r * (bn + 2 * h), (r + 1) * (bn + 2 * h))
                    cs = slice(c * h, (c + 1) * h)
                    np.testing.assert_array_equal(left[rs, cs], want[:, :h])
                    np.testing.assert_array_equal(right[rs, cs], want[:, -h:])


def case_cahn_hilliard_matches_oracle():
    rng = np.random.default_rng(0)
    n = 32
    c0 = jnp.asarray(0.5 + 0.01 * rng.standard_normal((n, n)), jnp.float32)
    for rows, cols in ((2, 4), (1, 8)):
        mesh = mesh2d(rows, cols)
        run = ch.make_solver(mesh, (rows, cols), inner_steps=20)
        got = run(c0, n_outer=1)
        want = c0
        for _ in range(20):
            want = ch.reference_step(want)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-4,
                                   err_msg=f"decomp {rows}x{cols}")


def case_mpdata_matches_oracle_all_layouts():
    """Paper Fig. 3: decomposition along dim 0 / dim 1 / 2-D must all give
    the same (oracle) answer."""
    rng = np.random.default_rng(1)
    n = 32
    psi0 = jnp.asarray(np.exp(-((np.arange(n) - 16) ** 2)[:, None] / 32
                              - ((np.arange(n) - 12) ** 2)[None, :] / 32),
                       jnp.float32) + 0.01
    want = psi0
    for _ in range(10):
        want = mpdata.reference_step(want)
    for rows, cols in ((8, 1), (1, 8), (2, 4)):
        mesh = mesh2d(rows, cols)
        run = mpdata.make_solver(mesh, inner_steps=10)
        got = run(psi0, n_outer=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-4,
                                   err_msg=f"decomp {rows}x{cols}")


def case_mpdata_conservation_and_positivity():
    """Property: homogeneous periodic advection conserves Σψ and keeps ψ>0."""
    rng = np.random.default_rng(2)
    n = 32
    psi0 = jnp.asarray(np.abs(rng.standard_normal((n, n))) + 0.1, jnp.float32)
    mesh = mesh2d(2, 4)
    run = mpdata.make_solver(mesh, inner_steps=25)
    out = run(psi0, n_outer=2)
    np.testing.assert_allclose(float(out.sum()), float(psi0.sum()),
                               rtol=1e-5)
    assert float(out.min()) >= 0.0


def case_cahn_hilliard_diagnostics_mass():
    """diagnostics=True: the in-program global_sum (a scalar jmpi allreduce,
    policy-routed to the small-payload algorithm) reports the exact global
    mass of the final field."""
    rng = np.random.default_rng(4)
    n = 32
    c0 = jnp.asarray(0.5 + 0.05 * rng.standard_normal((n, n)), jnp.float32)
    mesh = mesh2d(2, 4)
    run = ch.make_solver(mesh, (2, 4), k=0.0, inner_steps=10,
                         diagnostics=True)
    out, mass = run(c0, n_outer=1)
    np.testing.assert_allclose(float(mass), float(jnp.sum(out)), rtol=1e-5)
    np.testing.assert_allclose(float(mass), float(jnp.sum(c0)), rtol=1e-5)


def case_cahn_hilliard_conserves_mass_when_k0():
    """Property: pure Cahn–Hilliard (k=0) conserves total concentration."""
    rng = np.random.default_rng(3)
    n = 32
    c0 = jnp.asarray(0.5 + 0.05 * rng.standard_normal((n, n)), jnp.float32)
    mesh = mesh2d(2, 4)
    run = ch.make_solver(mesh, (2, 4), k=0.0, inner_steps=50)
    out = run(c0, n_outer=1)
    np.testing.assert_allclose(float(out.mean()), float(c0.mean()),
                               rtol=1e-6)


def _fused_run(rows, cols, steps, psi0):
    """``steps`` fused (Pallas, interpreted) MPDATA steps on a rows x cols
    decomposition: the kernel reads the strips of ``halo_strips_2d``.
    Tiles of 16 rows, so that each block spans several grid steps."""
    mesh = mesh2d(rows, cols)

    @jax.jit
    @jmpi.spmd(mesh, in_specs=P("px", "py"), out_specs=P("px", "py"))
    def run(blk):
        cart = jmpi.world().cart_create((rows, cols), periods=(True, True))
        return jax.lax.fori_loop(
            0, steps, lambda i, p: mpdata.fused_step(p, cart, interpret=True),
            blk)

    tile_rows, mpdata.TILE_ROWS = mpdata.TILE_ROWS, 16
    try:
        return run(psi0)
    finally:
        mpdata.TILE_ROWS = tile_rows


def case_mpdata_fused_decomposed_matches_oracle():
    """The fused step on 2x2, 1x4 and 4x1 decompositions (strips from the
    neighbours, corners included) against the one-device oracle."""
    rng = np.random.default_rng(5)
    n, m = 64, 512
    psi0 = jnp.asarray(1.0 + rng.random((n, m)), jnp.float32)
    want = psi0
    for _ in range(3):
        want = mpdata.reference_step(want)
    for rows, cols in ((2, 2), (1, 4), (4, 1)):
        got = _fused_run(rows, cols, 3, psi0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=0,
                                   err_msg=f"decomp {rows}x{cols}")


def case_mpdata_fused_conservation_and_positivity():
    """Property: the fused step on a 2x2 decomposition conserves Σψ and
    keeps ψ > 0 over 25 steps."""
    rng = np.random.default_rng(6)
    psi0 = jnp.asarray(np.abs(rng.standard_normal((64, 256))) + 0.1,
                       jnp.float32)
    out = _fused_run(2, 2, 25, psi0)
    np.testing.assert_allclose(float(jnp.sum(out)), float(jnp.sum(psi0)),
                               rtol=1e-5)
    assert float(out.min()) >= 0.0
