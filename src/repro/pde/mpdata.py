"""MPDATA 2-D advection — the PyMPDATA-MPI example (paper §3.2).

Solves ∂t ψ + ∇·(u ψ) = 0 (homogeneous advection, G=1, μ=0 — the
"hello-world" setup of the paper's Fig. 3) with the two-pass MPDATA scheme:
a donor-cell upwind pass followed by ``n_iters−1`` antidiffusive corrective
passes (Smolarkiewicz velocities) on proper face-centred Courant fields.
Periodic boundaries via jmpi halo exchange; the full time loop (all passes +
communication) is one JIT-compiled block.

The decomposition axis is a *user choice* exactly as PyMPDATA-MPI exposes it
(paper Fig. 3 compares layouts): build the mesh (r, c) and the solver
decomposes rows over the first axis and columns over the second.

Two paths compute the same time step:

- **fused** (``fused_step``): one Pallas TPU kernel per step, named
  ``mpdata_step``.  The second pass needs the first pass's result on a
  5-point cross, so ψ is needed on a radius-2 diamond: one exchange per
  step, at width 2 (``stencil.halo_strips_2d``, corners included), instead
  of two at width 1.  The kernel reads each row tile of the block once,
  with the rows just above and below it, computes the donor-cell pass, the
  antidiffusive velocities and the corrective pass in VMEM and writes the
  new field once: no padded copy of the field, no stored Courant fields.
  It engages when the mesh's devices are TPUs, ``n_iters`` is 2, the field
  is float32 and the local block tiles (rows a multiple of 16, columns a
  multiple of 128).
- **jnp** (``_mpdata_step``): two exchanges per step at width 1 through
  the module name ``halo_exchange_2d``, then the passes on the padded
  blocks.  It runs everywhere else (CPU, small or odd blocks), and is the
  oracle the kernel is tested against.

``path_stats()`` counts the time steps traced through each path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

import repro.core as jmpi
from repro.pde.stencil import halo_exchange_2d, halo_strips_2d

EPS = 1e-10             # antidiffusive ratio's guard against 0/0
LANES = 128             # TPU lane width: the kernel's column granule
TILE_ROWS = 128         # rows of ψ a grid step of the kernel reads
CHUNK = 1024            # columns one pass of the kernel's row sweep writes
_TILE_BYTES = 8 << 20   # cap on one (rows, m) tile of ψ in VMEM

_PATHS = {"fused": 0, "jnp": 0}


def path_stats() -> dict:
    """{'fused', 'jnp'}: time steps traced through the Pallas kernel and
    through the jnp passes since the last ``path_stats_clear``."""
    return dict(_PATHS)


def path_stats_clear() -> None:
    """Zero the path counters (tests isolating one solver's trace)."""
    for k in _PATHS:
        _PATHS[k] = 0


def _flux(psi_l, psi_r, c):
    """Donor-cell flux through a face with Courant number c."""
    return jnp.maximum(c, 0.0) * psi_l + jnp.minimum(c, 0.0) * psi_r


def _ratio(hi, lo, eps=EPS):
    """Smolarkiewicz's antidiffusive ratio across a face."""
    return (hi - lo) / (hi + lo + eps)


def _advect(psi_h, cx_f, cy_f):
    """One upwind pass on a halo-1 padded block.

    cx_f: (n, m+1) Courant at x-faces (col j−1/2 .. m−1/2);
    cy_f: (n+1, m) Courant at y-faces.  Returns the interior update (n, m).
    """
    n, m = psi_h.shape[0] - 2, psi_h.shape[1] - 2
    c = psi_h[1:1 + n, 1:1 + m]
    up = psi_h[0:n, 1:1 + m]
    dn = psi_h[2:2 + n, 1:1 + m]
    lf = psi_h[1:1 + n, 0:m]
    rt = psi_h[1:1 + n, 2:2 + m]
    fx_r = _flux(c, rt, cx_f[:, 1:])
    fx_l = _flux(lf, c, cx_f[:, :-1])
    fy_d = _flux(c, dn, cy_f[1:, :])
    fy_u = _flux(up, c, cy_f[:-1, :])
    return c - (fx_r - fx_l) - (fy_d - fy_u)


def _antidiff(psi_h, cx, cy, eps=EPS):
    """Smolarkiewicz antidiffusive face Courant fields from a halo-1 padded
    (positive-definite) field, for constant first-pass Courants (cx, cy)."""
    n, m = psi_h.shape[0] - 2, psi_h.shape[1] - 2
    row = psi_h[1:1 + n, :]                      # (n, m+2)
    ax = _ratio(row[:, 1:], row[:, :-1], eps)
    col = psi_h[:, 1:1 + m]                      # (n+2, m)
    ay = _ratio(col[1:, :], col[:-1, :], eps)
    cx2 = (jnp.abs(cx) - cx * cx) * ax           # (n, m+1)
    cy2 = (jnp.abs(cy) - cy * cy) * ay           # (n+1, m)
    return cx2, cy2


def _mpdata_step(psi, cx, cy, n_iters, exchange):
    ph = exchange(psi)
    n, m = psi.shape
    cx_f = jnp.full((n, m + 1), cx)
    cy_f = jnp.full((n + 1, m), cy)
    out = _advect(ph, cx_f, cy_f)
    for _ in range(n_iters - 1):
        oh = exchange(out)
        cx2, cy2 = _antidiff(oh, cx, cy)
        out = _advect(oh, cx2, cy2)
    return out


# --- the fused step: one Pallas kernel per time step ------------------------
#
# A grid step owns T rows of the block at full width.  Its VMEM scratch holds
# ψ on rows [-8, T+8) and columns [-128, m+128) of the tile: the tile, the 8
# rows above and below it (from the block itself, wrapped, or from the
# received row strips at the block's edges), and one lane column on each
# side (the block's own far columns, wrapped, or the received column
# strips).  The kernel then sweeps down that scratch in groups of 8 rows,
# over column chunks of CHUNK + 256 lanes: pass 1 runs one group ahead of
# pass 2, so each pass-2 group finds pass 1's result on the rows just above
# and below it in registers.  Only the centre rows and columns are written.


def _east(x):
    return pltpu.roll(x, x.shape[1] - 1, 1)          # x[:, j + 1]


def _west(x):
    return pltpu.roll(x, 1, 1)                       # x[:, j - 1]


def _south(x, nxt):
    """x[r + 1] of a group of 8 rows, its last row from ``nxt``'s first."""
    r = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    k = x.shape[0] - 1
    return jnp.where(r == k, pltpu.roll(nxt, k, 0), pltpu.roll(x, k, 0))


def _north(y, prev):
    """y[r − 1] of a group of 8 rows, its first row from ``prev``'s last."""
    r = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    return jnp.where(r == 0, pltpu.roll(prev, 1, 0), pltpu.roll(y, 1, 0))


def _step_kernel(*refs, cx, cy, tile, m, chunk, split_rows, split_cols):
    it = iter(refs)
    mid, above, below = next(it), next(it), next(it)
    if split_rows:
        top, bottom = next(it), next(it)
    if split_cols:
        left, left_tail, right, right_tail = (next(it) for _ in range(4))
    out, s = it
    t, lanes, w = tile, LANES, chunk + 2 * LANES
    i = pl.program_id(0)

    s[8:8 + t, lanes:lanes + m] = mid[...]
    if split_rows:
        s[0:8, lanes:lanes + m] = jnp.where(i == 0, top[...], above[...])
        s[t + 8:t + 16, lanes:lanes + m] = jnp.where(
            i == pl.num_programs(0) - 1, bottom[...], below[...])
    else:
        s[0:8, lanes:lanes + m] = above[...]
        s[t + 8:t + 16, lanes:lanes + m] = below[...]
    if split_cols:
        s[0:t, 0:lanes] = left[...]
        s[t:t + 16, 0:lanes] = left_tail[...]
        s[0:t, m + lanes:] = right[...]
        s[t:t + 16, m + lanes:] = right_tail[...]
    else:
        s[:, 0:lanes] = s[:, m:m + lanes]
        s[:, m + lanes:] = s[:, lanes:2 * lanes]

    kx = jnp.abs(cx) - cx * cx          # as in _antidiff
    ky = jnp.abs(cy) - cy * cy
    groups = t // 8 + 2                 # scratch rows in groups of 8

    def donor(x, x_next, fy_prev):
        """Pass 1 on a group; returns its result and its south fluxes."""
        fx = _flux(x, _east(x), cx)
        fy = _flux(x, _south(x, x_next), cy)
        return x - (fx - _west(fx)) - (fy - _north(fy, fy_prev)), fy

    def south_flux(o, o_next):
        """Pass 2's fluxes through a group's south faces."""
        so = _south(o, o_next)
        return _flux(o, so, ky * _ratio(so, o))

    def sweep(k, carry):
        c0 = pl.multiple_of(k * chunk, lanes)

        def rows(g):
            return s[pl.ds(pl.multiple_of(8 * g, 8), 8), pl.ds(c0, w)]

        # group 0 is the 8 rows above the tile: only its last row matters
        x1 = rows(1)
        o0, fy0 = donor(rows(0), x1, x1)
        o1, fy1 = donor(x1, rows(2), fy0)

        def body(g, carry):
            o, fy, fy2_prev = carry
            on, fyn = donor(rows(g + 1), rows(jnp.minimum(g + 2, groups - 1)),
                            fy)
            fy2 = south_flux(o, on)
            e = _east(o)
            fx2 = _flux(o, e, kx * _ratio(e, o))
            new = o - (fx2 - _west(fx2)) - (fy2 - _north(fy2, fy2_prev))
            out[pl.ds(pl.multiple_of(8 * (g - 1), 8), 8),
                pl.ds(c0, chunk)] = new[:, lanes:lanes + chunk]
            return on, fyn, fy2

        jax.lax.fori_loop(1, groups - 1, body, (o1, fy1, south_flux(o0, o1)))
        return carry

    jax.lax.fori_loop(0, m // chunk, sweep, 0)


def _tile_rows(shape) -> int:
    """Rows T of the kernel's tile for an (n, m) block (a multiple of 16
    dividing n, at most TILE_ROWS, one tile at most _TILE_BYTES), or 0
    where the block does not tile."""
    n, m = shape
    if n % 16 or m % LANES:
        return 0
    t = min(TILE_ROWS, n, _TILE_BYTES // (4 * m) // 16 * 16)
    while t >= 16 and n % t:
        t -= 16
    return max(t, 0)


def _chunk(m: int) -> int:
    """The widest CHUNK / 2^k columns that divide m (a multiple of 128)."""
    c = CHUNK
    while m % c:
        c //= 2
    return c


def fused_step(psi, cart=None, courant=(0.2, 0.2), *, interpret=False):
    """One two-pass MPDATA time step of the local block as one Pallas call.

    Args:
        psi: the local ``(n, m)`` float32 block.
        cart: 2-D periodic CartComm of the decomposition (None: ``psi`` is
            the whole periodic field).
        courant: constant (cx, cy).
        interpret: run the kernel in the Pallas interpreter (CPU tests).
    Returns:
        The block after one step (``_mpdata_step`` with ``n_iters`` = 2).
    Raises:
        ValueError: the block does not tile.
    """
    n, m = psi.shape
    t = _tile_rows(psi.shape)
    if not t or psi.dtype != jnp.float32:
        raise ValueError(f"fused_step cannot tile a {psi.dtype} block "
                         f"{psi.shape}")
    _PATHS["fused"] += 1
    dims = cart.dims if cart is not None else (1, 1)
    split_rows, split_cols = dims[0] > 1, dims[1] > 1
    chunk = _chunk(m)
    nb, tb = n // 8, t // 8
    args = [psi, psi, psi]
    specs = [pl.BlockSpec((t, m), lambda i: (i, 0)),
             pl.BlockSpec((8, m), lambda i: ((i * tb - 1) % nb, 0)),
             pl.BlockSpec((8, m), lambda i: (((i + 1) * tb) % nb, 0))]
    if split_rows or split_cols:
        top, bottom, left, right = halo_strips_2d(psi, cart, halo=2)
    if split_rows:    # the strips as the 8 rows above / below the block
        args += [jnp.pad(top, ((6, 0), (0, 0))),
                 jnp.pad(bottom, ((0, 6), (0, 0)))]
        specs += [pl.BlockSpec((8, m), lambda i: (0, 0))] * 2
    if split_cols:    # (n + 16, 128): rows -8 .. n + 8, strip at the inner lanes
        for strip, lanes in ((left, (LANES - 2, 0)), (right, (0, LANES - 2))):
            ext = jnp.pad(strip, ((6, 6), lanes))
            args += [ext, ext]
            specs += [pl.BlockSpec((t, LANES), lambda i: (i, 0)),
                      pl.BlockSpec((16, LANES),
                                   lambda i: ((i + 1) * t // 16, 0))]
    scratch = (t + 16) * (m + 2 * LANES) * 4
    vmem = (4 * t * m + 8 * 8 * m + 8 * (t + 16) * LANES) * 4 + scratch
    cx, cy = courant
    kernel = functools.partial(_step_kernel, cx=cx, cy=cy, tile=t, m=m,
                               chunk=chunk, split_rows=split_rows,
                               split_cols=split_cols)
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(psi.shape, psi.dtype),
        grid=(n // t,), in_specs=specs,
        out_specs=pl.BlockSpec((t, m), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((t + 16, m + 2 * LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + (16 << 20)),
        cost_estimate=pl.CostEstimate(flops=60 * n * m, transcendentals=0,
                                      bytes_accessed=8 * n * m),
        interpret=interpret, name="mpdata_step")(*args)


def make_solver(mesh, *, courant=(0.2, 0.2), n_iters=2, inner_steps=50):
    """Multi-rank MPDATA solver: run(psi_global, n_outer) -> psi_global."""
    axes = mesh.axis_names
    rows, cols = mesh.devices.shape
    on_tpu = mesh.devices.flat[0].platform == "tpu"

    @jmpi.spmd(mesh, in_specs=P(axes[0], axes[1]),
               out_specs=P(axes[0], axes[1]))
    def run_block(psi):
        world = jmpi.world()
        cart = world.cart_create((rows, cols), periods=(True, True))
        cx, cy = courant
        if (on_tpu and n_iters == 2 and psi.dtype == jnp.float32
                and _tile_rows(psi.shape)):
            step = lambda p: fused_step(p, cart, courant)
        else:
            def step(p):
                _PATHS["jnp"] += 1
                exchange = lambda f: halo_exchange_2d(f, cart, halo=1)
                return _mpdata_step(p, cx, cy, n_iters, exchange)
        return jax.lax.fori_loop(0, inner_steps, lambda i, p: step(p), psi)

    def run(psi_global, n_outer=1):
        for _ in range(n_outer):
            psi_global = run_block(psi_global)
        return psi_global

    return run


def reference_step(psi, courant=(0.2, 0.2), n_iters=2):
    """Single-device periodic oracle (jnp.roll halos)."""
    def pad(a):
        a = jnp.concatenate([a[-1:], a, a[:1]], axis=0)
        return jnp.concatenate([a[:, -1:], a, a[:, :1]], axis=1)
    def exchange(f):
        return pad(f)
    cx, cy = courant
    return _mpdata_step(psi, cx, cy, n_iters, exchange)
