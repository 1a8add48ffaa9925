"""Domain-decomposition halo exchange on top of jmpi (paper §3 substrate).

py-pde and PyMPDATA-MPI both reduce their distributed needs to one
primitive: exchange boundary strips with grid neighbours, then run the local
stencil.  ``halo_strips_2d`` implements exactly that — and, since the
topology subsystem landed, it no longer computes neighbour ranks at all: the
solver attaches a Cartesian topology once (``world.cart_create((rows,
cols), periods=(True, True))``) and each decomposed axis is one MPI-3
``neighbor_alltoall`` on the ``cart_sub`` sub-grid — the send-up/send-down
strip pair is exactly the collective's slot layout, so what used to be two
hand-rolled ``sendrecv`` ring permutations per axis is one first-class
registry collective (``xla_native`` shifts or the p2p-fused ``ring``
lowering, policy's choice).  ``halo_exchange_2d`` concatenates those
strips onto the block for the jnp stencils; a fused kernel
(``pde.mpdata``'s Pallas step) reads the strips themselves and never makes
the padded copy.

The decomposition layout is the Cartesian grid: decompose along axis 0,
axis 1, or both, by building the mesh with the matching axis sizes
(paper Fig. 3's layout study = benchmarks/bench_mpdata.py); degenerate
(size-1) dims wrap locally, matching the periodic self-neighbour.

Persistent plans: a PDE time loop re-exchanges the SAME strip signature
every step, so the exchange rides ``cart.neighbor_alltoall_init`` plans —
topology and algorithm are validated and frozen once per (shape, dtype,
comm) and the process-global plan cache serves every later step/trace
(MPI_Neighbor_alltoall_init semantics; see ``repro.core.plans``).

Halo slabs are **subarray datatypes** (``repro.core.datatypes.face``): the
boundary faces are described declaratively per (axis, side, width) — the
MPI ``MPI_Type_create_subarray`` idiom — and the datatype's ``pack``
materializes each strip at the transfer boundary; no manual slicing at
the call sites.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import repro.core as jmpi
from repro.core import datatypes


def _faces(blocks, axis: int, halo: int):
    """The ``lo`` and ``hi`` faces (width ``halo``, perpendicular to
    ``axis``) of the blocks stacked along the other axis: the faces of the
    stacked block, without stacking it."""
    lo, hi = [], []
    for b in blocks:
        lo.append(datatypes.face(b.shape, axis, "lo", halo,
                                 dtype=b.dtype).pack(b))
        hi.append(datatypes.face(b.shape, axis, "hi", halo,
                                 dtype=b.dtype).pack(b))
    return (jnp.concatenate(lo, axis=1 - axis),
            jnp.concatenate(hi, axis=1 - axis))


def _exchange_axis(sub: "jmpi.CartComm | None", lo, hi, algorithm=None):
    """One decomposed axis as a persistent neighbor_alltoall of its two
    faces.

    Args:
        sub: 1-D periodic CartComm along the axis (None = axis not
            decomposed → periodic local wrap).
        lo: the face sent to the −1 neighbour.
        hi: the face sent to the +1 neighbour.
        algorithm: registry entry to freeze into the plan (None = policy).
    Returns:
        ``(from_minus, from_plus)`` — the halo strips received from the
        −1 / +1 neighbours.
    """
    if sub is None:
        return hi, lo  # periodic self-wrap
    send = jnp.stack([lo, hi])
    plan = sub.neighbor_alltoall_init(
        jax.ShapeDtypeStruct(send.shape, send.dtype), algorithm=algorithm)
    _, recv = jmpi.wait(plan.start(send))
    return recv[0], recv[1]


def halo_strips_2d(field, cart: "jmpi.CartComm", halo: int = 1, *,
                   algorithm=None):
    """The periodic neighbour strips of ``field`` (local block), not
    concatenated onto it.

    Args:
        field: the local ``(n, m)`` block.
        cart: 2-D periodic :class:`~repro.core.topology.CartComm` from
            ``world.cart_create((rows, cols), periods=(True, True))`` —
            dim 0 decomposes rows, dim 1 columns; size-1 dims wrap locally.
        halo: strip width ``h``.
        algorithm: neighbor-collective registry entry to freeze into the
            exchange plans (None = the active policy's choice).
    Returns:
        ``(top, bottom, left, right)``: the row strips ``(h, m)`` from the
        −1 / +1 row neighbours, then the column strips ``(n + 2h, h)`` from
        the −1 / +1 column neighbours, corners included: the column phase
        sends the faces of the row-padded block.
    Raises:
        ValueError: ``cart`` is not 2-dimensional.
    """
    if cart.ndims != 2:
        raise ValueError(f"a 2-D halo exchange needs a 2-D CartComm, got "
                         f"{cart.ndims}-D dims={cart.dims}")
    h = halo
    sub_r = cart.cart_sub((True, False)) if cart.dims[0] > 1 else None
    sub_c = cart.cart_sub((False, True)) if cart.dims[1] > 1 else None

    # --- axis 0 (rows): 'lo' face to the -1 neighbour, 'hi' to the +1 ----
    top, bottom = _exchange_axis(sub_r, *_faces([field], 0, h), algorithm)
    # --- axis 1 (cols): faces of the row-padded block, so corners resolve -
    left, right = _exchange_axis(
        sub_c, *_faces([top, field, bottom], 1, h), algorithm)
    return top, bottom, left, right


def halo_exchange_2d(field, cart: "jmpi.CartComm", halo: int = 1, *,
                     algorithm=None):
    """Pad ``field`` (local block) with periodic neighbour strips.

    Args:
        field: the local ``(n, m)`` block.
        cart: 2-D periodic CartComm (see :func:`halo_strips_2d`).
        halo: strip width.
        algorithm: neighbor-collective registry entry to freeze into the
            exchange plans (None = the active policy's choice).
    Returns:
        The ``(n + 2·halo, m + 2·halo)`` padded block: the strips of
        :func:`halo_strips_2d` concatenated onto it.
    Raises:
        ValueError: ``cart`` is not 2-dimensional.
    """
    top, bottom, left, right = halo_strips_2d(field, cart, halo,
                                              algorithm=algorithm)
    rows = jnp.concatenate([top, field, bottom], axis=0)
    return jnp.concatenate([left, rows, right], axis=1)


def global_sum(field, *comms: "jmpi.Communicator | None"):
    """Global Σfield across the decomposition — the PDE diagnostics reduce
    (mass conservation, residual norms).

    The local partial sum is a scalar, so the collective-algorithm policy
    routes this through its latency-optimal small-payload entry
    (recursive_doubling under the built-in table) rather than the
    bandwidth schedule the field itself would get — the per-payload
    selection the registry exists for.

    Args:
        field: the local block to sum.
        comms: one communicator per decomposed axis (None entries skipped;
            no live comm → local sum only).
    Returns:
        The scalar global sum (same value on every rank).

    Uses an explicit fresh token (control-flow safe): diagnostics typically
    run right after a ``fori_loop``/``scan`` time loop, and the ambient
    token set inside that loop's trace must not be consumed outside it.
    """
    total = jnp.sum(field)
    for comm in comms:
        if comm is not None and comm.size() > 1:
            plan = comm.allreduce_init(
                jax.ShapeDtypeStruct(total.shape, total.dtype))
            _, total = jmpi.wait(plan.start(total, token=jmpi.new_token()))
    return total


def laplacian(c_halo, dx: float = 1.0, halo: int = 1):
    """5-point Laplacian of the interior of a halo-padded block.

    Args:
        c_halo: halo-padded ``(n + 2·halo, m + 2·halo)`` block.
        dx: grid spacing.
        halo: pad width of the input.
    Returns:
        The ``(n, m)`` interior Laplacian.
    """
    h = halo
    n = c_halo.shape[0] - 2 * h
    m = c_halo.shape[1] - 2 * h
    c = c_halo[h:h + n, h:h + m]
    up = c_halo[h - 1:h - 1 + n, h:h + m]
    dn = c_halo[h + 1:h + 1 + n, h:h + m]
    lf = c_halo[h:h + n, h - 1:h - 1 + m]
    rt = c_halo[h:h + n, h + 1:h + 1 + m]
    return (up + dn + lf + rt - 4.0 * c) / (dx * dx)
