"""Driver for a domain-decomposed PDE solver: ``repro.pde.mpdata``.

Set-up makes the field on the device from the seed, builds the solver with
``make_solver(mesh, inner_steps=...)`` on the traffic's mesh and runs one
block, which compiles it.  The window then calls the solver block after
block, each call ended by ``block_until_ready``, each inside a
``pde.run_block`` host span, until ``--seconds`` have passed:
``pde_step_ms`` is the window's time over the time steps completed in it.

What decides ``correct``: two blocks of the window -- one drawn from the
seed among its first calls, and the last -- are replayed from their own
input by the plain float32 reference (``refs/mpdata.py``) on one chip, and
the largest absolute difference of the solver's output from it must stay
within the cell's limit.  On a mesh of several chips the output's edges
come through the halo exchange, so the exchange is covered too.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from chipbench import harness


def _sizes(ctx) -> dict:
    cfg = dict(ctx.cell.config["scheme"])
    if ctx.rehearse:
        cfg.update(ctx.cell.config["rehearsal"])
    return cfg


@dataclasses.dataclass
class Solver:
    """The solver of a cell, built and compiled, with its first field."""

    solve: object             # one block: field -> field, ``steps`` steps
    psi: object               # the field from the seed, spread over mesh
    grid: int
    mesh: tuple               # (rows, cols)
    scheme: dict              # ``refs/mpdata.advance``'s keywords


def setup(ctx, seed: int) -> Solver:
    """The field from ``seed`` on the device, the solver on the traffic's
    mesh, and one block run, which compiles it."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import compat
    from repro.pde import mpdata
    from chipbench.refs import llama

    s = _sizes(ctx)
    rows, cols = ctx.cell.traffic["mesh"]
    n = int(s["grid"])
    scheme = dict(steps=int(s["inner_steps"]), courant=tuple(s["courant"]),
                  n_iters=int(s["n_iters"]))
    mesh = compat.make_mesh((rows, cols), ("x", "y"))
    make = jax.jit(lambda k: 1.0 + jax.random.uniform(k, (n, n)),
                   out_shardings=NamedSharding(mesh, P("x", "y")))
    psi = make(llama.seed_key(seed))
    solve = mpdata.make_solver(mesh, courant=scheme["courant"],
                               n_iters=scheme["n_iters"],
                               inner_steps=scheme["steps"])
    psi = jax.block_until_ready(solve(psi))
    return Solver(solve, psi, n, (rows, cols), scheme)


def max_abs_diff(ctx, sol: Solver, pairs) -> float:
    """The largest absolute difference between each (input, output) pair's
    output and the plain float32 reference's, advanced from the same input
    on one chip."""
    import jax
    import jax.numpy as jnp
    from chipbench.refs import mpdata as ref

    one = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    worst = 0.0
    for pair in pairs:
        inp, got = (jax.device_put(a, one) for a in pair)
        want = ref.advance(inp, **sol.scheme)
        worst = max(worst, float(jnp.max(jnp.abs(got - want))))
        del inp, got, want
    return worst


def run(ctx) -> harness.Outcome:
    import jax

    wl = ctx.cell.workload
    sol = setup(ctx, ctx.seed)                       # compiles: set-up
    steps = sol.scheme["steps"]
    psi, sol.psi = sol.psi, None          # the window holds what it advances

    rng = np.random.default_rng([ctx.seed, 7])
    sample = int(rng.integers(0, int(wl["sample_within_calls"])))
    held, calls, traced = None, 0, 0
    w0 = ctx.begin_window()
    while True:
        with ctx.clock.step(), jax.profiler.TraceAnnotation("pde.run_block"):
            out = jax.block_until_ready(sol.solve(psi))
        if calls == sample:
            held = (psi, out)
        calls += 1
        t = time.perf_counter()
        if ctx.tracer.active and ctx.tracer.path is None:
            traced += 1
            if t - w0 >= ctx.tracer.seconds:
                ctx.tracer.stop()
        if t - w0 >= ctx.seconds:
            break
        psi = out
    window_s = t - w0
    last = (psi, out)
    ctx.end_window()
    peak = harness.memory_peak_bytes(ctx.devices)

    worst = max_abs_diff(ctx, sol, [held if held is not None else last,
                                    last])
    rows, cols = sol.mesh
    return harness.Outcome(
        attempted=calls, failed=0,
        end_to_end={"pde_step_ms": 1e3 * window_s / (calls * steps),
                    "setup_s": ctx.setup_s},
        checks=[("max_abs_diff", worst, float(wl["limits"]["max_abs_diff"]))],
        info={"memory_peak_bytes": peak, "grid": sol.grid,
              "mesh": [rows, cols], "inner_steps": steps,
              "traced_steps": traced * steps, "bytes_per_cell": 4})
