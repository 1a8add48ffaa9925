#!/usr/bin/env python3
"""Readings that set a cell's rate and limits; not part of a benchmark run.

    python chipbench/calibrate.py sweep --workload yi6b.chat \\
        --rates 2,4,6 --seconds 20
    python chipbench/calibrate.py readings --workload mpdata.16k.1chip \\
        --seeds 11,12,13 --seconds 5

``sweep`` (serving cells) offers the cell's mix at each rate in turn, in
one process with one engine, and prints per rate the tails, the tokens per
second and the queue at the window's open and close: the knee is the
highest rate whose queue does not grow.

``readings`` prints, per seed, the number that decides ``correct`` as the
program gives it at the cell's own size and load, beside the control's:
the plain reference put in the program's place one precision lower
(bfloat16 for the float32 PDE; float8 for the bfloat16 model).  The limit
in ``workloads/<cell>.json`` is set between the program's largest reading
and the control's smallest.

Each reading is one JSON line on standard output.  Add ``--rehearse`` to
run on the CPU at the rehearsal sizes.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
# libtpu writes its logs under /tmp unless told otherwise; a run writes
# only inside its checkout and the directories it is given.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402

PDE_BLOCKS_BEFORE = 2      # solver blocks run before the one compared


def _out(row: dict) -> None:
    print(json.dumps(row), flush=True)


def _context(args):
    cell = harness.load_cell(args.workload)
    harness.program_path()
    if not args.rehearse:
        harness.use_compile_cache()
    devs, peaks = harness.chips(cell.chips, args.rehearse)
    return harness.Context(cell=cell, seed=0, seconds=args.seconds,
                           trace=False, rehearse=args.rehearse, devices=devs,
                           peaks=peaks, t_start=T_START)


def sweep(args) -> None:
    from chipbench.drivers import serve
    ctx = _context(args)
    m, serving, scale, params, eng = serve.setup(ctx, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        eng.reset()
        plan, prompts = serve.traffic(ctx, m, scale, rate, args.seconds,
                                      args.seed)
        got = serve.serve(eng, plan, prompts, serve.ramp_s(ctx),
                          args.seconds, 0.0)
        win = got.window_requests
        _out(dict(
            kind="sweep", rate_rps=rate, seconds=args.seconds,
            **got.end_to_end(), queued_open_close=list(got.queued),
            window_requests=len(win),
            no_first_token_at_close=sum(1 for t in win if not t.times),
            engine_steps=got.steps, lateness=got.lateness))


def readings(args) -> None:
    ctx = _context(args)
    seeds = [int(s) for s in args.seeds.split(",")]
    if ctx.cell.config["driver"] == "serve":
        from chipbench.drivers import serve
        state = list(serve.setup(ctx, seeds[0]))
        read = lambda seed: _serve_reading(ctx, state, seed, args)
    else:
        read = lambda seed: _pde_reading(ctx, seed, args)
    for seed in seeds:
        _out(dict(kind="readings", seed=seed, **read(seed)))


def _serve_reading(ctx, state, seed, args) -> dict:
    """One seed's weights in the warm engine, a short window at the cell's
    load, then the program's check and the control's."""
    from chipbench.drivers import serve
    from chipbench.refs import llama
    m, serving, scale, _, eng = state
    eng.params = state[3] = None                 # free the last seed's
    params = state[3] = eng.params = llama.make_params(m, seed)
    eng.reset()
    wl = ctx.cell.workload
    plan, prompts = serve.traffic(ctx, m, scale, serve.rate_rps(ctx),
                                  args.seconds, seed)
    got = serve.serve(eng, plan, prompts, serve.ramp_s(ctx), args.seconds,
                      float(wl["grace_s"]))
    done = {rid: np.asarray(eng.results[rid]) for rid in eng.results}
    prog = serve.check(params, m, done, got.tracks, wl, scale, seed)
    ctrl = serve.check(params, m, done, got.tracks, wl, scale, seed,
                       quant="fp8")
    return {"program": {n: v for n, v, _ in prog},
            "control": {n: v for n, v, _ in ctrl},
            "tokens_per_s": got.end_to_end()["tokens_per_s"]}


def _pde_reading(ctx, seed, args) -> dict:
    """The driver's set-up from ``seed``, a few blocks, then one block
    checked as a run checks it; beside it the control, the bfloat16
    reference put in the solver's place on the same input."""
    import jax
    from chipbench.drivers import pde
    from chipbench.refs import mpdata as ref
    sol = pde.setup(ctx, seed)
    psi, sol.psi = sol.psi, None
    for _ in range(PDE_BLOCKS_BEFORE):
        psi = sol.solve(psi)
    prog = pde.max_abs_diff(ctx, sol, [(psi, sol.solve(psi))])
    one = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    low = ref.advance(jax.device_put(psi, one), dtype="bfloat16",
                      **sol.scheme)
    return {"program": {"max_abs_diff": prog},
            "control": {"max_abs_diff": pde.max_abs_diff(
                ctx, sol, [(psi, low)])}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("sweep", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    (sweep if args.what == "sweep" else readings)(args)


if __name__ == "__main__":
    main()
