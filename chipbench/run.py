#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A run finds the cell in ``BENCHMARK.json``, refuses to run (non-zero exit,
no result) unless JAX finds a TPU of a kind in ``chipbench/peaks.json``
with the chips the cell asks for, builds and warms the cell's program
(``setup_s``), measures for ``--seconds``, checks what the timed path
produced against a plain float32 reference, and prints one JSON line last
on standard output.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window's first ``trace_seconds`` and reports the
per-layer metrics with the device's busy time and a breakdown.

``--rehearse`` runs the same path on the CPU at the tiny sizes of the
configuration's ``rehearsal`` block; its line says "cpu rehearsal" and
carries no metric and no device.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
# libtpu writes its logs under /tmp unless told otherwise; a run writes
# only inside its checkout and the directories it is given.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402
from chipbench import trace_reduce  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; reports no metric")
    return ap.parse_args(argv)


def rehearse_trace(ctx, out) -> None:
    """Drive the trace reduction and every reader on a CPU trace; print
    what was found, never a number under a device metric's name."""
    red = ctx.tracer.reduce()
    n_ops = sum(len(d.ops) for d in red.devices)
    spans = {k: len(v) for k, v in red.spans.items() if "." in k}
    print(f"[rehearsal] trace: {n_ops} XLA operations, spans {spans}",
          file=sys.stderr, flush=True)
    found = harness.per_layer(ctx.cell, red, out.info, {
        "hbm_bytes_per_s": 1.0, "bf16_flops_per_s": 1.0})
    for m in ctx.cell.per_layer:
        print(f"[rehearsal] reader {m['name']}: "
              f"{'read' if m['name'] in found else 'nothing to read'}",
              file=sys.stderr, flush=True)


def main(argv=None) -> dict:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    harness.program_path()
    cache = "off" if args.rehearse else harness.use_compile_cache()
    devs, peaks = harness.chips(cell.chips, args.rehearse)
    print(f"[chipbench] {cell.name}: {len(devs)} x {devs[0].device_kind}; "
          f"compile cache {cache}", file=sys.stderr, flush=True)
    driver = harness.load_module(
        os.path.join(harness.HERE, "drivers", cell.config["driver"] + ".py"),
        "chipbench_driver_" + cell.config["driver"])
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), rehearse=args.rehearse,
                          devices=devs, peaks=peaks, t_start=T_START)
    out = driver.run(ctx)
    correct = all(harness.passes(v, lim) for _, v, lim in out.checks)
    result = {"correct": bool(correct), "attempted": int(out.attempted),
              "failed": int(out.failed)}
    if args.rehearse:
        if args.trace:
            rehearse_trace(ctx, out)
        result = {"rehearsal": "cpu rehearsal", **result}
        harness.emit(result, out.checks)
        return result
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": out.info["memory_peak_bytes"]}
    if args.trace:
        red = ctx.tracer.reduce()
        metrics = harness.per_layer(cell, red, out.info, peaks)
        device["busy_s"] = harness.finite(trace_reduce.busy_s(red))
        device["window_s"] = red.window_s
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": trace_reduce.device_ops_top(red),
            "idle_gaps": trace_reduce.idle_gaps_top(red)})
    else:
        metrics = {m["name"]: {"value": harness.finite(
            out.end_to_end[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
        result.update(metrics=metrics, device=device)
    harness.emit(result, out.checks)
    return result


if __name__ == "__main__":
    main()
