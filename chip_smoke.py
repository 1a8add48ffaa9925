#!/usr/bin/env python3
"""Drive the main path once on a TPU and check what comes out.

    python chip_smoke.py                 # one chip, real sizes
    python chip_smoke.py --four-chips    # a 2x2 host: what exists only across chips
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny            # CPU rehearsal
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --tiny --four-chips             # 4 virtual devices

One chip, three phases, each through the entry points a user calls:

1. ``pi``: the paper's Listing 1 through ``jmpi.spmd`` -- a persistent
   allreduce plan (``allreduce_init(...).start()`` / ``jmpi.wait``) plus
   one ``sendrecv`` on the same communicator.
2. ``mpdata``: ``pde.mpdata.make_solver`` on an 8192^2 float32 field,
   10 inner steps, against ``reference_step`` applied 10 times.
3. ``serve``: yi-6b at its published widths, depth cut to 4 of 32 layers,
   served by ``ContinuousEngine`` (8 requests, 512-token prompts, 64 new
   tokens each, 8 slots) against a plain float32 reference; the Pallas
   decode kernel against ``_sdpa`` on one step's gathered K/V.

``--four-chips`` runs only MPDATA at 16384^2 on a 2x2 mesh against a
one-chip reference, and the registered collective lowerings against numpy
at 1 KiB and 4 MiB.

Each phase prints one line (its check, its cold and warm seconds: cold
includes compilation) and raises on a failed check, so the script exits
non-zero.  It refuses to run (non-zero exit, no result) where JAX finds no
TPU, unless ``--tiny`` is given.  The last line is one JSON object naming
the device the phases ran on.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import repro.core as jmpi  # noqa: E402
from repro.bench.suites.pi import RTOL as PI_RTOL  # noqa: E402
from repro.configs import get_config, get_tiny  # noqa: E402
from repro.core import compat  # noqa: E402
from repro.kernels.decode_attention.ops import decode_attention  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import cache as cache_lib  # noqa: E402
from repro.models import lm as lm_lib  # noqa: E402
from repro.models.attention import _sdpa  # noqa: E402
from repro.pde import mpdata  # noqa: E402
from repro.serve.engine import ContinuousEngine, ServeConfig  # noqa: E402

MPDATA_TOL = 1e-5        # max |solver - reference|, float32, same chip
KERNEL_TOL = 3e-2        # bf16 decode kernel vs float32 _sdpa (atol = rtol)
LOGIT_TOL_REL = 0.05     # bf16 serving logits vs float32 reference, as a
#                          share of the reference logits' largest magnitude


class CheckFailed(RuntimeError):
    """A phase's result disagreed with its reference."""


def require(ok, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def timed(fn, *args):
    """(result, seconds) of ``fn(*args)`` with every output on the device
    finished."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def report(phase: str, check: str, cold: float, warm: float) -> None:
    print(f"[{phase}] {check}; cold {cold:.2f}s (compile+run), "
          f"warm {warm:.3f}s", flush=True)


# --------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------- #

def phase_pi(n_intervals: int) -> None:
    mesh = compat.make_mesh((len(jax.devices()),), ("ranks",))

    def get_pi_part(n, rank, size):
        h = 1.0 / n
        idx = rank + 1 + size * jnp.arange(n // size + 1)
        x = h * (idx - 0.5)
        return h * jnp.sum(jnp.where(idx <= n, 4.0 / (1.0 + x * x), 0.0))

    @jmpi.spmd(mesh, in_specs=P(), out_specs=(P(), P("ranks")))
    def pi_step(dummy):
        comm = jmpi.world()
        part = get_pi_part(n_intervals, comm.rank(), comm.size()) + dummy
        plan = comm.allreduce_init(jax.ShapeDtypeStruct(part.shape,
                                                        part.dtype))
        _, pi = jmpi.wait(plan.start(part))
        _, got = comm.sendrecv(comm.rank()[None], pairs=comm.ring_perm(1))
        return pi, got

    x = jnp.float32(0.0)
    _, cold = timed(pi_step, x)
    (pi, got), warm = timed(pi_step, x)
    n = mesh.size
    err = abs(float(pi) - math.pi) / math.pi
    require(err < PI_RTOL, f"pi={float(pi)!r}: relative error {err} "
                           f">= {PI_RTOL}")
    want = (np.arange(n) - 1) % n
    require(np.array_equal(np.asarray(got), want),
            f"sendrecv ring shift gave {np.asarray(got)}, want {want}")
    report("pi", f"pi={float(pi):.7f} rel err {err:.2e} < {PI_RTOL} over "
           f"{n_intervals} intervals on {n} rank(s); sendrecv ring ok",
           cold, warm)


def _field(n: int, sharding):
    """Positive-definite float32 field made on the device from a seed."""
    make = jax.jit(lambda k: 1.0 + jax.random.uniform(k, (n, n)),
                   out_shardings=sharding)
    return make(jax.random.PRNGKey(0))


def _reference(steps: int):
    @jax.jit
    def run(psi):
        return jax.lax.fori_loop(0, steps,
                                 lambda i, p: mpdata.reference_step(p), psi)
    return run


def phase_mpdata(n: int, mesh, steps: int = 10) -> None:
    """make_solver on ``mesh`` vs ``reference_step`` on the first device."""
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    psi0 = _field(n, one)
    spread = jax.device_put(psi0, NamedSharding(mesh, P(*mesh.axis_names)))
    solve = mpdata.make_solver(mesh, inner_steps=steps)
    ref = _reference(steps)
    t0 = time.perf_counter()
    jax.block_until_ready((solve(spread), ref(psi0)))
    cold = time.perf_counter() - t0
    got, warm = timed(solve, spread)
    want, ref_warm = timed(ref, psi0)
    placed = [s.device.id for s in sorted(got.addressable_shards,
                                          key=lambda s: (s.index[0].start or 0,
                                                         s.index[1].start or 0))]
    mesh_ids = [d.id for d in mesh.devices.flat]
    require(placed == mesh_ids, f"solver shards on devices {placed}, "
                                f"mesh order {mesh_ids}")
    diff = float(jnp.max(jnp.abs(jax.device_put(got, one) - want)))
    require(diff <= MPDATA_TOL, f"mpdata max|diff| {diff} > {MPDATA_TOL}")
    shape = "x".join(map(str, mesh.devices.shape))
    report("mpdata", f"{n}^2 f32 x{steps} steps on a {shape} mesh (devices "
           f"{mesh_ids}) vs reference_step on device {jax.devices()[0].id}: "
           f"max|diff| {diff:.2e} <= {MPDATA_TOL}; reference warm "
           f"{ref_warm:.3f}s", cold, warm)


def _serve_setup(tiny: bool):
    if tiny:
        cfg = get_tiny("yi-6b")
        prompt_len, new_tokens = 24, 8
    else:
        full = get_config("yi-6b")
        cfg = dataclasses.replace(full, n_layers=4)
        prompt_len, new_tokens = 512, 64
        print(f"[serve] yi-6b depth cut: {cfg.n_layers} of {full.n_layers} "
              f"layers at published widths (d_model {cfg.d_model}, "
              f"{cfg.n_heads} heads, {cfg.n_kv_heads} KV heads, d_ff "
              f"{cfg.d_ff}, vocab {cfg.vocab_size}); "
              f"{lm_lib.count_params(cfg) / 1e9:.3f} B params, "
              f"param dtype {cfg.param_dtype}, compute dtype {cfg.dtype}",
              flush=True)
    return cfg, prompt_len, new_tokens


def phase_serve(tiny: bool, n_requests: int = 8, slots: int = 8) -> None:
    cfg, prompt_len, new_tokens = _serve_setup(tiny)
    params = jax.jit(lambda k: lm_lib.init_params(cfg, k))(
        jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (n_requests, prompt_len),
                           dtype=np.int32)
    bs = 8
    pages = -(-(prompt_len + new_tokens) // bs)
    sc = ServeConfig(max_prompt=prompt_len, eos_id=-1,
                     max_new_tokens=new_tokens, block_size=bs,
                     n_blocks=slots * pages + 1, max_slots=slots,
                     prefill_chunk=min(16, prompt_len),
                     prefill_batch=min(4, slots))
    eng = ContinuousEngine(cfg, params, sc)
    kernel_check = []

    def workload(check_kernel: bool):
        eng.reset()
        for i, p in enumerate(prompts):
            eng.submit(p, new_tokens, arrival=i // 2)
        while not eng.sched.idle:
            eng.step()
            if (check_kernel and not kernel_check
                    and len(eng.sched.decoding()) == slots):
                kernel_check.append(timed(_check_decode_kernel, cfg, eng))
        return dict(eng.results)

    _, cold = timed(workload, True)
    res, warm = timed(workload, False)
    require(kernel_check, "no engine step had every slot decoding")
    kernel_line, kernel_s = kernel_check[0]
    cold -= kernel_s
    require(len(res) == n_requests, f"{len(res)} of {n_requests} returned")
    for rid, toks in res.items():
        require(len(toks) == new_tokens,
                f"request {rid}: {len(toks)} tokens, budget {new_tokens}")
        require(((toks >= 0) & (toks < cfg.vocab_size)).all(),
                f"request {rid}: token ids outside [0, {cfg.vocab_size})")
    done = sum(len(t) for t in res.values())
    report("serve", f"{n_requests} requests x {prompt_len}-token prompts, "
           f"{done} tokens, each its full budget of {new_tokens} ids in "
           f"[0, {cfg.vocab_size}); {eng.stats['steps']} engine steps",
           cold, warm)
    print(f"[serve] decode kernel vs _sdpa on one step's gathered K/V "
          f"{kernel_line}; {kernel_s:.2f}s with compilation", flush=True)

    if jax.devices()[0].platform == "tpu":
        td = jnp.zeros((slots, 2), jnp.int32)
        hlo = eng._decode.lower(params, td, eng.cache.pool,
                                eng._gather()).compile().as_text()
        require("tpu_custom_call" in hlo,
                "compiled decode step has no tpu_custom_call (Pallas kernel)")
        print("[serve] compiled decode step holds the Pallas decode kernel "
              "(tpu_custom_call)", flush=True)

    _check_prefill_logits(cfg, params, prompts, res, prompt_len)


def _check_decode_kernel(cfg, eng) -> str:
    """Pallas decode kernel vs dense float32 ``_sdpa`` on the K/V the
    engine's last decode step attended (layer 0, every slot live)."""
    pos = np.full((eng.sc.max_slots,), -1, np.int32)
    for req in eng.sched.decoding():
        pos[req.slot] = req.prompt_len + len(req.tokens) - 2  # last written
    gather = eng._gather()
    pool = eng.cache.pool["main"]
    kg = jnp.take(pool["k"][0], gather, axis=0)           # (B, S, KH, D)
    vg = jnp.take(pool["v"][0], gather, axis=0)
    mask = cache_lib.paged_valid_mask(jnp.asarray(pos), eng.s_max)
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (eng.sc.max_slots, 1, cfg.n_heads, cfg.head_dim),
                          kg.dtype)
    got = decode_attention(q, kg, vg, mask, n_kv_heads=cfg.n_kv_heads)
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)
        want = _sdpa(f32(q), f32(kg), f32(vg),
                     mask[:, None, None, None, :], cfg.n_kv_heads)
    got, want = np.asarray(got, np.float32), np.asarray(want)
    np.testing.assert_allclose(got, want, atol=KERNEL_TOL, rtol=KERNEL_TOL)
    return (f"(B={kg.shape[0]}, S={kg.shape[1]}, KH={kg.shape[2]}, "
            f"D={kg.shape[3]}, {kg.dtype}): max|diff| "
            f"{float(np.abs(got - want).max()):.2e} <= {KERNEL_TOL}")


def _check_prefill_logits(cfg, params, prompts, res, prompt_len) -> None:
    """Serving-dtype prefill logits and the engine's first greedy tokens
    vs a plain float32 reference: dense attention, no Pallas kernel,
    ``precision="highest"``."""
    cfg32 = dataclasses.replace(cfg, dtype="float32")

    def last_logits(c):
        fn = jax.jit(lambda p, t: lm_lib.prefill(p, c, {"tokens": t},
                                                 prompt_len)[0][:, 0])
        return fn(params, jnp.asarray(prompts))

    got = np.asarray(last_logits(cfg), np.float32)[:, :cfg.vocab_size]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(last_logits(cfg32))[:, :cfg.vocab_size]
    tol = LOGIT_TOL_REL * float(np.abs(want).max())
    diff = float(np.abs(got - want).max())
    require(diff <= tol, f"prefill logits max|diff| {diff} > {tol}")
    top2 = np.sort(want, axis=1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > tol
    first = np.array([res[rid][0] for rid in sorted(res)])
    ref_first = want.argmax(axis=1)
    bad = np.nonzero(decided & (first != ref_first))[0]
    require(bad.size == 0, f"first greedy token differs from the reference "
                           f"for requests {bad.tolist()} whose top-two "
                           f"margin exceeds {tol}")
    print(f"[serve] prefill logits ({cfg.dtype}) vs float32 reference: "
          f"max|diff| {diff:.3e} <= {tol:.3e} ({LOGIT_TOL_REL} x max|ref| "
          f"{float(np.abs(want).max()):.3e}); first token agrees on "
          f"{int(decided.sum())} of {len(first)} requests whose margin "
          f"exceeds the tolerance ({int((first == ref_first).sum())} of "
          f"{len(first)} agree in all)", flush=True)


# --------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------- #

LOWERINGS = [("allreduce", a) for a in ("xla_native", "ring",
                                        "recursive_doubling", "hierarchical",
                                        "bf16_wire")]
LOWERINGS += [("bcast", "tree"), ("alltoall", "pairwise")]


def phase_lowerings(mesh, sizes) -> None:
    """Each lowering on the 2x2 mesh; alltoall, which takes a one-axis
    communicator, on a 4-rank line over the same devices."""
    n = mesh.size
    line = jax.sharding.Mesh(mesh.devices.reshape(-1), ("ranks",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    for nbytes in sizes:
        k = nbytes // 4
        data = np.random.default_rng(nbytes).standard_normal(
            (n, k)).astype(np.float32)
        for op, algo in LOWERINGS:
            m = line if op == "alltoall" else mesh
            spec = P(tuple(m.axis_names))
            x = jax.device_put(data, NamedSharding(m, spec))

            @jmpi.spmd(m, in_specs=spec, out_specs=spec)
            def run(v, op=op, algo=algo):
                comm = jmpi.world()
                v = v[0]
                if op == "allreduce":
                    _, out = comm.allreduce(v, algorithm=algo)
                elif op == "bcast":
                    _, out = comm.bcast(v, root=0, algorithm=algo)
                else:
                    _, out = comm.alltoall(v.reshape(n, -1), algorithm=algo)
                    out = out.reshape(-1)
                return out[None]

            _, cold = timed(run, x)
            got, warm = timed(run, x)
            if op == "allreduce":
                want = np.broadcast_to(data.sum(0), data.shape)
            elif op == "bcast":
                want = np.broadcast_to(data[0], data.shape)
            else:
                want = data.reshape(n, n, -1).transpose(1, 0, 2).reshape(n, k)
            tol = (dict(rtol=0.1, atol=0.1 * n) if algo == "bf16_wire"
                   else dict(rtol=5e-5, atol=1e-5))
            np.testing.assert_allclose(np.asarray(got), want, **tol)
            devs = sorted(d.id for d in got.sharding.device_set)
            require(len(devs) == n, f"{op}/{algo} result on devices {devs}")
            report("lowering", f"{op}/{algo} {nbytes} B per rank matches "
                   f"numpy (rtol {tol['rtol']}, atol {tol['atol']}); result "
                   f"on devices {devs}", cold, warm)


# --------------------------------------------------------------------- #

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tiny sizes, on whatever platform JAX finds")
    ap.add_argument("--four-chips", action="store_true",
                    help="only the multi-chip phases, on four devices")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit(f"chip_smoke: no TPU; JAX found platform {dev.platform!r} "
                 f"({dev.device_kind}). Use --tiny to rehearse there.")
    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {use_compile_cache()}", flush=True)

    if args.four_chips:
        require(len(jax.devices()) == 4,
                f"--four-chips needs 4 devices, found {len(jax.devices())}")
        mesh = compat.make_mesh((2, 2), ("x", "y"))
        print(f"[mesh] 2x2 devices {[d.id for d in mesh.devices.flat]}",
              flush=True)
        phase_mpdata(256 if args.tiny else 16384, mesh)
        phase_lowerings(mesh, (1024, 64 * 1024 if args.tiny else 4 << 20))
    else:
        phase_pi(10_000 if args.tiny else 1_000_000)
        phase_mpdata(64 if args.tiny else 8192,
                     compat.make_mesh((1, 1), ("x", "y")))
        phase_serve(args.tiny)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
