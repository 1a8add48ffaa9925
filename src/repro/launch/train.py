"""Training launcher: ``--arch <id>`` selects an assigned architecture.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 50 \
        [--tiny] [--ranks 4] [--microbatch 2] [--ckpt DIR] [--comm jmpi]

``--tiny`` (default) runs the reduced config on host devices; without it the
full config is used (sized for real accelerators — on CPU it is only
feasible via the dry-run).  Fault tolerance is on: watchdog + periodic async
checkpoints + resume-from-latest.
"""

import argparse
import os


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--full", dest="tiny", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ranks", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compression", type=int, default=0,
                    choices=[0, 8, 16])
    ap.add_argument("--comm", default="gspmd", choices=["gspmd", "jmpi"])
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    return ap.parse_args()


def main():
    args = parse_args()
    if args.ranks > 1 and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={args.ranks}"

    import jax
    import jax.numpy as jnp

    import repro.core as jmpi
    from repro.configs import get_config, get_tiny
    from repro.configs.base import RunConfig, ShapeCell
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm as lm_lib
    from repro.train import checkpoint as ckpt
    from repro.train import optim
    from repro.train.data import SyntheticLM
    from repro.train.ft import Watchdog
    from repro.train.trainer import build_jmpi_train_step, build_train_step

    use_compile_cache()
    cfg = get_tiny(args.arch) if args.tiny else get_config(args.arch)
    rc = RunConfig(learning_rate=args.lr, microbatch=args.microbatch,
                   grad_compression_bits=args.grad_compression,
                   comm_backend=args.comm)
    mesh = make_host_mesh(args.ranks, axes=("data",))
    cell = ShapeCell("cli", args.seq, args.batch, "train")

    params = lm_lib.init_params(cfg, jax.random.PRNGKey(rc.seed))
    opt = optim.init(params, rc)
    data = SyntheticLM(cfg, args.batch, args.seq, seed=rc.seed)
    wd = Watchdog()
    saver = ckpt.AsyncSaver()

    start = 0
    if args.ckpt:
        latest = ckpt.latest_step(args.ckpt)
        if latest is not None:
            (params, opt), start, _ = ckpt.restore(args.ckpt, (params, opt))
            start += 1
            print(f"[train] resumed from step {start}")

    if args.comm == "jmpi":
        step = build_jmpi_train_step(cfg, rc, mesh, None)
        comp = jax.tree.map(lambda p: jmpi.init_state(p), params)
    else:
        step = build_train_step(cfg, rc, mesh, cell).jitted()

    import time
    for i in range(start, args.steps):
        t0 = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        if args.comm == "jmpi":
            params, opt, comp, loss = step(params, opt, comp, batch)
            loss_v = float(loss)
        else:
            params, opt, metrics = step(params, opt, batch)
            loss_v = float(metrics["loss"])
        dt = time.perf_counter() - t0
        if wd.observe(i, dt):
            print(f"[train] straggler flagged at step {i} ({dt:.2f}s)")
        if i % 10 == 0 or i == args.steps - 1:
            print(f"[train] step {i:4d} loss={loss_v:.4f} ({dt*1e3:.0f} ms)")
        if args.ckpt and i % args.ckpt_every == args.ckpt_every - 1:
            saver.save_async(args.ckpt, (params, opt), i)
    saver.wait()
    if args.ckpt:
        ckpt.save(args.ckpt, (params, opt), args.steps - 1)
    print("[train] done")


if __name__ == "__main__":
    main()
