"""Serving launcher: continuous batching over the paged KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-3-4b \
        --requests 8 --new-tokens 32 [--prompt-len 16] [--engine padded]

Drives :class:`repro.serve.engine.ContinuousEngine` on a mixed workload
(per-request budgets, staggered arrivals) and reports aggregate tokens/s
plus p50/p99 request latency; ``--engine padded`` runs the fixed-batch
baseline on the same prompts for an eyeball comparison.
"""

import argparse
import sys
import time


def main():
    """CLI entry point."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-3-4b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--engine", choices=("continuous", "padded"),
                    default="continuous")
    args = ap.parse_args()

    import jax
    import numpy as np

    from repro.configs import get_tiny
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import lm as lm_lib
    from repro.serve.engine import ContinuousEngine, Engine, ServeConfig

    use_compile_cache()

    cfg = get_tiny(args.arch)
    if cfg.embeds_input or cfg.n_img_tokens:
        sys.exit(f"{args.arch} needs modality frontend inputs; "
                 "pick a text arch for the CLI demo")
    params = lm_lib.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.requests, args.prompt_len), dtype=np.int32)

    if args.engine == "padded":
        eng = Engine(cfg, params,
                     ServeConfig(max_prompt=args.prompt_len + 8,
                                 max_new_tokens=args.new_tokens))
        eng.generate(prompts)                  # compile
        t0 = time.perf_counter()
        out = eng.generate(prompts)
        dt = time.perf_counter() - t0
        print(f"[serve/padded] {cfg.name}: {out.shape[0]}×{out.shape[1]} "
              f"tokens in {dt:.2f}s -> {out.size/dt:.0f} tok/s")
        print(out[: min(2, len(out))])
        return

    slots = min(8, args.requests)
    bs = 8
    max_seq = args.prompt_len + args.new_tokens
    pages = -(-max_seq // bs)
    sc = ServeConfig(max_prompt=args.prompt_len, eos_id=-1,
                     max_new_tokens=args.new_tokens, block_size=bs,
                     n_blocks=slots * pages + 1, max_slots=slots,
                     prefill_chunk=min(16, args.prompt_len),
                     prefill_batch=min(4, slots))
    eng = ContinuousEngine(cfg, params, sc)

    def workload():
        eng.reset()
        # mixed budgets + two arrivals per step: the traffic shape
        # continuous batching exists for
        wrng = np.random.default_rng(1)
        for i, p in enumerate(prompts):
            mnt = int(wrng.integers(max(1, args.new_tokens // 4),
                                    args.new_tokens + 1))
            eng.submit(p, mnt, arrival=i // 2)
        return eng.run()

    workload()                                 # compile
    t0 = time.perf_counter()
    res = workload()
    dt = time.perf_counter() - t0
    done = sum(len(v) for v in res.values())
    lat = np.sort(np.array(list(eng.latency.values()))) * 1e3
    print(f"[serve/continuous] {cfg.name}: {len(res)} requests, {done} "
          f"tokens in {dt:.2f}s -> {done/dt:.0f} tok/s "
          f"(p50 {np.percentile(lat, 50):.0f}ms, "
          f"p99 {np.percentile(lat, 99):.0f}ms; steps={eng.stats['steps']}, "
          f"peak_active={eng.stats['peak_active']})")
    for rid in sorted(res)[:2]:
        print(f"  rid {rid}: {[int(t) for t in res[rid][:12]]}"
              f"{' ...' if len(res[rid]) > 12 else ''}")


if __name__ == "__main__":
    main()
