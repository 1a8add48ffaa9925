"""Driver for the serving engine: ``repro.serve.engine.ContinuousEngine``.

Set-up makes the weights from the seed (``refs/llama.make_params``, one
jitted call on the device), builds the engine with the configuration's
``serving`` settings, and pushes a few requests through it so that its
prefill and decode programs compile (the only two shapes it runs).

Traffic is open-loop (``traffic/open_loop.py``): requests are submitted at
their due time on the wall clock, whatever the engine is doing, at the
cell's fixed rate.  A ramp of traffic runs before the window, so that the
window opens in steady state.  Each ``engine.step()`` runs in an
``engine.step`` host span.  Every token is stamped with the host time at
which its step returned it.

- ``itl_p95_ms``: over every gap between consecutive tokens of a request
  whose later token arrived in the window.
- ``tokens_per_s``: tokens that arrived in the window over its length.
- TTFT p95 (printed on standard error, and read by ``calibrate.py
  sweep``; not a metric of ``BENCHMARK.json``): over every request due in
  the window, first token's host time minus the time it was due.  After
  the window closes the engine keeps serving (arrivals go on) until each
  of those requests has its first token, at most ``grace_s``; one that
  never gets it counts as failed, with its wait so far as its latency.

What decides ``correct``: once serving is over and the engine freed, a
sample of finished requests drawn from the seed (the longest among them,
then more until ``check.min_tokens`` served tokens and
``check.min_requests`` requests) is run through the
plain float32 reference (``refs/llama.py``) over prompt and served
tokens, and the widest gap by which a served token's reference logit lies
below the reference's best must stay within the cell's limit.  The first
token of each request comes from the prefill program and the rest from
the decode program, through the paged cache and (on the TPU) the Pallas
decode kernel, so both are covered.
"""

from __future__ import annotations

import dataclasses
import sys
import time

import jax
import numpy as np

from chipbench import harness
from chipbench.stats import quantile
from chipbench.traffic import open_loop

HF_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


def model_sizes(config: dict, rehearse: bool) -> tuple:
    """(model dict in the reference's names, serving dict, length scale)."""
    c = dict(config)
    scale = 1.0
    if rehearse:
        c.update(config["rehearsal"])
        scale = float(config["rehearsal"]["length_scale"])
    m = {ours: c[hf] for hf, ours in HF_KEYS.items()}
    return m, dict(c["serving"]), scale


def scaled_mix(mix: dict, scale: float, max_new: int) -> dict:
    """The mix cut to a rehearsal's sizes: prompt lengths times ``scale``,
    answer lengths so that the longest fits ``max_new`` tokens."""
    if scale == 1.0:
        return mix
    out = dict(mix)
    for part, f in (("prompt", scale),
                    ("output", min(1.0, max_new / mix["output"]["max"]))):
        spec = dict(mix[part])
        for k in ("median", "min", "max"):
            if k in spec:
                spec[k] = max(1, int(round(spec[k] * f)))
        out[part] = spec
    return out


def program_config(m: dict, config: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], dtype=config["compute_dtype"],
        param_dtype=config["param_dtype"])


@dataclasses.dataclass
class Track:
    """One request as the client sees it."""

    prompt: np.ndarray
    output_len: int
    due: float                 # absolute host time
    in_window: bool
    req: object = None         # the engine's Request
    times: list = dataclasses.field(default_factory=list)


class StepLog:
    """What each traced engine step did, for the per-layer readers:
    per decode dispatch the live context of each slot, per prefill
    dispatch (chunk start, real rows, prompt done) of each row."""

    def __init__(self):
        self.decode, self.prefill = [], []

    def before(self, tracks):
        return {id(t): (t.req.cursor, len(t.req.tokens)) for t in tracks}

    def after(self, eng, tracks, snap, stats0):
        pre_rows, dec_ctx = [], []
        for t in tracks:
            cur0, n0 = snap[id(t)]
            r = t.req
            if r.cursor > cur0:
                pre_rows.append((cur0, r.cursor - cur0,
                                 r.cursor == r.prompt_len))
            n_dec = len(r.tokens) - n0 - (r.cursor == r.prompt_len
                                          and cur0 < r.prompt_len)
            if n_dec > 0:
                k = len(r.tokens) - 1          # tokens before this decode
                dec_ctx.append(r.prompt_len + k)
        if eng.stats["prefill_chunks"] > stats0[0]:
            self.prefill.append(pre_rows)
        if eng.stats["decode_steps"] > stats0[1]:
            self.decode.append(dec_ctx)


def _warm(eng, vocab: int, serving: dict) -> None:
    """Compile the engine's two programs on a few short requests."""
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng.submit(rng.integers(0, vocab, serving["prefill_chunk"] // 2,
                                dtype=np.int32), 3)
    while not eng.sched.idle:
        eng.step()
    eng.reset()


def setup(ctx, seed: int):
    """(model sizes, serving dict, length scale, params, engine), warm."""
    from repro.serve.engine import ContinuousEngine, ServeConfig
    from chipbench.refs import llama

    m, serving, scale = model_sizes(ctx.cell.config, ctx.rehearse)
    params = llama.make_params(m, seed)
    eng = ContinuousEngine(program_config(m, ctx.cell.config), params,
                           ServeConfig(eos_id=-1, **serving))
    _warm(eng, m["vocab_size"], serving)
    return m, serving, scale, params, eng


def ramp_s(ctx) -> float:
    """Seconds of traffic before the window (1 s in a rehearsal)."""
    return 1.0 if ctx.rehearse else float(ctx.cell.workload["ramp_s"])


def rate_rps(ctx) -> float:
    """The cell's offered rate; a rehearsal's own, which keeps its few
    slots busy."""
    if ctx.rehearse:
        return float(ctx.cell.config["rehearsal"]["rate_rps"])
    return float(ctx.cell.workload["rate_rps"])


def traffic(ctx, m: dict, scale: float, rate: float, seconds: float,
            seed: int) -> tuple:
    """(schedule, prompts) of the cell's mix at ``rate``; the prompts'
    token ids come from ``seed``."""
    wl = ctx.cell.workload
    rng = np.random.default_rng([seed, 1])
    mix = ctx.cell.traffic
    if ctx.rehearse:
        mix = scaled_mix(mix, scale, int(ctx.cell.config["rehearsal"][
            "serving"]["max_new_tokens"]))
    plan = open_loop.schedule(mix, rate, ramp_s(ctx), seconds,
                              float(wl["tail_s"]))
    prompts = [rng.integers(0, m["vocab_size"], a.prompt_len, dtype=np.int32)
               for a in plan]
    return plan, prompts


@dataclasses.dataclass
class Served:
    """One open-loop session: every request's track and the window."""

    tracks: dict
    w0: float
    w1: float
    end: float
    steps: int
    lateness: dict
    log: StepLog
    queued: tuple             # requests waiting at window open and close
    longest_step_s: float     # the longest loop step on the host clock

    def end_to_end(self) -> dict:
        win = [tr for tr in self.tracks.values() if tr.in_window]
        ttft = [(tr.times[0] if tr.times else self.end) - tr.due
                for tr in win]
        itl = [b - a for tr in self.tracks.values()
               for a, b in zip(tr.times, tr.times[1:])
               if self.w0 <= b < self.w1]
        n_tok = sum(1 for tr in self.tracks.values() for x in tr.times
                    if self.w0 <= x < self.w1)
        return {"ttft_p95_ms": 1e3 * quantile(ttft, 0.95),
                "itl_p95_ms": 1e3 * quantile(itl, 0.95),
                "tokens_per_s": n_tok / (self.w1 - self.w0)}

    @property
    def window_requests(self) -> list:
        return [tr for tr in self.tracks.values() if tr.in_window]


def serve(eng, plan, prompts, ramp_s: float, seconds: float,
          grace_s: float, ctx=None) -> Served:
    """Drive ``eng`` open-loop through ``plan`` (see the module docstring).
    With ``ctx``, the window opens through ``ctx.begin_window`` and its
    tracer traces the first ``trace_seconds``, logging the traced steps."""
    t0 = time.perf_counter()                         # traffic starts
    w0 = t0 + ramp_s
    w1 = w0 + seconds
    grace_end = w1 + grace_s
    tracks: dict[int, Track] = {}
    submitted, nxt, window_open, log = [], 0, False, StepLog()
    tracing, steps0, queued = False, eng.stats["steps"], []
    clock = ctx.clock if ctx is not None else harness.StepClock()
    while True:
        t = time.perf_counter()
        if len(queued) == 1 and t >= w1:
            queued.append(len(eng.sched.queue))
        if not window_open and t >= w0:
            window_open = True
            queued.append(len(eng.sched.queue))
            if ctx is not None:
                ctx.begin_window()
                tracing = ctx.tracer.active
        with clock.step():                        # submissions
            while nxt < len(plan) and t0 + plan[nxt].due <= t:
                a = plan[nxt]
                rid = eng.submit(prompts[nxt], a.output_len)
                tracks[rid] = Track(prompts[nxt], a.output_len, t0 + a.due,
                                    a.in_window, eng.sched.queue[-1])
                submitted.append((t, t0 + a.due))
                nxt += 1
        if t >= w1 and (t >= grace_end or all(
                tr.times for tr in tracks.values() if tr.in_window)):
            break
        if tracing and t >= w0 + ctx.tracer.seconds:
            ctx.tracer.stop()
            tracing = False
        if eng.sched.idle:
            if nxt < len(plan):
                time.sleep(max(0.0, min(t0 + plan[nxt].due - t, 0.002)))
            continue
        if tracing:
            live = [tr for tr in tracks.values() if tr.req.state != "done"]
            snap = log.before(live)
            stats0 = (eng.stats["prefill_chunks"], eng.stats["decode_steps"])
        with clock.step(), jax.profiler.TraceAnnotation("engine.step"):
            emitted = eng.step()
        te = time.perf_counter()
        for rid, _ in emitted:
            tracks[rid].times.append(te)
        if tracing:
            log.after(eng, live, snap, stats0)
    if ctx is not None:
        ctx.end_window()
    late = open_loop.lateness([s for s, _ in submitted],
                              [d for _, d in submitted])
    return Served(tracks, w0, w1, time.perf_counter(),
                  eng.stats["steps"] - steps0, late, log, tuple(queued),
                  clock.longest_s)


def run(ctx) -> harness.Outcome:
    wl = ctx.cell.workload
    m, serving, scale, params, eng = setup(ctx, ctx.seed)
    plan, prompts = traffic(ctx, m, scale, rate_rps(ctx), ctx.seconds,
                            ctx.seed)
    got = serve(eng, plan, prompts, ramp_s(ctx), ctx.seconds,
                float(wl["grace_s"]), ctx)
    peak = harness.memory_peak_bytes(ctx.devices)
    e2e = dict(got.end_to_end(), setup_s=ctx.setup_s)
    print(f"[serve] generator lateness over {got.lateness['n']} requests: "
          f"median {got.lateness['p50'] * 1e3:.3f} ms, max "
          f"{got.lateness['max'] * 1e3:.3f} ms; {got.steps} engine steps, "
          f"the longest {got.longest_step_s * 1e3:.3f} ms; TTFT p95 "
          f"{e2e['ttft_p95_ms']:.3f} ms", file=sys.stderr, flush=True)
    win = got.window_requests
    failed = sum(1 for tr in win if not tr.times)

    done = {rid: np.asarray(eng.results[rid]) for rid in eng.results}
    info = {"memory_peak_bytes": peak, "model": m, "serving": serving,
            "decode_steps": got.log.decode, "prefill_steps": got.log.prefill,
            "decode_kernel": "decode_attention",
            "decode_program": "_decode_fn", "prefill_program": "_prefill_fn"}
    eng.cache.pool = None
    del eng
    checks = check(params, m, done, got.tracks, wl, scale, ctx.seed)
    return harness.Outcome(attempted=len(win), failed=failed,
                           end_to_end=e2e, checks=checks, info=info)


def check(params, m, done, tracks, wl, scale, seed, quant=None) -> list:
    """The checks that decide ``correct`` (see the module docstring)."""
    sample = _sample(done, tracks, wl["check"], scale, seed)
    gap, n_checked, bad_ids = _check(params, m, sample, tracks, done, quant)
    want = max(1, round(int(wl["check"]["min_tokens"]) * scale))
    print(f"[serve] checked {len(sample)} requests, {n_checked} served "
          f"tokens", file=sys.stderr, flush=True)
    return [("max_logit_gap", gap, float(wl["limits"]["max_logit_gap"])),
            ("tokens_checked_short", max(0, want - n_checked), 0),
            ("token_ids_out_of_range", bad_ids, 0)]


def _sample(done: dict, tracks: dict, check: dict, scale: float,
            seed: int) -> list:
    """Finished requests drawn from the seed: the one with the longest
    served sequence, then others until both ``min_tokens`` served tokens
    and ``min_requests`` requests (a fault confined to some slots then
    shows in one of them), at most ``max_requests``."""
    if not done:
        return []
    size = lambda rid: len(tracks[rid].prompt) + len(done[rid])
    longest = max(done, key=size)
    rest = [r for r in sorted(done) if r != longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, n = [longest], len(done[longest])
    for i in order:
        if len(out) >= int(check["max_requests"]) or (
                n >= int(check["min_tokens"]) * scale
                and len(out) >= int(check["min_requests"])):
            break
        out.append(rest[i])
        n += len(done[rest[i]])
    return out


def _check(params, m, sample, tracks, done, quant=None) -> tuple:
    """(widest gap, served tokens compared, token ids out of range); with
    ``quant``, the gap of the control's tokens instead of the served."""
    from chipbench.refs import llama
    bad = sum(int(((done[r] < 0) | (done[r] >= m["vocab_size"])).sum())
              for r in sample)
    if bad or not sample:
        return None, 0, bad
    seqs = [(tracks[r].prompt, done[r]) for r in sample]
    gaps = llama.served_gaps(params, m, seqs, quant=quant)
    return float(max(g.max() for g in gaps)), sum(len(g) for g in gaps), 0
