"""decode_attn_roofline: the Pallas decode-attention kernel's device time
against the least time its work needs on this chip.

The work is counted from each traced decode step's live slots
(``costs.decode_attn_cost``: K/V of each slot's live context plus q and
out, and 4 x heads x head size FLOPs per attended position), times the
layers; the least time is the larger of bytes over HBM bandwidth and
FLOPs over peak, and the bound that wins is named on standard error.
Today's kernel reads the gathered ``s_max``-wide K/V, so the live count is
the work any implementation needs.  Reads the trace's operations whose
name holds ``info['decode_kernel']`` and the step log of
``drivers/serve.py``."""

import sys

from chipbench import trace_reduce
from chipbench.metrics.costs import decode_attn_cost


def read(red, info, peaks):
    name = info["decode_kernel"]
    secs = trace_reduce.op_seconds(red, lambda n: name in n)
    kernel_s = sum(secs.values())
    steps = info["decode_steps"]
    if kernel_s <= 0 or not steps:
        return None
    m = info["model"]
    flops = byts = 0
    for ctxs in steps:
        f, b = decode_attn_cost(ctxs, m["n_heads"], m["n_kv_heads"],
                                m["head_dim"])
        flops += f * m["n_layers"]
        byts += b * m["n_layers"]
    t_bytes = byts / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    print(f"[metric] decode_attn_roofline: {len(steps)} decode steps, "
          f"kernel {kernel_s:.6f} s, bound by {bound} "
          f"({max(t_bytes, t_flops):.6f} s)", file=sys.stderr)
    return 100.0 * max(t_bytes, t_flops) / kernel_s
