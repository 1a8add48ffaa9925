"""decode.step_mfu: model FLOPs of the traced decode steps over their
device time times the chip's bf16 peak.  FLOPs per live slot are
``costs.decoder_token_flops`` at its position, head included (every
decoded token's logits are used); idle slots do no model work.  Device
time is the summed duration of the decode program's runs (XLA modules
whose name holds ``info['decode_program']``)."""

from chipbench import trace_reduce
from chipbench.metrics.costs import decoder_token_flops


def read(red, info, peaks):
    name = info["decode_program"]
    runs = trace_reduce.module_runs(red, lambda n: name in n)
    busy = sum(e - s for _, _, s, e in runs) / 1e9
    if busy <= 0 or not info["decode_steps"]:
        return None
    m = info["model"]
    flops = sum(decoder_token_flops(m, c - 1, True)
                for ctxs in info["decode_steps"] for c in ctxs)
    return 100.0 * flops / (busy * peaks["bf16_flops_per_s"])
