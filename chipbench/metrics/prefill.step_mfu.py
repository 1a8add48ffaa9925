"""prefill.step_mfu: model FLOPs of the traced prefill chunks over their
device time times the chip's bf16 peak.  FLOPs are counted over the real
prompt rows of each chunk, not its padding (``costs.decoder_token_flops``
at each row's position), with the output head only for the row whose
logits give a prompt's first token.  Device time is the summed duration
of the prefill program's runs (XLA modules whose name holds
``info['prefill_program']``)."""

from chipbench import trace_reduce
from chipbench.metrics.costs import decoder_token_flops


def read(red, info, peaks):
    name = info["prefill_program"]
    runs = trace_reduce.module_runs(red, lambda n: name in n)
    busy = sum(e - s for _, _, s, e in runs) / 1e9
    if busy <= 0 or not info["prefill_steps"]:
        return None
    m = info["model"]
    flops = 0
    for rows in info["prefill_steps"]:
        for c0, real, done in rows:
            flops += sum(decoder_token_flops(m, p, False)
                         for p in range(c0, c0 + real))
            if done:
                flops += 2 * m["d_model"] * m["vocab_size"]
    return 100.0 * flops / (busy * peaks["bf16_flops_per_s"])
