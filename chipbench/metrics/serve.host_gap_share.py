"""serve.host_gap_share: device idle time inside the benchmark's
``engine.step`` host spans, over the traced window.  That is idle caused
by the engine's host loop (admission, block tables, the token fetch), not
by a lack of requests: time between steps is left out."""

from chipbench import trace_reduce


def read(red, info, peaks):
    if not red.spans.get("engine.step") or not red.devices:
        return None
    return 100.0 * trace_reduce.idle_inside(red, "engine.step") / red.window_s
