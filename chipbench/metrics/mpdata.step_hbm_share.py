"""mpdata.step_hbm_share: the least time an MPDATA step could take on the
chip's HBM (the field read once and written once, ``costs.
mpdata_step_bytes`` of each chip's block) over the device time each step
took: the busy time of the traced window per chip, over the time steps
run in it.  Reads the trace's device operations and ``drivers/pde.py``'s count of
traced steps, grid and mesh."""

from chipbench import trace_reduce
from chipbench.metrics.costs import mpdata_step_bytes


def read(red, info, peaks):
    steps = info.get("traced_steps", 0)
    busy = trace_reduce.busy_s(red)
    if not steps or busy <= 0:
        return None
    rows, cols = info["mesh"]
    n = info["grid"]
    least = mpdata_step_bytes(n // rows, n // cols, info["bytes_per_cell"]
                              ) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / (busy / steps)
