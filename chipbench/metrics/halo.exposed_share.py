"""halo.exposed_share: the share of the traced window in which a
collective operation (collective-permute and the like, ``trace_reduce.
is_collective``) runs on a chip while no other operation runs there,
averaged over the chips.  Reads only the trace's device operations; a
trace with no collective reads nothing."""

from chipbench import trace_reduce


def read(red, info, peaks):
    if not trace_reduce.op_seconds(red, trace_reduce.is_collective):
        return None
    return 100.0 * trace_reduce.exposed_collective_s(red) / red.window_s
