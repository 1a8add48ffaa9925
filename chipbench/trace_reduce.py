"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

``reduce(path)`` gives, on one clock (the host's, in nanoseconds):

- ``window``: the ``bench.window`` host span that the benchmark wraps
  around the traced part of its measured window;
- ``devices``: per device, the operations that ran on it (name, start,
  end) and the programs (XLA modules) they belong to;
- ``spans``: the benchmark's host spans by name (``engine.step``,
  ``pde.run_block``, ...), written with ``jax.profiler.TraceAnnotation``.

On a TPU the device planes are ``/device:TPU:<n>`` with an ``XLA Ops``
and an ``XLA Modules`` line.  A CPU trace has no device plane: its XLA
operations run on host threads and carry an ``hlo_op`` stat, so there the
host plane stands in for one device (used only by rehearsals and tests).

Interval arithmetic (union, clipping, gaps) is kept here so that every
metric computes busy and idle time the same way.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
COLLECTIVE_MARKS = ("collective-permute", "all-reduce", "all-gather",
                    "all-to-all", "reduce-scatter", "collective-broadcast",
                    "send", "recv")


@dataclasses.dataclass
class Device:
    """What ran on one device: ``ops`` and ``modules`` as (name, s, e).

    ``leaves`` are the ops that enclose no other op: a control-flow op
    (``while``, ``conditional``) spans the ops of its body, which the trace
    lists too, so everything that asks what ran when (time per op, busy
    time, collectives exposed) takes the leaves alone.  Else a ``while``
    would count the gaps between its body's ops as busy, and hide every
    collective inside it behind itself."""

    name: str
    ops: list
    modules: list

    @property
    def leaves(self) -> list:
        return leaf_ops(self.ops)


@dataclasses.dataclass
class Reduced:
    """A trace reduced to a window, device timelines and host spans."""

    window: tuple
    devices: list
    spans: dict

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


# --------------------------------------------------------------------- #
# interval arithmetic (nanoseconds, half-open)
# --------------------------------------------------------------------- #

def union(intervals) -> list:
    """Sorted, merged (s, e) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    """Each interval cut to [lo, hi); empty ones dropped."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    """Summed length of already-merged intervals."""
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi) -> list:
    """The idle intervals in [lo, hi) around merged ``busy`` intervals."""
    return subtract([(lo, hi)], busy)


# --------------------------------------------------------------------- #
# reading the trace
# --------------------------------------------------------------------- #

def find_xplane(trace_dir: str) -> str:
    """Newest ``.xplane.pb`` under a profiler log directory."""
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """``fusion.41`` from a TPU op event's HLO text ``%fusion.41 = ...``."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def leaf_ops(ops) -> list:
    """The ops of one device that enclose no other op."""
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(order)
    stack = []                                  # indices of open ops
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and s < e <= order[stack[-1]][2] and (s, e) != (
                order[stack[-1]][1], order[stack[-1]][2]):
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(order, parent) if not p]


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def reduce(path: str, window=None) -> Reduced:
    """Read one ``.xplane.pb`` and reduce it (see the module docstring).

    ``window`` (start, end) in the trace's nanoseconds stands in for the
    ``bench.window`` span, for traces recorded without one."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], collections.defaultdict(list)
    host_ops = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                evs = [(short_name(e.name), e.start_ns,
                        e.start_ns + e.duration_ns) for e in line.events]
                if line.name == "XLA Ops":
                    ops = evs
                elif line.name == "XLA Modules":
                    modules = evs
            if ops or modules:
                devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    spans[e.name].append((e.start_ns, end))
                    if line.name != "python":
                        st = _stats(e)
                        if "hlo_op" in st:
                            host_ops.append((e.name, e.start_ns, end,
                                             st.get("hlo_module", "?"),
                                             st.get("run_id", 0)))
    if not devices and host_ops:
        devices.append(_host_device(host_ops))
    if window is None:
        if not spans.get(WINDOW_SPAN):
            raise ValueError(f"trace {path} has no {WINDOW_SPAN!r} span")
        window = max(spans[WINDOW_SPAN], key=lambda se: se[1] - se[0])
    return Reduced(window=window, devices=devices, spans=dict(spans))


def _host_device(host_ops) -> Device:
    """A CPU trace's XLA operations as one device; each program run is the
    extent of its operations."""
    runs = {}
    for _, s, e, mod, run in host_ops:
        lo, hi = runs.get((mod, run), (s, e))
        runs[(mod, run)] = (min(lo, s), max(hi, e))
    modules = [(mod, s, e) for (mod, _), (s, e) in runs.items()]
    return Device("/host:CPU", [o[:3] for o in host_ops], modules)


# --------------------------------------------------------------------- #
# what the metrics read
# --------------------------------------------------------------------- #

def is_collective(name: str) -> bool:
    n = name.lower()
    return any(m in n for m in COLLECTIVE_MARKS)


def busy(dev: Device, lo, hi) -> list:
    """Merged intervals of [lo, hi) in which some leaf operation ran."""
    return union(clip([(s, e) for _, s, e in dev.leaves], lo, hi))


def busy_s(red: Reduced) -> float:
    """Seconds of the window in which an operation ran, averaged over the
    devices."""
    lo, hi = red.window
    if not red.devices:
        return 0.0
    return sum(total(busy(d, lo, hi)) for d in red.devices) / (
        1e9 * len(red.devices))


def op_seconds(red: Reduced, match=None) -> dict:
    """{op name: seconds} inside the window, summed over devices, for the
    leaf operations whose name ``match`` accepts (all when None)."""
    lo, hi = red.window
    out = collections.defaultdict(float)
    for d in red.devices:
        for name, s, e in d.leaves:
            if match is None or match(name):
                for cs, ce in clip([(s, e)], lo, hi):
                    out[name] += (ce - cs) / 1e9
    return dict(out)


def module_runs(red: Reduced, match) -> list:
    """[(device, name, s, e)] of program runs whose name ``match`` accepts,
    with their start inside the window."""
    lo, hi = red.window
    return [(d.name, n, s, e) for d in red.devices for n, s, e in d.modules
            if match(n) and lo <= s < hi]


def exposed_collective_s(red: Reduced) -> float:
    """Seconds of the window in which a collective operation runs on a
    device while no other leaf operation runs there, averaged over
    devices."""
    lo, hi = red.window
    if not red.devices:
        return 0.0
    out = 0.0
    for d in red.devices:
        leaves = d.leaves
        coll = union(clip([(s, e) for n, s, e in leaves if is_collective(n)],
                          lo, hi))
        comp = union(clip([(s, e) for n, s, e in leaves
                           if not is_collective(n)], lo, hi))
        out += total(subtract(coll, comp))
    return out / (1e9 * len(red.devices))


def idle_inside(red: Reduced, span_name: str) -> float:
    """Device idle seconds inside the host spans named ``span_name`` (and
    inside the window), averaged over devices."""
    lo, hi = red.window
    spans = union(clip(red.spans.get(span_name, []), lo, hi))
    if not red.devices:
        return 0.0
    out = 0.0
    for d in red.devices:
        out += total(subtract(spans, busy(d, lo, hi)))
    return out / (1e9 * len(red.devices))


def device_ops_top(red: Reduced, n: int = 10) -> list:
    """The ``n`` operations that took most device time: [[name, s]]."""
    secs = op_seconds(red)
    k = max(len(red.devices), 1)
    top = sorted(secs.items(), key=lambda kv: -kv[1])[:n]
    return [[name, s / k] for name, s in top]


def idle_gaps_top(red: Reduced, n: int = 10,
                  span_names=("engine.step", "pde.run_block")) -> list:
    """The ``n`` longest idle gaps on the first device, each named by the
    benchmark host span it falls in ("between spans" where none)."""
    if not red.devices:
        return []
    lo, hi = red.window
    idle = gaps(busy(red.devices[0], lo, hi), lo, hi)
    host = sorted((s, e, name) for name in span_names
                  for s, e in red.spans.get(name, []))
    out = []
    for s, e in sorted(idle, key=lambda se: se[0] - se[1])[:n]:
        mid = (s + e) / 2
        label = next((name for hs, he, name in host if hs <= mid < he),
                     "between spans")
        out.append([label, (e - s) / 1e9])
    return out
