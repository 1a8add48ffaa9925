"""What every cell shares: finding its files, the chip, the compile cache,
the trace, the per-layer readers and the result line.

A cell is found by name.  Its entry in ``BENCHMARK.json`` names a
configuration and a traffic mix; the harness then reads

- ``chipbench/configs/<config>.json``: sizes, the driver, the deployment;
- ``chipbench/traffic/<traffic>.json``: the mix's parameters;
- ``chipbench/workloads/<cell>.json``: the cell's own fixed numbers
  (offered rate, correctness limits);
- ``chipbench/drivers/<driver>.py``: the entry point's driver;
- ``chipbench/metrics/<metric>.py``: one reader per per-layer metric.

So a later cell, configuration or metric is a new file and a new entry,
never an edit.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import resource
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(SystemExit):
    """JAX found no accelerator, too few chips, or an unknown kind."""


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file of ``chipbench/`` by path (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything a driver needs to know about one cell."""

    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    bench: dict

    @property
    def end_to_end(self) -> list:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    @property
    def per_layer(self) -> list:
        """The per-layer metrics whose readers run in this cell."""
        e2e = {m["name"] for m in self.end_to_end}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def load_cell(name: str) -> Cell:
    bench = read_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    return Cell(name=name, chips=int(entry["chips"]),
                config=read_json(HERE, "configs", entry["config"] + ".json"),
                traffic=read_json(HERE, "traffic",
                                  entry["traffic"] + ".json"),
                workload=read_json(HERE, "workloads", name + ".json"),
                bench=bench)


def program_path() -> str:
    """The system under test (``src/``), put on ``sys.path``."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"the program under test is missing: no "
                         f"{os.path.join(src, 'repro')}")
    if src not in sys.path:
        sys.path.insert(0, src)
    return src


def use_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    ``<checkout>/.jax_cache``; every program is kept, however fast it
    compiled, so that only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips(n: int, rehearse: bool):
    """The first ``n`` devices, with the kind's peaks.

    On the chip: fails (NoChip) unless JAX finds a TPU with at least ``n``
    chips of a kind in ``peaks.json``.  In a rehearsal: the CPU, no peaks.
    """
    import jax
    devs = jax.devices()
    if rehearse:
        if devs[0].platform != "cpu":
            raise NoChip("a rehearsal runs on the CPU only")
        if len(devs) < n:
            raise NoChip(f"rehearsal needs {n} CPU devices (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={n})")
        return devs[:n], None
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devs)}")
    peaks = read_json(HERE, "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    return devs[:n], peaks[kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where unreported)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Tracer:
    """Profiler trace of part of the window, bracketed by the
    ``bench.window`` host span.  Inactive (all no-ops) when not asked for.
    """

    def __init__(self, active: bool, seconds: float):
        self.active = active
        self.seconds = seconds
        self.dir = os.path.join(ROOT, ".chipbench_trace")
        self._ann = None
        self.path = None

    def start(self) -> None:
        if not self.active:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def stop(self) -> None:
        if self._ann is None:
            return
        import jax
        from chipbench import trace_reduce
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()
        self.path = trace_reduce.find_xplane(self.dir)

    def reduce(self):
        from chipbench import trace_reduce
        try:
            return trace_reduce.reduce(self.path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def per_layer(cell: Cell, reduced, info: dict, peaks: dict) -> dict:
    """Run each of the cell's per-layer readers; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                          "chipbench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(reduced, info, peaks)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def emit(result: dict, checks: list) -> None:
    """Print the checks on standard error, then the result line.

    ``checks`` is [(name, value, limit)]; each passes when value <= limit.
    The checks go last in the line too, under ``checks``.
    """
    for name, value, limit in checks:
        verdict = "ok" if passes(value, limit) else "FAIL"
        print(f"check {name} {value!r} limit {limit!r} {verdict}",
              file=sys.stderr, flush=True)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    print(json.dumps(result, allow_nan=False), flush=True)


def passes(value, limit) -> bool:
    """A check passes when it gave a number within its limit."""
    return value is not None and value <= limit


def finite(x: float) -> float:
    if not math.isfinite(x):
        raise ValueError(f"metric value {x!r} is not finite")
    return x


class StepClock:
    """The window's slowest steps on the host clock, each with what the
    process did meanwhile: CPU seconds (user, system), page faults,
    context switches and seconds in garbage collection, and its start
    (``at_s``) from the window's open.  A stall with
    little CPU time was spent waiting (on the device, or off the CPU); one
    with CPU time was spent in the process.  Printed on standard error."""

    def __init__(self, keep: int = 3):
        self.keep, self.top, self.n = keep, [], 0
        self._gc_s, self._gc_t0 = 0.0, 0.0
        self.t_open = time.perf_counter()

    def _gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._gc_s += time.perf_counter() - self._gc_t0

    def begin(self) -> None:
        """The window opens: time garbage collections from here on."""
        self.t_open = time.perf_counter()
        gc.callbacks.append(self._gc)

    def end(self, label: str) -> None:
        if self._gc in gc.callbacks:
            gc.callbacks.remove(self._gc)
        for wall, d in self.top:
            print(f"[{label}] slow step {wall * 1e3:.3f} ms of {self.n}: "
                  + ", ".join(f"{k} {v:.6g}" for k, v in d.items()),
                  file=sys.stderr, flush=True)

    @property
    def longest_s(self) -> float:
        return self.top[0][0] if self.top else 0.0

    @contextlib.contextmanager
    def step(self):
        r0, g0 = resource.getrusage(resource.RUSAGE_SELF), self._gc_s
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        self.n += 1
        if len(self.top) == self.keep and wall <= self.top[-1][0]:
            return
        r1 = resource.getrusage(resource.RUSAGE_SELF)
        d = {"at_s": t0 - self.t_open,
             "user_s": r1.ru_utime - r0.ru_utime,
             "sys_s": r1.ru_stime - r0.ru_stime,
             "gc_s": self._gc_s - g0}
        for k in ("minflt", "majflt", "nvcsw", "nivcsw"):
            d[k] = getattr(r1, "ru_" + k) - getattr(r0, "ru_" + k)
        self.top = sorted(self.top + [(wall, d)],
                          key=lambda x: -x[0])[:self.keep]


@dataclasses.dataclass
class Context:
    """One run of one cell, as the driver sees it."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list
    peaks: dict | None
    t_start: float
    tracer: Tracer = None
    setup_s: float | None = None
    compiles: list = dataclasses.field(default_factory=list)
    clock: StepClock = dataclasses.field(default_factory=StepClock)

    def __post_init__(self):
        secs = min(self.seconds, float(self.cell.workload["trace_seconds"]))
        self.tracer = Tracer(self.trace, secs)

    def begin_window(self) -> float:
        """Close set-up, start the trace if asked; returns the window's
        start on the host clock."""
        import jax
        t = time.perf_counter()
        self.setup_s = t - self.t_start
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        self.clock.begin()
        self.tracer.start()
        return t

    def end_window(self) -> None:
        """Stop the trace, the count of compilations and the step clock;
        report the count (there should be none inside the window) and the
        slowest steps."""
        import jax
        self.tracer.stop()
        jax.monitoring.unregister_event_duration_listener(self._compiled)
        print(f"[chipbench] compilations inside the window: "
              f"{len(self.compiles)} {self.compiles}", file=sys.stderr,
              flush=True)
        self.clock.end("chipbench")

    def _compiled(self, event: str, seconds: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiles.append(round(seconds, 3))


@dataclasses.dataclass
class Outcome:
    """What a driver hands back after its window and its check."""

    attempted: int
    failed: int
    end_to_end: dict          # {metric name: value}
    checks: list              # [(name, value, limit)]
    info: dict                # what per-layer readers need besides the trace
