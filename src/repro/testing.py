"""Test/bench harness utilities.

Multi-rank behaviour needs emulated devices, but
``--xla_force_host_platform_device_count`` is process-global and must never
leak into the main test process (smoke tests and benches see exactly 1
device).  Case modules therefore execute in a subprocess with the flag set
only there, pinned to the CPU backend (on a host with a chip the parent
may hold it), run every ``case_*`` function, and report a per-case
PASS/FAIL transcript back to the parent.

Speed: the pytest wrappers call :func:`assert_case`, which runs the whole
case module ONCE per (module, device-count) — the transcript is cached and
each parametrized test just asserts its own case's slice.  That keeps
per-case reporting while paying the subprocess + jax-import cost once per
module instead of once per case.  Every case also runs under a per-case
SIGALRM timeout (default 120 s, ``REPRO_CASE_TIMEOUT`` to override) so one
hung case fails loudly instead of eating the blanket subprocess timeout.

Property-based testing: :func:`property_testing` returns hypothesis's
``(given, settings, strategies)`` when the real library is installed and a
minimal deterministic shim otherwise (seeded rng, ``max_examples`` draws,
first falsifying example reported).
"""

from __future__ import annotations

import functools
import os
import subprocess
import sys

PER_CASE_TIMEOUT = int(os.environ.get("REPRO_CASE_TIMEOUT", "120"))


def child_env(n_devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", "")).strip()
    # keep child import path identical to parent
    env["PYTHONPATH"] = os.pathsep.join(p for p in (
        os.path.join(_repo_root(), "src"), env.get("PYTHONPATH", "")) if p)
    return env


def _repo_root() -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))  # src/repro -> repo root


# Child runner: everything (incl. tracebacks) goes to stdout so the parent
# can attribute output lines to cases by position.
_RUNNER = r"""
import signal, sys, traceback
mod_name = sys.argv[1]
only = sys.argv[2] if len(sys.argv) > 2 and sys.argv[2] != "-" else None
per_case = int(sys.argv[3]) if len(sys.argv) > 3 else 0


class CaseTimeout(Exception):
    pass


def _alarm(signum, frame):
    raise CaseTimeout(f"case exceeded {per_case}s")


import importlib
mod = importlib.import_module(mod_name)
cases = [n for n in dir(mod) if n.startswith("case_")]
if only:
    cases = [c for c in cases if c == only]
failed = []
for name in sorted(cases):
    try:
        if per_case > 0 and hasattr(signal, "SIGALRM"):
            signal.signal(signal.SIGALRM, _alarm)
            signal.alarm(per_case)
        try:
            getattr(mod, name)()
        finally:
            if per_case > 0 and hasattr(signal, "SIGALRM"):
                signal.alarm(0)
        print(f"PASS {name}", flush=True)
    except CaseTimeout as e:
        failed.append(name)
        print(f"FAIL {name} (timeout: {e})", flush=True)
    except Exception:
        failed.append(name)
        print(f"FAIL {name}", flush=True)
        traceback.print_exc(file=sys.stdout)
        sys.stdout.flush()
sys.exit(1 if failed else 0)
"""


def _run_child(module: str, n_devices: int, only: str | None,
               timeout: int, per_case_timeout: int):
    return subprocess.run(
        [sys.executable, "-c", _RUNNER, module, only or "-",
         str(per_case_timeout)],
        env=dict(child_env(n_devices), JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=timeout, cwd=_repo_root())


def run_cases(module: str, n_devices: int = 8, only: str | None = None,
              timeout: int = 900,
              per_case_timeout: int = PER_CASE_TIMEOUT) -> str:
    """Run all (or ``only`` one) case_* functions of ``module`` under N
    emulated devices, in a fresh subprocess.

    Returns the child transcript; raises AssertionError (with transcript) on
    any failure so pytest shows exactly which cases broke.  Prefer
    :func:`assert_case` in parametrized wrappers — it shares one subprocess
    across the whole module.
    """
    proc = _run_child(module, n_devices, only, timeout, per_case_timeout)
    transcript = proc.stdout + proc.stderr
    assert proc.returncode == 0, (
        f"case module {module} failed under {n_devices} devices:\n{transcript}")
    return transcript


@functools.lru_cache(maxsize=None)
def module_results(module: str, n_devices: int = 8, timeout: int = 900,
                   per_case_timeout: int = PER_CASE_TIMEOUT
                   ) -> dict[str, tuple[bool, str]]:
    """Run the whole case module once; return {case: (passed, log)}.

    Cached per (module, n_devices) for the life of the test process: the
    first parametrized test pays the subprocess, the rest read the cache —
    including module-level timeouts (cached as a failure, so a hung module
    costs the 900 s budget once, not once per parametrized test).
    """
    try:
        proc = _run_child(module, n_devices, None, timeout, per_case_timeout)
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"")
        out = out.decode() if isinstance(out, bytes) else out
        return {"__timeout__": (
            False, f"case module {module} exceeded {timeout}s under "
                   f"{n_devices} devices; partial transcript:\n{out}")}
    results: dict[str, tuple[bool, str]] = {}
    current: str | None = None
    buf: list[str] = []
    for line in proc.stdout.splitlines():
        if line.startswith("PASS ") or line.startswith("FAIL "):
            if current is not None:
                ok, log = results[current]
                results[current] = (ok, "\n".join(buf))
            passed = line.startswith("PASS ")
            current = line.split()[1]
            results[current] = (passed, line)
            buf = [line]
        else:
            buf.append(line)
    if current is not None:
        ok, _ = results[current]
        results[current] = (ok, "\n".join(buf))
    if not results and proc.returncode != 0:
        # import-time crash: attribute the whole transcript to every lookup
        results["__import__"] = (
            False, f"case module {module} crashed under {n_devices} "
                   f"devices:\n{proc.stdout}{proc.stderr}")
    return results


def assert_case(module: str, case: str, n_devices: int = 8) -> None:
    """Assert one case of ``module`` passed (module runs once, cached)."""
    results = module_results(module, n_devices)
    for sentinel in ("__import__", "__timeout__"):
        if sentinel in results:
            raise AssertionError(results[sentinel][1])
    assert case in results, (
        f"case {case} not found in {module} under {n_devices} devices; "
        f"known: {sorted(results)}")
    passed, log = results[case]
    assert passed, (f"case {case} of {module} failed under {n_devices} "
                    f"devices:\n{log}")


# ---------------------------------------------------------------------------
# hypothesis-or-shim
# ---------------------------------------------------------------------------

class _Strategy:
    def __init__(self, draw):
        self.draw = draw


class _Strategies:
    """The subset of hypothesis.strategies the test-suite uses."""

    @staticmethod
    def sampled_from(seq):
        seq = list(seq)
        return _Strategy(lambda rng: seq[int(rng.integers(len(seq)))])

    @staticmethod
    def integers(min_value, max_value):
        return _Strategy(
            lambda rng: int(rng.integers(min_value, max_value + 1)))

    @staticmethod
    def booleans():
        return _Strategy(lambda rng: bool(rng.integers(2)))

    @staticmethod
    def tuples(*strategies):
        return _Strategy(lambda rng: tuple(s.draw(rng) for s in strategies))


def _shim_settings(max_examples: int = 20, deadline=None, **_ignored):
    def deco(fn):
        fn._shim_max_examples = max_examples
        return fn
    return deco


def _shim_given(**kw_strategies):
    def deco(fn):
        @functools.wraps(fn)
        def run():
            import numpy as np
            rng = np.random.default_rng(0)
            n = getattr(run, "_shim_max_examples",
                        getattr(fn, "_shim_max_examples", 20))
            for _ in range(n):
                draw = {k: s.draw(rng) for k, s in kw_strategies.items()}
                try:
                    fn(**draw)
                except Exception as e:
                    raise AssertionError(
                        f"property falsified on {draw!r}: {e}") from e
        return run
    return deco


def property_testing():
    """(given, settings, strategies) — hypothesis if installed, shim else.

    The shim draws ``max_examples`` deterministic examples (seeded rng) and
    reports the first falsifying draw; no shrinking, kwargs-style ``given``
    only — exactly the surface the case modules use.
    """
    try:
        from hypothesis import given, settings, strategies
        return given, settings, strategies
    except ImportError:
        return _shim_given, _shim_settings, _Strategies
