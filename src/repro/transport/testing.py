"""Multi-process test harness: run the existing ``tests/cases_*.py``
oracle modules across real worker processes.

The contract mirrors ``repro.testing`` exactly — same ``PASS {case}`` /
``FAIL {case}: {err}`` transcript protocol, same run-the-module-once
caching — but the module executes on every rank of a launched multiproc
job instead of inside one XLA trace.  The case *functions* are untouched:
the case modules read ``JMPI_BACKEND``/``JMPI_NP`` at import to size ``N``
and to route their ``spmd_collective`` helper to :func:`run_collective`,
so one oracle body is the parity test for both backends.

Every rank runs the case bodies on its own, so every rank must draw the
same property examples: under ``JMPI_BACKEND=multiproc``
:func:`repro.testing.property_testing` returns the seeded shim, never
hypothesis.  A job has a 120 s deadline (:data:`JOB_TIMEOUT`); rank 0
prints ``START {case}`` before each case, so a job that hangs fails the
case that started last, with the job's transcripts, and every case after
it as "not reached".

Worker-side entries (referenced by ``module:function`` name from the
launcher): :func:`_case_entry` (case runner), :func:`_bench_worker`
(interactive OMB-style p2p timing loop), :func:`_spin_entry` (barrier
heartbeat for launcher kill/orphan tests).
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import sys
import time

#: Deadline of one case-module job, in seconds: more than six times the
#: slowest module job measured on a CPU host, so only a hang reaches it.
JOB_TIMEOUT = 120.0


def run_collective(fn, shards):
    """Multiproc twin of the case modules' ``spmd_collective``.

    Each rank applies ``fn`` eagerly to its own shard (the ambient WORLD
    is this worker's :class:`~repro.transport.endpoint.MultiprocComm`, so
    every jmpi op inside ``fn`` goes over the wire), then object-allgathers
    the results — every rank returns the full per-rank list, exactly like
    the emulated helper, so case assertions run unmodified on all ranks.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import comm as comm_lib
    from repro.core import token as token_lib

    comm = comm_lib.world()
    token_lib.reset_ambient()  # fresh ordering chain, like each spmd trace
    out = fn(jnp.asarray(shards[comm.rank_id]))
    gathered = comm.endpoint.allgather_obj(np.asarray(out))
    return [np.asarray(g) for g in gathered]


def _case_entry(comm, args) -> None:
    """Worker entry: run every ``case_*`` of ``args["module"]`` on all
    ranks, agree on the outcome, and have rank 0 emit the transcript.

    Outcome agreement (a status-allgather of the per-rank error string —
    pre-encoded CTRL frames for the common all-ok vote, pickle only on
    failure) makes a failure on ANY rank visible in rank 0's transcript;
    the epoch bump + barrier between cases guarantees a case that raised
    mid-exchange cannot leak a stale frame into the next case.  Rank 0's
    ``START {case}`` line names the case a hung job was in.
    """
    mod = importlib.import_module(args["module"])
    ep = comm.endpoint
    for name in sorted(n for n in dir(mod) if n.startswith("case_")):
        if comm.rank_id == 0:
            print(f"START {name}", flush=True)
        err = None
        try:
            getattr(mod, name)()
        except Exception as e:  # noqa: BLE001 — reported per case
            err = f"{type(e).__name__}: {e}"
        errs = ep.allgather_status(err)
        ep.bump_epoch()
        ep.barrier()
        if comm.rank_id == 0:
            bad = next(((r, x) for r, x in enumerate(errs) if x), None)
            if bad is None:
                print(f"PASS {name}", flush=True)
            else:
                print(f"FAIL {name}: [rank {bad[0]}] {bad[1]}", flush=True)


@functools.lru_cache(maxsize=None)
def module_results_multiproc(module: str, nprocs: int, transport: str,
                             timeout: float = JOB_TIMEOUT
                             ) -> dict[str, tuple[bool, str]]:
    """Run ``module`` once under a (nprocs, transport) job; {case: (ok, log)}.

    Cached per configuration for the life of the test process, mirroring
    ``repro.testing.module_results``.  A job that hangs past ``timeout``
    or loses a worker fails the case that started last with the job's
    transcripts; the cases that finished keep their verdicts, and the
    ``__unreached__`` sentinel fails every case that never ran.  When no
    case is to blame (the module did not import, or the job failed after
    its last case) the ``__job__`` sentinel fails every case.
    """
    from repro.transport import launcher

    job = launcher.launch(nprocs, "repro.transport.testing:_case_entry",
                          transport=transport, args={"module": module},
                          timeout=timeout)
    try:
        transcript, ended = job.wait(), None
    except (TimeoutError, launcher.WorkerFailure) as e:
        transcript, ended = job.transcript(0), e
    finally:
        job.close()
    where = f"multiproc n={nprocs} ({transport})"
    results: dict[str, tuple[bool, str]] = {}
    started = None
    for line in transcript.splitlines():
        if line.startswith("START "):
            started = line.split()[1]
        elif line.startswith(("PASS ", "FAIL ")):
            name = line.split()[1].rstrip(":")
            results[name] = (line.startswith("PASS "), line)
    if ended is not None:
        if started is None or started in results:
            return {"__job__": (False, str(ended))}
        how = "hung" if isinstance(ended, TimeoutError) else "crashed"
        results[started] = (False, f"case {started} of {module} {how} "
                                   f"under {where}:\n{ended}")
        results["__unreached__"] = (
            False, f"not reached: {started} {how} under {where}")
    elif not results:
        results["__job__"] = (
            False, f"case module {module} produced no transcript under "
                   f"{where}:\n{transcript}")
    return results


def assert_case_multiproc(module: str, case: str, nprocs: int,
                          transport: str,
                          timeout: float = JOB_TIMEOUT) -> None:
    """Assert one case passed under a real-process job (module runs once
    per (nprocs, transport, timeout) configuration, cached)."""
    results = module_results_multiproc(module, nprocs, transport, timeout)
    if "__job__" in results:
        raise AssertionError(results["__job__"][1])
    if case not in results and "__unreached__" in results:
        raise AssertionError(results["__unreached__"][1])
    assert case in results, (
        f"case {case} not found in {module} under multiproc n={nprocs} "
        f"({transport}); known: {sorted(results)}")
    passed, log = results[case]
    assert passed, (f"case {case} of {module} failed under multiproc "
                    f"n={nprocs} ({transport}):\n{log}")


# ---------------------------------------------------------------------------
# interactive bench worker (driven by repro.bench.suites.p2p)
# ---------------------------------------------------------------------------

def _bench_worker(comm, args=None) -> None:
    """Interactive OMB-style timing loop over the jmpi p2p surface.

    Reads one JSON command per stdin line (the launcher writes each
    command to every rank, so all ranks execute the same schedule)::

        {"op": "pingpong",   "size": <bytes>, "inner": <iters>}
        {"op": "window",     "size": <bytes>, "window": <w>, "inner": <iters>}
        {"op": "pingpong_persistent", "size": <bytes>, "inner": <iters>}
        {"op": "window_persistent",   "size": <bytes>, "window": <w>,
                             "inner": <iters>}
        {"op": "gradsync",   "total": <floats>, "algorithm": ""|"int8_ef"|
                             "topk_ef", "buckets": <b>, "overlap": <bool>,
                             "inner": <iters>}
        {"op": "wire_bytes", "total": <floats>}
        {"op": "exit"}

    The ``*_persistent`` twins run the same exchange through cached
    ``sendrecv_init`` plans — first command per size pays the channel
    negotiation (outside the timed region), steady state runs the
    zero-copy channel fast path.

    Rank 0 replies ``DONE {"secs": ...}`` per command on stdout
    (``wire_bytes`` replies the per-rank transmitted payload bytes of one
    fp32 / int8_ef / topk_ef(1/32) allreduce instead — the endpoint spy
    measuring the compressed frames' literal size, ISSUE 8).
    """
    import jax.numpy as jnp
    import numpy as np

    import repro.core as jmpi
    from repro.core import p2p, token as token_lib
    from repro.distributed import overlap as overlap_lib

    def grad_tree(total):
        # synthetic uneven leaf split of one rank's `total`-float gradient
        fr = (0.4, 0.2, 0.1, 0.1, 0.08, 0.06, 0.04, 0.02)
        sizes = [int(total * f) for f in fr]
        sizes[0] += total - sum(sizes)
        rng = np.random.default_rng(comm.rank_id)
        return [jnp.asarray(rng.standard_normal(s), jnp.float32)
                for s in sizes]

    ep = comm.endpoint
    grads_cache: dict[int, list] = {}
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        cmd = json.loads(line)
        if cmd["op"] == "exit":
            return
        if cmd["op"] == "gradsync":
            grads = grads_cache.setdefault(int(cmd["total"]),
                                           grad_tree(int(cmd["total"])))
            comp = [jmpi.init_state(g) for g in grads]
            token_lib.reset_ambient()
            ep.barrier()
            t0 = time.perf_counter()
            for _ in range(int(cmd.get("inner", 3))):
                _, comp = overlap_lib.bucketed_grad_sync(
                    grads, comp, comm=comm,
                    algorithm=cmd.get("algorithm", ""),
                    buckets=int(cmd.get("buckets", 4)),
                    overlap=bool(cmd.get("overlap", False)), mean=True)
            secs = time.perf_counter() - t0
            ep.barrier()
            if comm.rank_id == 0:
                print("DONE " + json.dumps({"secs": secs}), flush=True)
            continue
        if cmd["op"] == "wire_bytes":
            g = jnp.asarray(
                np.random.default_rng(3).standard_normal(int(cmd["total"])),
                jnp.float32)
            token_lib.reset_ambient()
            ep.barrier()
            out = {}
            for name, run in (
                    ("fp32", lambda: jmpi.allreduce(g, comm=comm)),
                    ("int8", lambda: jmpi.compressed_allreduce(
                        g, jmpi.init_state(g), comm=comm,
                        algorithm="int8_ef")),
                    ("topk", lambda: jmpi.compressed_allreduce(
                        g, jmpi.init_state(g), comm=comm,
                        algorithm="topk_ef", frac=1 / 32))):
                ep.reset_wire_stats()
                run()
                out[name] = ep.wire_stats()["data_bytes"]
            ep.barrier()
            if comm.rank_id == 0:
                print("DONE " + json.dumps(out), flush=True)
            continue
        n_f32 = max(1, int(cmd["size"]) // 4)
        x = jnp.zeros((n_f32,), jnp.float32)
        inner = int(cmd.get("inner", 10))
        token_lib.reset_ambient()
        if cmd["op"].endswith("_persistent"):
            # Plan/channel setup (negotiation on first use per size;
            # process-global plan cache makes repeats free) happens here,
            # BEFORE the barrier and the clock — steady state is timed.
            from repro.core import plans as plans_lib
            sig = ((n_f32,), jnp.float32)
            fwd = plans_lib.sendrecv_init(sig, pairs=[(0, 1)], comm=comm)
            bwd = plans_lib.sendrecv_init(sig, pairs=[(1, 0)], comm=comm)
            ack = plans_lib.sendrecv_init(((1,), jnp.float32),
                                          pairs=[(1, 0)], comm=comm)
        ep.barrier()
        t0 = time.perf_counter()
        if cmd["op"] == "pingpong":
            for _ in range(inner):
                _, y = p2p.sendrecv(x, pairs=[(0, 1)], comm=comm)
                _, x = p2p.sendrecv(y, pairs=[(1, 0)], comm=comm)
        elif cmd["op"] == "window":
            window = int(cmd.get("window", 16))
            for _ in range(inner):
                reqs = [p2p.isendrecv(x, pairs=[(0, 1)], tag=i, comm=comm)
                        for i in range(window)]
                p2p.waitall(reqs)
                p2p.sendrecv(x[:1], pairs=[(1, 0)], comm=comm)  # completion ack
        elif cmd["op"] == "pingpong_persistent":
            for _ in range(inner):
                _, y = p2p.wait(fwd.start(x))
                _, x = p2p.wait(bwd.start(y))
        elif cmd["op"] == "window_persistent":
            window = int(cmd.get("window", 16))
            for _ in range(inner):
                reqs = [fwd.start(x, tag=i) for i in range(window)]
                p2p.waitall(reqs)
                p2p.wait(ack.start(x[:1]))  # completion ack
        else:
            raise ValueError(f"unknown bench op {cmd['op']!r}")
        secs = time.perf_counter() - t0
        ep.barrier()
        if comm.rank_id == 0:
            print("DONE " + json.dumps({"secs": secs}), flush=True)


def _spin_entry(comm, args) -> None:
    """Barrier heartbeat loop for launcher hardening tests: workers stay
    collectively synchronized until the parent kills one (the survivor's
    barrier then times out) or ``seconds`` elapse."""
    deadline = time.monotonic() + float((args or {}).get("seconds", 60))
    while time.monotonic() < deadline:
        comm.endpoint.barrier()
        time.sleep(0.02)


def backend_name() -> str:
    """The backend this process is configured for (env ``JMPI_BACKEND``,
    default ``emulated``) — the bench env fingerprint reads this."""
    return os.environ.get("JMPI_BACKEND", "emulated")
