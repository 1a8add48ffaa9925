"""Process launcher: spawn N workers, rendezvous them into a transport
mesh, and supervise the job (the ``mpiexec`` of the multiproc backend).

Failure containment is the point of this module: a worker that crashes or
hangs mid-collective must fail the *job* promptly — never leave the parent
blocked on a dead peer or orphan the surviving workers.  The monitor loop
polls every child; the first nonzero exit (including signal deaths, e.g.
−9 after an OOM kill) or the job deadline triggers terminate→kill of every
remaining child, followed by shared-memory unlink and a raised error
carrying the worker transcripts.  An ``atexit`` hook replays the same
teardown for any job still live when the parent exits, so an interrupted
pytest run cannot leak processes or shm segments.

The parent stays jax-free: workers are ``python -m repro.transport.worker``
subprocesses with their own 1-device XLA config on the CPU backend
(``repro.testing.child_env`` keeps the import path identical to the
parent's); they are host-side ranks and never reach for a chip.
"""

from __future__ import annotations

import atexit
import json
import os
import select
import shutil
import subprocess
import sys
import tempfile
import time
import uuid
from multiprocessing import shared_memory

from repro.testing import _repo_root, child_env
from repro.transport.shm import segment_name

_LIVE_JOBS: list["Job"] = []


class WorkerFailure(RuntimeError):
    """A worker exited nonzero (or died to a signal) before job completion."""


def _default_timeout() -> float:
    return float(os.environ.get("JMPI_TIMEOUT", "120"))


class Job:
    """A running multiproc job: one worker process per rank.

    Obtain via :func:`launch`; consume via :meth:`wait` (batch entries) or
    :meth:`command`/:meth:`read_line` (interactive entries, e.g. the bench
    workers).  :meth:`close` is idempotent and always reaps.
    """

    def __init__(self, nprocs: int, transport: str, session: str, rdv: str,
                 procs: list[subprocess.Popen], timeout: float,
                 interactive: bool):
        self.nprocs, self.transport = nprocs, transport
        self.session, self.rdv = session, rdv
        self.procs, self.timeout = procs, timeout
        self.interactive = interactive
        self._closed = False
        _LIVE_JOBS.append(self)

    # -- observation -------------------------------------------------------
    def transcript(self, rank: int = 0) -> str:
        """The captured stdout+stderr of ``rank`` (empty if interactive
        rank 0, whose stdout is a live pipe)."""
        path = os.path.join(self.rdv, f"out_{rank}.txt")
        try:
            with open(path, errors="replace") as f:
                return f.read()
        except FileNotFoundError:
            return ""

    def _transcripts(self) -> str:
        parts = []
        for r in range(self.nprocs):
            text = self.transcript(r).strip()
            if text:
                parts.append(f"--- rank {r} ---\n{text}")
        return "\n".join(parts) or "(no worker output captured)"

    def pids(self) -> list[int]:
        """Worker process ids (for orphan checks in tests)."""
        return [p.pid for p in self.procs]

    # -- supervision -------------------------------------------------------
    def wait(self) -> str:
        """Block until every worker exits 0; return rank 0's transcript.

        Raises:
            WorkerFailure: any worker exited nonzero / died to a signal —
                every other worker is terminated and reaped first.
            TimeoutError: the job deadline passed — all workers reaped.
        """
        deadline = time.monotonic() + self.timeout
        try:
            while True:
                codes = [p.poll() for p in self.procs]
                bad = [(r, c) for r, c in enumerate(codes)
                       if c is not None and c != 0]
                if bad:
                    self._reap()
                    rank, code = bad[0]
                    raise WorkerFailure(
                        f"worker rank {rank} exited with code {code}; "
                        f"job torn down.\n{self._transcripts()}")
                if all(c == 0 for c in codes):
                    return self.transcript(0)
                if time.monotonic() > deadline:
                    self._reap()
                    raise TimeoutError(
                        f"multiproc job exceeded {self.timeout:.0f}s "
                        f"(transport={self.transport}, n={self.nprocs}); "
                        f"workers killed.\n{self._transcripts()}")
                time.sleep(0.02)
        finally:
            if all(p.poll() is not None for p in self.procs):
                self._release_segments()

    # -- interactive mode --------------------------------------------------
    def command(self, obj) -> None:
        """Write one JSON command line to EVERY worker's stdin."""
        line = json.dumps(obj) + "\n"
        for p in self.procs:
            p.stdin.write(line)
            p.stdin.flush()

    def read_line(self, timeout: float | None = None) -> str:
        """One line from rank 0's stdout (its reply channel), with a
        deadline; reaps and raises if rank 0 dies or stays silent."""
        deadline = time.monotonic() + (timeout or self.timeout)
        p0 = self.procs[0]
        while True:
            budget = deadline - time.monotonic()
            if budget <= 0:
                self._reap()
                raise TimeoutError(
                    f"no reply from rank 0 within {timeout or self.timeout}s"
                    f"\n{self._transcripts()}")
            ready, _, _ = select.select([p0.stdout], [], [], min(budget, 0.5))
            if not ready:
                if p0.poll() is not None:
                    self._reap()
                    raise WorkerFailure(
                        f"rank 0 exited with code {p0.returncode} while a "
                        f"reply was pending\n{self._transcripts()}")
                continue
            line = p0.stdout.readline()
            if not line:
                self._reap()
                raise WorkerFailure(
                    f"rank 0 closed stdout (code {p0.poll()})"
                    f"\n{self._transcripts()}")
            return line.rstrip("\n")

    # -- teardown ----------------------------------------------------------
    def _reap(self) -> None:
        """Terminate every surviving worker; escalate to SIGKILL."""
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        grace = time.monotonic() + 2.0
        while time.monotonic() < grace and \
                any(p.poll() is None for p in self.procs):
            time.sleep(0.02)
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
        self._release_segments()

    def _release_segments(self) -> None:
        """Unlink any shm segments the workers left behind (backstop — the
        creating worker unlinks its own on a clean exit).  Ring segments
        are enumerable from (session, nprocs); persistent-channel segments
        carry dynamic channel ids, so those are swept by session-prefix
        scan of /dev/shm (best-effort: the scan is Linux-specific, and a
        clean worker exit already unlinked everything)."""
        if self.transport != "shm":
            return
        for i in range(self.nprocs):
            for j in range(self.nprocs):
                if i == j:
                    continue
                try:
                    seg = shared_memory.SharedMemory(
                        name=segment_name(self.session, i, j))
                    seg.close()
                    seg.unlink()
                except (FileNotFoundError, OSError):
                    pass
        try:
            leaked = [n for n in os.listdir("/dev/shm")
                      if n.startswith(f"{self.session}_c")]
        except OSError:
            leaked = []
        for name in leaked:
            try:
                seg = shared_memory.SharedMemory(name=name)
                seg.close()
                seg.unlink()
            except (FileNotFoundError, OSError):
                pass

    def close(self) -> None:
        """Reap workers and delete the rendezvous directory (idempotent)."""
        if self._closed:
            return
        self._closed = True
        if self.interactive:
            for p in self.procs:
                try:
                    p.stdin.close()
                except OSError:
                    pass
        self._reap()
        shutil.rmtree(self.rdv, ignore_errors=True)
        if self in _LIVE_JOBS:
            _LIVE_JOBS.remove(self)


def launch(nprocs: int, entry: str, *, transport: str = "sock", args=None,
           timeout: float | None = None, interactive: bool = False) -> Job:
    """Spawn ``nprocs`` workers running ``entry`` over a transport mesh.

    Args:
        nprocs: worker count (ranks 0..nprocs−1).
        entry: ``"module:function"``; the worker imports the module and
            calls ``function(comm)`` — or ``function(comm, args)`` when
            ``args`` is given — with a live :class:`MultiprocComm` whose
            backend is installed as the ambient WORLD.
        transport: ``"sock"`` (portable, default) or ``"shm"`` (fast path).
        args: JSON-serializable value forwarded to the entry function.
        timeout: job deadline in seconds (default env ``JMPI_TIMEOUT`` or
            120); also forwarded to the workers' endpoint deadline.
        interactive: keep every worker's stdin open as a command pipe and
            rank 0's stdout as a reply pipe (:meth:`Job.command` /
            :meth:`Job.read_line`); batch jobs capture all output to files.
    Returns:
        The supervised :class:`Job`.
    Raises:
        ValueError: unknown transport or a malformed entry.
    """
    if transport not in ("shm", "sock"):
        raise ValueError(f"unknown transport {transport!r}; "
                         "expected 'shm' or 'sock'")
    if ":" not in entry:
        raise ValueError(f"entry must be 'module:function', got {entry!r}")
    timeout = _default_timeout() if timeout is None else float(timeout)
    session = uuid.uuid4().hex[:8]
    rdv = tempfile.mkdtemp(prefix="jmpi_rdv_")
    procs: list[subprocess.Popen] = []
    try:
        for rank in range(nprocs):
            env = child_env(1)
            env.update({
                # ranks are host processes: none may reach for a chip
                "JAX_PLATFORMS": "cpu",
                "JMPI_RANK": str(rank),
                "JMPI_NP": str(nprocs),
                "JMPI_TRANSPORT": transport,
                "JMPI_SESSION": session,
                "JMPI_RENDEZVOUS": rdv,
                "JMPI_ENTRY": entry,
                "JMPI_ENTRY_ARGS": json.dumps(args),
                "JMPI_BACKEND": "multiproc",
                "JMPI_TIMEOUT": str(timeout),
                "PYTHONUNBUFFERED": "1",
            })
            log = open(os.path.join(rdv, f"out_{rank}.txt"), "w")
            if interactive and rank == 0:
                stdout, stderr = subprocess.PIPE, log
            else:
                stdout, stderr = log, subprocess.STDOUT
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.transport.worker"],
                env=env, cwd=_repo_root(),
                stdin=subprocess.PIPE if interactive else subprocess.DEVNULL,
                stdout=stdout, stderr=stderr, text=True))
            log.close()  # the child holds its own dup of the fd
    except Exception:
        for p in procs:
            p.kill()
        shutil.rmtree(rdv, ignore_errors=True)
        raise
    return Job(nprocs, transport, session, rdv, procs, timeout, interactive)


@atexit.register
def _cleanup_live_jobs() -> None:
    for job in list(_LIVE_JOBS):
        job.close()
