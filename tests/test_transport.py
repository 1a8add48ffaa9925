"""Unit tests for the multiproc transport subsystem (jax-light: no
emulated-device subprocesses; real processes only where the launcher is
the thing under test)."""

from __future__ import annotations

import os
import signal
import socket
import threading
import time

import numpy as np
import pytest

from repro.transport import base
from repro.transport.sock import SockWire


# ---------------------------------------------------------------------------
# frame protocol
# ---------------------------------------------------------------------------

def _wire_pair():
    a, b = socket.socketpair()
    return SockWire(a), SockWire(b)


@pytest.mark.parametrize("dtype", ["float32", "float64", "int32", "int64",
                                   "complex64", "bfloat16"])
def test_frame_array_roundtrip(dtype):
    if dtype == "bfloat16":
        import ml_dtypes
        np_dtype = np.dtype(ml_dtypes.bfloat16)
    else:
        np_dtype = np.dtype(dtype)
    arr = np.arange(12).reshape(3, 4).astype(np_dtype)
    w0, w1 = _wire_pair()
    meta, data = base.encode_array(arr)
    base.send_frame(w0, base.KIND_ARRAY, tag=7, epoch=3, meta=meta, data=data)
    kind, tag, epoch, meta2, data2 = base.recv_frame(
        w1, time.monotonic() + 5)
    assert (kind, tag, epoch) == (base.KIND_ARRAY, 7, 3)
    out = base.decode_array(meta2, data2)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(out, arr)
    w0.close(), w1.close()


def test_frame_array_noncontiguous():
    arr = np.arange(24.0).reshape(4, 6)[::2, ::3]  # strided view
    meta, data = base.encode_array(arr)
    np.testing.assert_array_equal(base.decode_array(meta, data), arr)


def test_frame_array_zero_dim():
    # regression: ascontiguousarray promotes 0-d to (1,); a scalar
    # allreduce payload must come off the wire still 0-d
    arr = np.asarray(np.float32(2.5))
    out = base.decode_array(*base.encode_array(arr))
    assert out.shape == () and out == np.float32(2.5)


def test_frame_obj_and_ctrl_roundtrip():
    w0, w1 = _wire_pair()
    meta, data = base.encode_obj({"err": None, "n": [1, 2]})
    base.send_frame(w0, base.KIND_OBJ, tag=-12, epoch=0, meta=meta, data=data)
    base.send_frame(w0, base.KIND_CTRL, tag=-101, epoch=0)
    kind, _, _, _, data2 = base.recv_frame(w1, time.monotonic() + 5)
    assert kind == base.KIND_OBJ
    assert base.decode_obj(data2) == {"err": None, "n": [1, 2]}
    kind, tag, _, meta3, data3 = base.recv_frame(w1, time.monotonic() + 5)
    assert (kind, tag, meta3, data3) == (base.KIND_CTRL, -101, b"", b"")
    w0.close(), w1.close()


def test_frame_recv_timeout_and_eof():
    w0, w1 = _wire_pair()
    with pytest.raises(TimeoutError):
        base.recv_frame(w1, time.monotonic() + 0.3)
    w0.close()
    with pytest.raises(EOFError):
        base.recv_frame(w1, time.monotonic() + 5)
    w1.close()


# ---------------------------------------------------------------------------
# shm ring
# ---------------------------------------------------------------------------

def test_shm_ring_wraparound():
    """Stream several ring capacities through one SPSC ring: exercises the
    wrap-around copy split and the monotonic head/tail counters."""
    from repro.transport import shm as shm_mod

    seg = shm_mod._attach(f"jmpi_test_{os.getpid()}", create=True,
                          deadline=time.monotonic() + 5)
    writer = shm_mod._Ring(seg, writer=True, owner=False)
    reader = shm_mod._Ring(seg, writer=False, owner=False)
    total = 3 * shm_mod.RING_SIZE + 12345
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, size=total, dtype=np.uint8).tobytes()

    def produce():
        deadline = time.monotonic() + 30
        for ofs in range(0, total, 70_001):  # odd chunking vs. ring size
            writer.write(payload[ofs:ofs + 70_001], deadline)

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    got = reader.read(total, time.monotonic() + 30)
    t.join(timeout=10)
    assert got == payload
    writer.close()  # the rings hold views of the segment: release first
    reader.close()
    seg.close()
    seg.unlink()


def test_backoff_spin_then_sleep_phases():
    """Backoff reports False through the spin+yield phases, True once it
    sleeps; reset() restarts the spin phase."""
    bo = base.Backoff(spin=5, min_sleep=1e-6, max_sleep=1e-5)
    phases = [bo.pause() for _ in range(5 + 4)]  # spin + the sleep(0) yields
    assert not any(phases), "spin/yield pauses must report False"
    assert bo.pause() is True, "first real sleep must report True"
    bo.reset()
    assert bo.pause() is False, "reset must restart the spin phase"


def test_decode_array_owned_skips_copy():
    arr = np.arange(12, dtype=np.float32)
    meta, data = base.encode_array(arr)
    data = bytearray(data)  # what an owning wire recv actually hands over
    view = base.decode_array(meta, data, owned=True)
    copy = base.decode_array(meta, data, owned=False)
    np.testing.assert_array_equal(view, arr)
    np.testing.assert_array_equal(copy, arr)
    assert np.shares_memory(view, np.frombuffer(data, np.uint8)), \
        "owned decode must alias the recv buffer (zero copy)"
    assert not np.shares_memory(copy, np.frombuffer(data, np.uint8)), \
        "borrowed decode must defensively copy"


def _rtt_echo(name_a, name_b, n):
    # Child side of the ring round-trip test below (module-level so the
    # spawn start method can pickle it; spawn avoids forking a process
    # that already holds JAX's internal threads).
    from repro.transport import shm as shm_mod
    d = time.monotonic() + 60
    a = shm_mod._attach(name_a, create=False, deadline=d)
    b = shm_mod._attach(name_b, create=False, deadline=d)
    ra = shm_mod._Ring(a, writer=False, owner=False)
    wb = shm_mod._Ring(b, writer=True, owner=False)
    for _ in range(n):
        wb.write(ra.read(1, d), d)
    ra.close()  # closes the attached segments too (owner=False: no unlink)
    wb.close()


def test_shm_ring_roundtrip_latency_floor():
    """Adaptive spin-then-backoff ring waits: the cross-process 1-byte
    round trip must sit far below the old fixed 200µs-poll floor (two
    polls per RTT ≈ 400µs+); the spin path lands in the ~10µs range, so
    a 200µs median bound has wide margin yet catches a poll-sleep
    regression outright."""
    import multiprocessing as mp

    from repro.transport import shm as shm_mod

    d = time.monotonic() + 20
    na, nb = f"jmpi_rtt_a_{os.getpid()}", f"jmpi_rtt_b_{os.getpid()}"
    seg_a = shm_mod._attach(na, create=True, deadline=d)
    seg_b = shm_mod._attach(nb, create=True, deadline=d)
    wa = shm_mod._Ring(seg_a, writer=True, owner=False)
    rb = shm_mod._Ring(seg_b, writer=False, owner=False)
    try:
        n = 300
        proc = mp.get_context("spawn").Process(
            target=_rtt_echo, args=(na, nb, n), daemon=True)
        proc.start()
        deadline = time.monotonic() + 60
        rtts_us = []
        for _ in range(n):
            t0 = time.perf_counter()
            wa.write(b"x", deadline)
            rb.read(1, deadline)
            rtts_us.append((time.perf_counter() - t0) * 1e6)
        proc.join(timeout=30)
        assert proc.exitcode == 0
        median = sorted(rtts_us)[n // 2]
        assert median < 200.0, (
            f"ring RTT median {median:.0f}µs — the adaptive backoff floor "
            f"should be well under the old 2×200µs poll-sleep floor")
    finally:
        wa.close()
        rb.close()
        for seg in (seg_a, seg_b):
            seg.unlink()


# ---------------------------------------------------------------------------
# persistent channels: shm slot protocol in-process, sock negotiation
# ---------------------------------------------------------------------------

class _StubEndpoint:
    """The slice of Endpoint that ShmChannel touches."""

    def __init__(self):
        self.epoch, self.timeout, self.rank = 0, 5.0, 0
        self.chan_bytes = 0

    def _count_chan(self, payload, overhead):
        self.chan_bytes += payload + overhead


def _shm_channel_pair(key, nbytes):
    from multiprocessing import shared_memory

    from repro.transport import channel as channel_lib

    cap, _ = channel_lib.chunk_layout(nbytes)
    seg = shared_memory.SharedMemory(
        name=f"jmpi_chan_{os.getpid()}_{nbytes}", create=True,
        size=channel_lib._CTRL_BYTES + channel_lib.NSLOTS * cap)
    ep = _StubEndpoint()
    send = channel_lib.ShmChannel(ep, 1, key, seg, sender=True, owner=True)
    seg2 = shared_memory.SharedMemory(name=seg.name)
    recv = channel_lib.ShmChannel(ep, 0, key, seg2, sender=False, owner=False)
    return ep, send, recv


def test_shm_channel_single_chunk_slots():
    """Single-chunk messages move through the 2 slots with seq/ack flow
    control; the recv view is the slot itself (zero copy)."""
    key = ("sendrecv", (8,), "float32", None)
    ep, send, recv = _shm_channel_pair(key, 32)
    try:
        for i in range(5):  # > NSLOTS: exercises ack-gated slot reuse
            msg = np.full(8, float(i), np.float32)
            send.send(msg)
            got = recv.recv()
            assert np.shares_memory(got, recv._slots[i % 2]), \
                "single-chunk recv must return the slot view itself"
            np.testing.assert_array_equal(got, msg)
            recv.release()
            del got  # borrowed view: drop before the segment closes
        assert ep.chan_bytes == 5 * 32
    finally:
        send.close()
        recv.close()


def test_shm_channel_chunk_pipelined_large_message():
    """Messages above CHUNK_CAP stream through the slot window in chunks
    and reassemble exactly."""
    from repro.transport import channel as channel_lib

    n = (channel_lib.CHUNK_CAP // 4) + 12345   # > 1 chunk of float32
    key = ("sendrecv", (n,), "float32", None)
    ep, send, recv = _shm_channel_pair(key, n * 4)
    try:
        assert send._nchunks > 1
        rng = np.random.default_rng(7)
        msg = rng.standard_normal(n).astype(np.float32)
        send.send(msg)
        np.testing.assert_array_equal(recv.recv(), msg)
        recv.release()
        msg2 = msg[::-1].copy()
        send.send(msg2)
        np.testing.assert_array_equal(recv.recv(), msg2)
        recv.release()
    finally:
        send.close()
        recv.close()


def test_shm_channel_epoch_reset_reuses_segment():
    """bump_epoch-style epoch moves re-zero the stream in place: the same
    segment carries the next epoch's messages with no handshake frames."""
    key = ("sendrecv", (4,), "int64", None)
    ep, send, recv = _shm_channel_pair(key, 32)
    try:
        send.send(np.arange(4))
        np.testing.assert_array_equal(recv.recv(), np.arange(4))
        recv.release()
        ep.epoch += 1                      # collective bump (stub: shared ep)
        fresh = np.arange(4) + 100
        send.send(fresh)                   # sender republishes gen, seq=1
        assert send._count == 1, "epoch reset must restart the chunk stream"
        np.testing.assert_array_equal(recv.recv(), fresh)
        recv.release()
    finally:
        send.close()
        recv.close()


def test_endpoint_sock_channel_negotiation_and_zero_meta(endpoints):
    """open_channels over a real socketpair: batched SYN/ACK negotiation,
    both directions exchange through CHAN frames, and the steady state
    moves ZERO meta bytes and zero eager frames (the wire spy separates
    channel traffic from eager traffic)."""
    ep0, ep1 = endpoints
    key = ("sendrecv", (16,), "float32", None)
    out = {}

    def side1():
        out["tx1"], out["rx1"] = ep1.open_channels([(0, key)], [(0, key)])

    t = threading.Thread(target=side1, daemon=True)
    t.start()
    tx0, rx0 = ep0.open_channels([(1, key)], [(1, key)])
    t.join(timeout=10)
    assert "tx1" in out, "negotiation did not complete"

    ep0.reset_wire_stats()
    ep1.reset_wire_stats()
    for i in range(3):
        msg = np.full(16, float(i), np.float32)
        tx0[1].send(msg)
        got = out["rx1"][0].recv()
        np.testing.assert_array_equal(got, msg)
        out["rx1"][0].release()
        out["tx1"][0].send(msg + 1)
        got = rx0[1].recv()
        np.testing.assert_array_equal(got, msg + 1)
        rx0[1].release()
    for ep in (ep0, ep1):
        s = ep.wire_stats()
        assert s["meta_bytes"] == 0, s
        assert s["frames"] == 0, ("steady-state channel traffic must not "
                                  "touch the eager frame counters", s)
        assert s["chan_msgs"] == 3 and s["chan_bytes"] > 0, s


def test_endpoint_channel_key_mismatch_is_negotiation_error(endpoints):
    """A receiver whose frozen key disagrees with the sender's fails AT
    NEGOTIATION (init) time, not in steady state: the receiver raises the
    mismatch, the sender never gets its ACK."""
    ep0, ep1 = endpoints
    k_send = ("sendrecv", (16,), "float32", None)
    k_recv = ("sendrecv", (32,), "float32", None)   # wrong shape
    errs = {}

    def side0():
        try:
            ep0.open_channels([(1, k_send)], [])
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errs["send"] = e

    t = threading.Thread(target=side0, daemon=True)
    t.start()
    with pytest.raises(RuntimeError, match="mismatch"):
        ep1.open_channels([], [(0, k_recv)])
    t.join(timeout=10)
    assert isinstance(errs.get("send"), (TimeoutError, RuntimeError)), \
        "the un-ACKed sender must fail its negotiation too"


def test_endpoint_channels_cached_per_key(endpoints):
    """Repeated open_channels with the same (peer, key) reuses the live
    channel objects — plans rebuilt across traces must not leak channels."""
    ep0, ep1 = endpoints
    key = ("allreduce", (4,), "float32", None)

    def side1():
        for _ in range(2):
            ep1.open_channels([(0, key)], [(0, key)])

    t = threading.Thread(target=side1, daemon=True)
    t.start()
    tx_a, rx_a = ep0.open_channels([(1, key)], [(1, key)])
    tx_b, rx_b = ep0.open_channels([(1, key)], [(1, key)])
    t.join(timeout=10)
    assert tx_a[1] is tx_b[1] and rx_a[1] is rx_b[1]
    assert len(ep0._channels) == 2   # one tx + one rx, not four


# ---------------------------------------------------------------------------
# endpoint: tag matching, epochs, barrier — two endpoints in one process
# ---------------------------------------------------------------------------

class _PairTransport(base.Transport):
    kind = "sock"

    def __init__(self, wires):
        self._wires = wires

    def wire(self, peer):
        return self._wires[peer]

    def close(self):
        for w in self._wires.values():
            w.close()


@pytest.fixture()
def endpoints():
    from repro.transport.endpoint import Endpoint
    s0, s1 = socket.socketpair()
    ep0 = Endpoint(_PairTransport({1: SockWire(s0)}), 0, 2, timeout=1.0)
    ep1 = Endpoint(_PairTransport({0: SockWire(s1)}), 1, 2, timeout=1.0)
    yield ep0, ep1
    ep0.close()
    ep1.close()


def test_endpoint_tag_matching_out_of_order(endpoints):
    ep0, ep1 = endpoints
    a, b = np.arange(3.0), np.arange(4) + 10
    ep0.send_array(1, a, tag=5)
    ep0.send_array(1, b, tag=3)
    # tag 3 arrived second but is claimable first; tag 5 stays pending.
    np.testing.assert_array_equal(ep1.recv_array(0, 3), b)
    np.testing.assert_array_equal(ep1.recv_array(0, 5), a)


def test_endpoint_obj_and_barrier(endpoints):
    ep0, ep1 = endpoints
    ep0.send_obj(1, ("payload", 42))
    assert ep1.recv_obj(0) == ("payload", 42)
    done = []
    t = threading.Thread(target=lambda: (ep1.barrier(), done.append(1)),
                         daemon=True)
    t.start()
    ep0.barrier()
    t.join(timeout=5)
    assert done == [1]


def test_endpoint_epoch_discards_stale_frames(endpoints):
    ep0, ep1 = endpoints
    ep0.send_array(1, np.arange(3.0), tag=5)   # epoch 0: will go stale
    ep1.bump_epoch()                           # ep1 now only accepts epoch 1
    with pytest.raises(TimeoutError, match="no frame"):
        ep1.recv_array(0, 5)
    ep0.bump_epoch()
    fresh = np.arange(4.0) + 1
    ep0.send_array(1, fresh, tag=5)
    np.testing.assert_array_equal(ep1.recv_array(0, 5), fresh)


def test_endpoint_future_epoch_stays_pending(endpoints):
    ep0, ep1 = endpoints
    ep0.bump_epoch()                           # ep0 runs ahead
    future = np.arange(5.0)
    ep0.send_array(1, future, tag=9)
    with pytest.raises(TimeoutError):          # not claimable at epoch 0 ...
        ep1.recv_array(0, 9)
    ep1.bump_epoch()                           # ... but kept, not dropped
    np.testing.assert_array_equal(ep1.recv_array(0, 9), future)


def test_endpoint_peer_close_is_an_error(endpoints):
    ep0, ep1 = endpoints
    ep0.close()
    with pytest.raises(RuntimeError, match="closed its wire"):
        ep1.recv_array(0, 5)


# ---------------------------------------------------------------------------
# launcher hardening: crash/timeout containment, zero orphans
# ---------------------------------------------------------------------------

def _assert_all_dead(job):
    for p in job.procs:
        assert p.poll() is not None, f"worker pid {p.pid} still running"
    for pid in job.pids():
        # reparented orphans would still answer signal 0
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            continue
        # pid exists: must be our own zombie already reaped by Popen.wait
        assert False, f"orphan worker pid {pid} survived teardown"


def test_launcher_kill_mid_collective_no_orphans():
    """SIGKILL one worker of a live shm job mid-barrier: wait() must raise
    promptly, every other worker must be reaped, and every shared-memory
    segment must be unlinked."""
    from repro.transport import launch, WorkerFailure

    job = launch(2, "repro.transport.testing:_spin_entry", transport="shm",
                 args={"seconds": 120}, timeout=60)
    try:
        time.sleep(2.0)  # let the mesh come up and the barrier loop spin
        os.kill(job.pids()[1], signal.SIGKILL)
        t0 = time.monotonic()
        with pytest.raises(WorkerFailure, match="rank 1"):
            job.wait()
        assert time.monotonic() - t0 < 30, "dead worker detected too slowly"
        _assert_all_dead(job)
        from multiprocessing import shared_memory
        from repro.transport.shm import segment_name
        for i, j in ((0, 1), (1, 0)):
            with pytest.raises(FileNotFoundError):
                shared_memory.SharedMemory(
                    name=segment_name(job.session, i, j))
    finally:
        job.close()


def test_launcher_job_timeout_reaps_workers():
    from repro.transport import launch

    job = launch(2, "repro.transport.testing:_spin_entry",
                 args={"seconds": 120}, timeout=6)
    try:
        with pytest.raises(TimeoutError, match="exceeded 6s"):
            job.wait()
        _assert_all_dead(job)
    finally:
        job.close()


def test_launcher_worker_exception_carries_transcript():
    from repro.transport import launch, WorkerFailure

    job = launch(2, "repro.transport.testing:_case_entry",
                 args={"module": "tests.no_such_module"}, timeout=60)
    try:
        with pytest.raises(WorkerFailure, match="no_such_module"):
            job.wait()
        _assert_all_dead(job)
    finally:
        job.close()


def test_launcher_rejects_bad_arguments():
    from repro.transport import launch

    with pytest.raises(ValueError, match="transport"):
        launch(2, "mod:fn", transport="carrier-pigeon")
    with pytest.raises(ValueError, match="module:function"):
        launch(2, "not-an-entry")


@pytest.mark.parametrize("runner", ["case_subprocess", "transport_job"])
def test_children_pinned_to_cpu(runner, monkeypatch):
    """Case children and launcher workers pin JAX_PLATFORMS=cpu themselves,
    whatever the parent's environment says."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    if runner == "case_subprocess":
        from repro.testing import run_cases
        run_cases("tests.cases_platform", 1)
    else:
        from repro.transport.testing import assert_case_multiproc
        assert_case_multiproc("tests.cases_platform",
                              "case_child_runs_on_cpu", 2, "sock")


# ---------------------------------------------------------------------------
# satellite: plan cache keys carry backend/transport identity
# ---------------------------------------------------------------------------

def test_plan_cache_backend_identity():
    import jax.numpy as jnp

    from repro.core import plans
    from repro.core.comm import Communicator
    from repro.transport.endpoint import MultiprocComm

    emu = Communicator(("ranks",), 0)
    shm = MultiprocComm(("proc",), 0, rank_id=0, nprocs=2,
                        transport_kind="shm")
    sock = MultiprocComm(("proc",), 0, rank_id=0, nprocs=2,
                         transport_kind="sock")
    keys = {plans._backend_key(c) for c in (emu, shm, sock)}
    assert len(keys) == 3, "backend/transport identity must split cache keys"

    plans.plan_cache_clear()
    x = jnp.zeros((4,), jnp.float32)
    p_shm = plans.allreduce_init(x, comm=shm)
    p_sock = plans.allreduce_init(x, comm=sock)
    assert p_shm is not p_sock, "shm plan served to a sock communicator"
    assert plans.allreduce_init(x, comm=sock) is p_sock
    stats = plans.plan_cache_stats()
    assert stats["by_backend"]["multiproc"]["misses"] >= 2
    assert stats["by_backend"]["multiproc"]["hits"] >= 1
