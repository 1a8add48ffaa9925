"""Persistent point-to-point channels: the zero-copy fast path behind the
MPI-4 persistent plans (``*_init``/``sendrecv_init``) on the multiproc
backend.

The eager wire pays, per message: JSON meta encode/decode, a full
``tobytes()`` staging copy, a ``frombuffer`` copy on the far side, a
reader-thread queue handoff, and (pre-backoff) fixed poll sleeps.  A
channel amortizes ALL of that into one negotiation when the plan is
built: both ends agree on a frozen ``(op, shape, dtype, extra)`` key, so
steady-state execution moves only payload bytes.

Two concrete flavors, chosen by the communicator's transport kind:

``ShmChannel`` — a dedicated shared-memory segment per directed channel,
bypassing the frame rings AND the reader threads entirely::

    [ gen: u64 ][ seq: u64 ][ ack: u64 ][ pad → 64 ][ slot0 ][ slot1 ]

``seq``/``ack`` are monotonic chunk counters (sender owns ``seq``,
receiver owns ``ack`` — the same SPSC publish-after-payload discipline
as the frame ring).  The sender writes payload straight from the source
array into slot ``k % NSLOTS`` through a cached numpy view (no staging
buffer, no header, no meta) and publishes ``seq = k+1``; the receiver
waits for ``seq``, reads the slot view directly, and acks.  Messages
larger than a slot are chunk-pipelined: with two slots the sender fills
chunk ``k+1`` while the receiver drains chunk ``k``.  ``gen`` carries
the endpoint epoch (+1, so a zeroed fresh segment is never a valid
generation): after ``bump_epoch`` the sender re-zeroes the counters and
publishes the new generation; the receiver waits for it — no handshake
frames, and stale in-flight state from an abandoned epoch is discarded
wholesale.

``SockSendChannel``/``SockRecvChannel`` — CHAN frames over the existing
TCP wire with a pre-encoded cached header (kind/chan-id/epoch/length are
all frozen, so the header is packed once per epoch, not per send) and no
meta bytes.  The endpoint's reader thread routes CHAN frames by channel
id and ``recv_into``-s the payload directly into a pooled, preallocated
receive array — single copy end to end, zero allocation and zero pickle
in steady state.

Negotiation (driven by ``Endpoint.open_channels``) is a batched
three-phase SYN/ACK over ordinary OBJ frames: every SYN goes out before
any blocking read, so any static SPMD channel pattern opens deadlock-
free; the receiver validates the sender's frozen key against its own at
negotiation time, making signature mismatches (and the plans layer's
static ERR_TRUNCATE) init-time errors rather than steady-state ones.
"""

from __future__ import annotations

import time

import numpy as np

from repro.transport import base
from repro.transport.shm import u64_view

#: Slot payload capacity.  Messages above this are chunk-pipelined
#: through the slots; at or below it a message is a single zero-staging
#: slot write/read.
CHUNK_CAP = 256 << 10

#: Slots per shm channel: double buffering overlaps one producer copy
#: with one consumer copy, which is all a single shared segment can use.
NSLOTS = 2

_GEN, _SEQ, _ACK = 0, 1, 2  # u64 counter indices (see shm.u64_view)
_CTRL_BYTES = 64  # gen + seq + ack, padded out of false-sharing range


def channel_segment_name(session: str, src: int, dst: int, cid: int) -> str:
    """Shared-memory segment name for sender ``src``'s channel ``cid`` to
    ``dst``.  Shares the job session prefix so the launcher's orphan
    backstop can unlink leaked channel segments by prefix scan."""
    return f"{session}_c{cid}r{src}to{dst}"


def chunk_layout(nbytes: int) -> tuple[int, int]:
    """``(slot_capacity, nchunks)`` for a frozen message of ``nbytes``."""
    cap = min(max(nbytes, 1), CHUNK_CAP)
    return cap, max(1, -(-nbytes // cap))


def key_layout(key: tuple) -> tuple[tuple, np.dtype, int]:
    """``(shape, np_dtype, nbytes)`` from a channel key
    ``(op, shape, dtype_name, extra)``."""
    _, shape, dtype_name, _ = key
    dtype = base._dtype_from_name(dtype_name)
    nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    return tuple(shape), dtype, nbytes


class ShmChannel:
    """One end (sender or receiver) of a directed shm slot channel."""

    def __init__(self, endpoint, peer: int, key: tuple, segment,
                 sender: bool, owner: bool):
        self._ep, self.peer, self.key = endpoint, peer, key
        self._shm, self._sender, self._owner = segment, sender, owner
        shape, dtype, self._nbytes = key_layout(key)
        self._cap, self._nchunks = chunk_layout(self._nbytes)
        buf = segment.buf
        count = int(np.prod(shape, dtype=np.int64))
        self._slots = []   # per-slot uint8 byte views (chunked transfer)
        self._typed = []   # per-slot dtype/shape views (single-chunk path)
        for i in range(NSLOTS):
            off = _CTRL_BYTES + i * self._cap
            self._slots.append(np.frombuffer(buf, np.uint8, self._cap, off))
            if self._nchunks == 1:
                self._typed.append(
                    np.frombuffer(buf, dtype, count, off).reshape(shape))
        self._count = 0                   # chunks through this end
        self._epoch = endpoint.epoch
        self._recv_buf = (np.empty(shape, dtype)
                          if not sender and self._nchunks > 1 else None)
        self._ctl = u64_view(buf, 3)      # [gen, seq, ack]
        if sender:
            self._ctl[_SEQ] = 0
            self._ctl[_ACK] = 0
            self._ctl[_GEN] = endpoint.epoch + 1
        else:
            self._wait(_GEN, endpoint.epoch + 1, "generation")

    # -- counters ------------------------------------------------------------
    def _wait(self, idx: int, need: int, what: str) -> None:
        ctl = self._ctl
        if ctl[idx] >= need:
            return
        backoff = base.Backoff(spin=300)
        deadline = time.monotonic() + self._ep.timeout
        while ctl[idx] < need:
            if backoff.pause() and time.monotonic() > deadline:
                raise TimeoutError(
                    f"rank {self._ep.rank}: persistent channel to rank "
                    f"{self.peer} stalled waiting for {what} >= {need} "
                    f"(key={self.key}, peer gone?)")

    def _sync_epoch(self) -> None:
        ep = self._ep.epoch
        if self._epoch == ep:
            return
        # Epoch moved since last use.  The case runner bumps epochs
        # collectively (bump + barrier), so neither end is mid-message
        # here; the sender resets the stream and publishes the new
        # generation, the receiver waits for it.  Both ends reach their
        # first post-bump use at the same epoch (same SPMD program).
        if self._sender:
            self._ctl[_SEQ] = 0
            self._ctl[_ACK] = 0
            self._ctl[_GEN] = ep + 1
        else:
            self._wait(_GEN, ep + 1, "generation")
        self._count = 0
        self._epoch = ep

    # -- sender --------------------------------------------------------------
    def send(self, arr: np.ndarray) -> None:
        """Write one frozen-signature message straight into the slots."""
        self._sync_epoch()
        if self._nchunks == 1:
            k = self._count
            self._wait(_ACK, k + 1 - NSLOTS, "ack")
            np.copyto(self._typed[k % NSLOTS], arr, casting="no")
            self._ctl[_SEQ] = k + 1  # publish after payload
            self._count = k + 1
            self._ep._count_chan(self._nbytes, 0)
            return
        src = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
        for c in range(self._nchunks):
            k = self._count
            self._wait(_ACK, k + 1 - NSLOTS, "ack")
            lo = c * self._cap
            hi = min(lo + self._cap, self._nbytes)
            self._slots[k % NSLOTS][:hi - lo] = src[lo:hi]
            self._ctl[_SEQ] = k + 1
            self._count = k + 1
        self._ep._count_chan(self._nbytes, 0)

    # -- receiver ------------------------------------------------------------
    def recv(self) -> np.ndarray:
        """The next message.  Single-chunk messages return the slot view
        itself (borrowed: consume it, then :meth:`release`); chunked
        messages assemble into one persistent receive buffer, acking each
        chunk so the sender pipelines the next one behind it."""
        self._sync_epoch()
        if self._nchunks == 1:
            k = self._count
            self._wait(_SEQ, k + 1, "payload")
            self._count = k + 1
            return self._typed[k % NSLOTS]
        dst = self._recv_buf.reshape(-1).view(np.uint8)
        for c in range(self._nchunks):
            k = self._count
            self._wait(_SEQ, k + 1, "payload")
            lo = c * self._cap
            hi = min(lo + self._cap, self._nbytes)
            dst[lo:hi] = self._slots[k % NSLOTS][:hi - lo]
            self._ctl[_ACK] = k + 1  # slot free for the sender
            self._count = k + 1
        return self._recv_buf

    def release(self) -> None:
        """Done consuming the last :meth:`recv` — ack its slot back."""
        if self._nchunks == 1:
            self._ctl[_ACK] = self._count

    def close(self) -> None:
        # Views alias the mmap; drop ours before closing it.  BufferError
        # means a caller still holds a borrowed recv() view — leave the
        # mapping for the interpreter to reclaim, but still unlink the
        # name so the segment cannot leak past the process.
        self._slots, self._typed, self._recv_buf = [], [], None
        self._ctl.release()
        try:
            self._shm.close()
        except (OSError, BufferError):
            pass
        if self._owner:
            try:
                self._shm.unlink()
            except (OSError, FileNotFoundError):
                pass


class SockSendChannel:
    """Sender end of a channel over the TCP wire: pre-encoded header,
    zero meta, payload streamed from the source array's own memory."""

    #: Below this, header + payload are concatenated into one sendall
    #: (one syscall beats avoiding one small copy); above, the payload
    #: memoryview goes out as-is.
    _INLINE = 16 << 10

    def __init__(self, endpoint, peer: int, key: tuple, cid: int, wire):
        self._ep, self.peer, self.key = endpoint, peer, key
        self._cid, self._wire = cid, wire
        _, _, self._nbytes = key_layout(key)
        self._hdr_epoch, self._hdr = None, b""

    def send(self, arr: np.ndarray) -> None:
        epoch = self._ep.epoch
        if epoch != self._hdr_epoch:  # re-pack only when the epoch moves
            self._hdr = base.HEADER.pack(base.KIND_CHAN, self._cid, epoch,
                                         0, self._nbytes)
            self._hdr_epoch = epoch
        flat = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
        if self._nbytes <= self._INLINE:
            self._wire.sendall(self._hdr + flat.tobytes())
        else:
            self._wire.sendall(self._hdr)
            self._wire.sendall(flat.data)
        self._ep._count_chan(self._nbytes, base.HEADER_LEN)

    def close(self) -> None:
        pass  # the wire belongs to the transport


class SockRecvChannel:
    """Receiver end over TCP: the endpoint's reader thread lands CHAN
    payloads directly in pooled preallocated arrays via ``recv_into``
    and signals a condition — no queue handoff, no parse, no pickle."""

    def __init__(self, endpoint, peer: int, key: tuple, cid: int):
        import threading

        self._ep, self.peer, self.key, self.cid = endpoint, peer, key, cid
        self._shape, self._dtype, self._nbytes = key_layout(key)
        self._cv = threading.Condition()
        self._ready: list = []   # (epoch, (arr, u8view)) in arrival order
        self._free: list = []    # returned buffers, reused round-robin
        self._cur = None

    def _buffer(self):
        with self._cv:
            if self._free:
                return self._free.pop()
        arr = np.empty(self._shape, self._dtype)
        return arr, arr.reshape(-1).view(np.uint8)

    def deliver(self, wire, epoch: int, data_len: int,
                deadline: float) -> None:
        """Reader-thread entry: land one CHAN payload."""
        if data_len != self._nbytes:
            raise RuntimeError(
                f"persistent channel {self.cid} from rank {self.peer}: "
                f"payload of {data_len} bytes does not match the "
                f"negotiated {self._nbytes} (key={self.key})")
        pair = self._buffer()
        wire.recv_into(pair[1], deadline)
        with self._cv:
            self._ready.append((epoch, pair))
            self._cv.notify()

    def recv(self) -> np.ndarray:
        """The next current-epoch message (borrowed buffer: consume, then
        :meth:`release`).  Stale-epoch messages are dropped in place;
        future-epoch ones stay queued until this rank catches up."""
        deadline = time.monotonic() + self._ep.timeout
        with self._cv:
            while True:
                epoch, keep = self._ep.epoch, []
                found = None
                for item in self._ready:
                    if item[0] < epoch:
                        self._free.append(item[1])  # stale: recycle
                    elif found is None and item[0] == epoch:
                        found = item[1]
                    else:
                        keep.append(item)
                self._ready = keep
                if found is not None:
                    self._cur = found
                    return found[0]
                if not self._cv.wait(timeout=0.2) and \
                        time.monotonic() > deadline:
                    raise TimeoutError(
                        f"rank {self._ep.rank}: persistent channel "
                        f"{self.cid} from rank {self.peer} received no "
                        f"payload within {self._ep.timeout:.0f}s")

    def release(self) -> None:
        pair, self._cur = self._cur, None
        if pair is not None:
            with self._cv:
                self._free.append(pair)

    def close(self) -> None:
        self._ep._chan_rx.pop((self.peer, self.cid), None)


# ---------------------------------------------------------------------------
# Persistent issue closures — what the plans layer binds instead of the
# generic kernel closure when a MultiprocComm can negotiate channels.
# The builders return None when the op/algorithm has no channel lowering
# (the plan then falls back to the eager kernel unchanged).
# ---------------------------------------------------------------------------

def _take(chan) -> np.ndarray:
    """Copy a borrowed channel buffer out and release the slot.

    The copy is what makes slot recycling safe around JAX's async
    dispatch: a jnp op may read its operand after issue returns, so the
    channel buffer must never be aliased past release().
    """
    out = np.array(chan.recv())
    chan.release()
    return out


def sendrecv_issue(comm, shape: tuple, dtype_name: str, perm):
    """Persistent ``sendrecv`` issue closure over negotiated channels,
    or None when the pattern is purely local.

    The closure is host-synchronous and numpy-native end to end (the plan
    layer marks such plans ``host=True``): no token ops, no jnp dispatch —
    those per-call costs are milliseconds against a µs-scale channel.
    """
    ep, me = comm.endpoint, comm.rank_id

    key = ("sendrecv", tuple(shape), dtype_name, None)
    dsts = [d for s, d in perm if s == me and d != me]
    srcs = [s for s, d in perm if d == me]
    local = bool(srcs) and srcs[0] == me
    # One batched negotiation: every SYN leaves before any blocking read,
    # so a symmetric pattern (e.g. a ring) opens deadlock-free.
    tx, rx = ep.open_channels([(d, key) for d in dsts],
                              [(s, key) for s in srcs if s != me])
    zeros = np.zeros(shape, base._dtype_from_name(dtype_name))
    zeros.setflags(write=False)  # shared across starts, like a jnp const

    def issue(v, t):
        arr = np.asarray(v)
        for d in dsts:
            tx[d].send(arr)
        if local:
            out = np.array(arr)  # own the buffer: v may be a device view
        elif srcs:
            out = _take(rx[srcs[0]])
        else:
            out = zeros
        return out, t

    return issue


def collective_issue(comm, op_name: str, algo_name: str, shape: tuple,
                     dtype_name: str, kw: dict):
    """Persistent issue closure for a ``direct``-algorithm collective, or
    None when no channel lowering exists for ``(op_name, algo_name)``."""
    if algo_name != "direct":
        return None
    builder = _COLLECTIVE_BUILDERS.get(op_name)
    if builder is None:
        return None
    return builder(comm, tuple(shape), dtype_name, kw)


def _open_symmetric(ep, peers, key):
    """One channel each way with every peer (the all-to-all pattern)."""
    return ep.open_channels([(p, key) for p in peers],
                            [(p, key) for p in peers])


def _allreduce_issue(comm, shape, dtype_name, kw):
    from repro.core.operators import combiner

    ep, me, n = comm.endpoint, comm.rank_id, comm.nprocs
    key = ("allreduce", shape, dtype_name, None)
    peers = [r for r in range(n) if r != me]
    tx, rx = _open_symmetric(ep, peers, key)
    combine, pre, post = combiner(kw["op"])

    def issue(v, t):
        arr = np.asarray(v)
        for p in peers:
            tx[p].send(arr)
        acc = None
        for r in range(n):  # reduce-on-receive, rank order (bit-identical)
            part = arr if r == me else _take(rx[r])
            if pre is not None:
                part = pre(part)
            acc = part if acc is None else combine(acc, part)
        if post is not None:
            acc = post(acc, v.dtype)
        return acc, t

    return issue


def _reduce_scatter_issue(comm, shape, dtype_name, kw):
    from repro.core.operators import combiner

    ep, me, n = comm.endpoint, comm.rank_id, comm.nprocs
    chunk = shape[0] // n
    key = ("reduce_scatter", (chunk,) + shape[1:], dtype_name, None)
    peers = [r for r in range(n) if r != me]
    tx, rx = _open_symmetric(ep, peers, key)
    combine, pre, post = combiner(kw["op"])

    def issue(v, t):
        arr = np.asarray(v)
        for d in peers:  # each destination gets only ITS chunk
            tx[d].send(arr[d * chunk:(d + 1) * chunk])
        acc = None
        for r in range(n):
            part = (arr[me * chunk:(me + 1) * chunk] if r == me
                    else _take(rx[r]))
            if pre is not None:
                part = pre(part)
            acc = part if acc is None else combine(acc, part)
        if post is not None:
            acc = post(acc, v.dtype)
        return acc, t

    return issue


def _bcast_issue(comm, shape, dtype_name, kw):
    ep, me, n = comm.endpoint, comm.rank_id, comm.nprocs
    root = kw["root"]
    key = ("bcast", shape, dtype_name, root)
    if me == root:
        tx, _ = ep.open_channels(
            [(p, key) for p in range(n) if p != root], [])

        def issue(v, t):
            arr = np.asarray(v)
            for p in range(n):
                if p != root:
                    tx[p].send(arr)
            return arr, t
    else:
        _, rx = ep.open_channels([], [(root, key)])

        def issue(v, t):
            return _take(rx[root]), t

    return issue


def _allgather_issue(comm, shape, dtype_name, kw):
    ep, me, n = comm.endpoint, comm.rank_id, comm.nprocs
    key = ("allgather", shape, dtype_name, None)
    peers = [r for r in range(n) if r != me]
    tx, rx = _open_symmetric(ep, peers, key)
    scalar = len(shape) == 0

    def issue(v, t):
        arr = np.asarray(v)
        for p in peers:
            tx[p].send(arr)
        parts = [arr if r == me else _take(rx[r]) for r in range(n)]
        out = np.stack(parts) if scalar else np.concatenate(parts, axis=0)
        return out, t

    return issue


def _alltoall_issue(comm, shape, dtype_name, kw):
    ep, me, n = comm.endpoint, comm.rank_id, comm.nprocs
    split_axis = kw.get("split_axis", 0)
    concat_axis = kw.get("concat_axis", 0)
    chunk_shape = list(shape)
    chunk_shape[split_axis] //= n
    key = ("alltoall", tuple(chunk_shape), dtype_name,
           (split_axis, concat_axis))
    peers = [r for r in range(n) if r != me]
    tx, rx = _open_symmetric(ep, peers, key)

    def issue(v, t):
        chunks = np.split(np.asarray(v), n, axis=split_axis)
        for d in peers:
            tx[d].send(chunks[d])
        got = [chunks[s] if s == me else _take(rx[s]) for s in range(n)]
        return np.concatenate(got, axis=concat_axis), t

    return issue


_COLLECTIVE_BUILDERS = {
    "allreduce": _allreduce_issue,
    "reduce_scatter": _reduce_scatter_issue,
    "bcast": _bcast_issue,
    "allgather": _allgather_issue,
    "alltoall": _alltoall_issue,
}
