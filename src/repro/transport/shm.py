"""Shared-memory transport: one SPSC ring buffer per directed rank pair.

The fast path.  Each directed pair (src → dst) gets its own
``multiprocessing.shared_memory`` segment named
``{session}_r{src}to{dst}`` with layout::

    [ head: u64 ][ tail: u64 ][ ring bytes: RING_SIZE ]

``head``/``tail`` are *monotonic* byte counters (they never wrap; the
ring index is ``counter % RING_SIZE``), which makes full/empty
unambiguous: ``head - tail`` is the number of unread bytes.  Exactly one
process writes ``head`` (the segment's creator, src) and exactly one
writes ``tail`` (dst), so the single-producer/single-consumer handshake
needs no locks — an 8-byte-aligned u64 store is a single atomic
instruction on x86-64/aarch64, and the counter update is published only
*after* the payload bytes it covers are in place.

The counters are read and written only through :func:`u64_view`, whose
element access is one aligned 8-byte copy.  ``struct.pack_into`` is not
safe here: it zero-fills the field and then writes it byte by byte, so
the peer could read ``0`` or a half-written counter, compute a negative
``head - tail``, and move ``tail`` backwards — the ring then replays
stale bytes (wrong payloads) or loses frame sync (a hang).

Writers block briefly when the ring is full.  That is deadlock-safe
here because the endpoint dedicates a reader thread per inbound wire
that drains unconditionally into per-source queues — the consumer never
waits on the producer.
"""

from __future__ import annotations

import time
from multiprocessing import shared_memory

from repro.transport import base

#: Ring capacity per directed pair.  Frames larger than the ring still
#: flow — the writer chunks and the counters never wrap — 1 MiB just
#: bounds the per-pair footprint (n*(n-1) segments per job).
RING_SIZE = 1 << 20

_HDR_BYTES = 16  # head + tail


def u64_view(buf, count: int) -> memoryview:
    """The first ``count`` u64 counters of a shared segment as a native
    ``'Q'`` memoryview: each element read or write is a single aligned
    8-byte copy, so a concurrent peer never sees a torn counter.
    Release the view before closing the segment."""
    return buf[:8 * count].cast("Q")


def segment_name(session: str, src: int, dst: int) -> str:
    """Shared-memory segment name for the directed pair ``src → dst``.

    The launcher derives the same names for orphan-cleanup unlinking.
    """
    return f"{session}_r{src}to{dst}"


def _attach(name: str, create: bool, deadline: float) -> shared_memory.SharedMemory:
    if create:
        shm = shared_memory.SharedMemory(name=name, create=True,
                                         size=_HDR_BYTES + RING_SIZE)
        shm.buf[:_HDR_BYTES] = b"\x00" * _HDR_BYTES
        return shm
    backoff = base.Backoff(spin=0, min_sleep=5e-5, max_sleep=5e-3)
    while True:
        try:
            shm = shared_memory.SharedMemory(name=name)
            break
        except (FileNotFoundError, ValueError):
            # ValueError: the creator has opened the segment but not yet
            # sized it, and an empty file cannot be mapped
            if time.monotonic() > deadline:
                raise TimeoutError(f"shm rendezvous: segment {name} never "
                                   "appeared (creator died?)")
            backoff.pause()
    # The stdlib resource_tracker assumes every attacher owns the segment
    # and double-unlinks it at exit (bpo-38119).  Only the creator unlinks;
    # deregister the attach so teardown stays single-owner.
    try:
        from multiprocessing import resource_tracker
        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:
        pass
    return shm


class _Ring:
    """One end of an SPSC ring (producer if ``writer`` else consumer)."""

    def __init__(self, shm: shared_memory.SharedMemory, writer: bool,
                 owner: bool):
        self._shm, self._writer, self._owner = shm, writer, owner
        self._ctr = u64_view(shm.buf, 2)  # [head, tail]

    def write(self, data: bytes, deadline: float) -> None:
        mv, pos = memoryview(data), 0
        backoff = base.Backoff(spin=100)
        while pos < len(mv):
            head, tail = self._ctr[0], self._ctr[1]
            free = RING_SIZE - (head - tail)
            if free == 0:
                if backoff.pause() and time.monotonic() > deadline:
                    raise TimeoutError("shm ring stayed full (reader gone?)")
                continue
            backoff.reset()
            n = min(free, len(mv) - pos)
            start = head % RING_SIZE
            first = min(n, RING_SIZE - start)
            self._shm.buf[_HDR_BYTES + start:_HDR_BYTES + start + first] = \
                mv[pos:pos + first]
            if n > first:  # wrap-around: second chunk at ring offset 0
                self._shm.buf[_HDR_BYTES:_HDR_BYTES + n - first] = \
                    mv[pos + first:pos + n]
            # Publish AFTER the payload bytes are visible.
            self._ctr[0] = head + n
            pos += n

    def read(self, n: int, deadline: float, stop=None) -> bytearray:
        # Returned buffer is freshly built here and owned by the caller
        # (``ShmWire.owns_recv``) — no trailing bytes() copy.
        out = bytearray()
        backoff = base.Backoff(spin=100)
        while len(out) < n:
            head, tail = self._ctr[0], self._ctr[1]
            avail = head - tail
            if avail == 0:
                if stop is not None and stop():
                    raise EOFError("endpoint stopped")
                if backoff.pause() and time.monotonic() > deadline:
                    raise TimeoutError(f"shm recv timed out with "
                                       f"{n - len(out)} of {n} bytes "
                                       "outstanding")
                continue
            backoff.reset()
            take = min(avail, n - len(out))
            start = tail % RING_SIZE
            first = min(take, RING_SIZE - start)
            out += self._shm.buf[_HDR_BYTES + start:_HDR_BYTES + start + first]
            if take > first:
                out += self._shm.buf[_HDR_BYTES:_HDR_BYTES + take - first]
            self._ctr[1] = tail + take
        return out

    def close(self) -> None:
        self._ctr.release()
        try:
            self._shm.close()
            if self._owner:
                self._shm.unlink()
        except (OSError, BufferError):
            pass


class ShmWire(base.Wire):
    """Wire over a pair of directed rings (out: me→peer, in: peer→me)."""

    #: ``_Ring.read`` builds a fresh bytearray per call — the receiver
    #: owns it, so frame decoding may alias it instead of copying.
    owns_recv = True

    def __init__(self, out_ring: _Ring, in_ring: _Ring,
                 write_timeout: float = 120.0):
        self._out, self._in = out_ring, in_ring
        self._write_timeout = write_timeout

    def sendall(self, data: bytes) -> None:
        self._out.write(data, time.monotonic() + self._write_timeout)

    def recv_exactly(self, n: int, deadline: float) -> bytes:
        return self._in.read(n, deadline, stop=self._stopped)

    def _stopped(self) -> bool:
        return self.stop_check is not None and self.stop_check()

    def close(self) -> None:
        self._out.close()
        self._in.close()


class ShmTransport(base.Transport):
    """Full shm-ring mesh for one rank.

    Each rank *creates* its outbound segments (me → peer) and *attaches*
    to its inbound ones (peer → me); creation doubles as rendezvous.
    """

    kind = "shm"

    def __init__(self, rank: int, nprocs: int, session: str,
                 timeout: float = 60.0):
        self.rank, self.nprocs, self.session = rank, nprocs, session
        deadline = time.monotonic() + timeout
        self._wires: dict[int, ShmWire] = {}
        for peer in range(nprocs):
            if peer == rank:
                continue
            out_shm = _attach(segment_name(session, rank, peer),
                              create=True, deadline=deadline)
            in_shm = _attach(segment_name(session, peer, rank),
                             create=False, deadline=deadline)
            self._wires[peer] = ShmWire(
                _Ring(out_shm, writer=True, owner=True),
                _Ring(in_shm, writer=False, owner=False),
                write_timeout=timeout)

    def wire(self, peer: int) -> ShmWire:
        return self._wires[peer]

    def close(self) -> None:
        for w in self._wires.values():
            w.close()
        self._wires.clear()
