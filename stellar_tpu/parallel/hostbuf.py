"""Reusable host receive buffers for the wire ingress (ISSUE 19).

The ingress server reads every frame body with ``recv_into`` directly
into a buffer leased from this pool, decodes items in place
(messages stay :class:`memoryview` slices of the lease — see
``wire.decode_submit``), and hands those views straight into the
verify service's queues: one kernel→userspace copy per frame, zero
intermediate copies between the wire and the dispatch path (the
engine packs device operands from whatever host bytes it is given,
so keeping the wire bytes stable and view-shared is what makes the
hand-off copy-free).

Because decoded views alias the lease, a lease is REFCOUNTED: the
reader retains it once per frame decoded from it and the responder
releases when that frame's tickets reach a terminal and the response
is on the wire. A buffer returns to the free list only at refcount
zero — reuse can never scribble over message bytes a queued ticket
still references. The pool is bounded: when every buffer is leased a
fresh bytearray is allocated instead (counted in ``misses`` — the
perf surface, never a stall) and simply dropped at release.

Lease state mutates from reader and responder threads, so all of it
lives under the pool's one lock — this module sits in
``analysis/locks.py`` SCOPE (and the lockorder prover's graph) with
no allowlist entries.
"""

from __future__ import annotations

import threading
from typing import List, Optional

__all__ = ["HostBufferPool", "Lease", "DEFAULT_BUF_BYTES",
           "DEFAULT_POOL_BUFFERS"]

# sized for the wire: a handful of MAX_FRAME_BYTES frames per buffer,
# a handful of buffers per connection's working set
DEFAULT_BUF_BYTES = 1 << 20
DEFAULT_POOL_BUFFERS = 8


class Lease:
    """One leased buffer. ``buf``/``mv`` are stable for the lease's
    lifetime; ``retain``/``release`` go through the pool's lock. The
    linter contract: this class owns no lock of its own — every
    mutation of its refcount happens inside the pool's ``_locked``
    helpers."""

    __slots__ = ("buf", "mv", "refs", "pooled")

    def __init__(self, buf: bytearray, pooled: bool):
        self.buf = buf
        self.mv = memoryview(buf)
        self.refs = 1           # the lease itself holds one ref
        self.pooled = pooled


class HostBufferPool:
    """Bounded free-list of reusable receive buffers."""

    def __init__(self, buffers: int = DEFAULT_POOL_BUFFERS,
                 buf_bytes: int = DEFAULT_BUF_BYTES):
        self._lock = threading.Lock()
        self.buf_bytes = max(1, int(buf_bytes))
        self._free: List[bytearray] = [
            bytearray(self.buf_bytes)
            for _ in range(max(0, int(buffers)))]
        self._capacity = len(self._free)
        self._leases = 0
        self._misses = 0
        self._outstanding = 0

    def lease(self) -> Lease:
        """A buffer to ``recv_into`` — pooled when one is free, a
        fresh (counted) allocation otherwise."""
        with self._lock:
            self._leases += 1
            self._outstanding += 1
            if self._free:
                return Lease(self._free.pop(), pooled=True)
            self._misses += 1
        return Lease(bytearray(self.buf_bytes), pooled=False)

    def retain(self, lease: Lease) -> None:
        """One more frame's decoded views alias ``lease``."""
        with self._lock:
            self._retain_locked(lease)

    def release(self, lease: Lease) -> None:
        """Drop one ref; at zero the buffer rejoins the free list
        (pooled leases only — overflow allocations are dropped)."""
        with self._lock:
            self._release_locked(lease)

    def _retain_locked(self, lease: Lease) -> None:
        if lease.refs <= 0:
            raise RuntimeError("retain after final release")
        lease.refs += 1

    def _release_locked(self, lease: Lease) -> None:
        if lease.refs <= 0:
            raise RuntimeError("double release")
        lease.refs -= 1
        if lease.refs == 0:
            self._outstanding -= 1
            if lease.pooled and len(self._free) < self._capacity:
                self._free.append(lease.buf)

    def stats(self) -> dict:
        with self._lock:
            return {
                "capacity": self._capacity,
                "buf_bytes": self.buf_bytes,
                "free": len(self._free),
                "leases": self._leases,
                "misses": self._misses,
                "outstanding": self._outstanding,
            }
