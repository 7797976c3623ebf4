"""Fault-injection harness for chaos testing the dispatch layer.

Production code plants named injection points (``faults.inject("...")``)
at the spots whose real-world failure modes we must survive — the
device probe, the kernel dispatch, the device-array resolve. With no
fault armed the call is a dict lookup on an empty dict; with one armed
it misbehaves in a controlled, configurable way so
``tests/test_chaos_dispatch.py`` can drive the breaker/deadline/
failover machinery on CPU, no broken device required.

Faults are armed programmatically (:func:`set_fault`) or via the
``STELLAR_TPU_FAULTS`` environment variable, e.g.::

    STELLAR_TPU_FAULTS="device.resolve=hang:2;device.probe=raise"

Modes (``mode[:arg]``):

* ``raise[:msg]``   — raise :class:`FaultInjected` on every call;
* ``hang[:secs]``   — sleep ``secs`` (default 30) per call: the
  wedged-device shape, where calls block instead of raising;
* ``flake[:k]``     — raise on every k-th call (default 2): an
  intermittently healthy link;
* ``failn[:n]``     — raise on the first ``n`` calls (default 1), then
  behave: a link that recovers (breaker re-close path).

Per-device modes (``mode:<device_index>`` — fault ONE chip of a mesh,
the fault-domain chaos shapes of ``docs/robustness.md``):

* ``fail-device:<idx>``    — raise on every call attributed to mesh
  device ``idx``; other devices behave (single-chip outage);
* ``flaky-device:<idx>``   — raise on every 2nd call attributed to
  device ``idx`` (an intermittently sick chip, breaker flapping);
* ``corrupt-device:<idx>`` — never raises: calls succeed, but verdict
  arrays fetched from device ``idx`` come back BIT-FLIPPED via
  :func:`corrupt_verdicts` — the silently-corrupting-chip shape that
  only the result-integrity audit can catch;
* ``stall-device:<idx>``   — never raises: sleeps
  :data:`STALL_DEVICE_SECONDS` (``set_fault(..., seconds=)``
  overrides) before every call attributed to device ``idx`` — the
  host-side inter-dispatch stall shape the pipeline-bubble profiler
  must attribute as a bubble (ISSUE 10,
  ``tools/pipeline_selfcheck.py``);
* ``stall-transfer:<idx>`` — never raises: same sleep, but armed at
  the H2D UPLOAD point (``device.transfer``) instead of the kernel
  call — a slow host→device transfer lane, distinguishable from a
  slow kernel enqueue. The profiler must attribute the delay as
  ``queue_wait`` on the stalled device (the host was moving bytes,
  not encoding — prep-vs-queue_wait attribution, ISSUE 12).

Production code attributes a call to a device by passing
``inject(point, device=i)``; calls with ``device=None`` (single-device
dispatch) never match a per-device fault.

Wire modes (ISSUE 19 — the misbehaving-client shapes of the ingress
chaos gate, ``tools/ingress_selfcheck.py``). These are armed at
CLIENT-side points (by convention ``wire.client.<shape>``) and fire
through :func:`wire_plan` / :func:`send_mangled`, never through
``trip()`` — they mangle what a client PUTS ON THE WIRE, they do not
make server code misbehave:

* ``torn-frame``          — split every send at deterministic,
  call-count-derived byte offsets (every fragment is a legal TCP
  segmentation the server must reassemble);
* ``slow-client:<bytes/s>`` — trickle the send in small chunks with
  pacing sleeps: the slow-loris shape the per-connection read
  deadline must bound;
* ``disconnect-mid-batch`` — send roughly half the frame, then close
  the connection;
* ``garbage-prefix``      — prepend junk bytes that are not a valid
  frame type (the server must reject typed and drop the connection,
  never desync);
* ``oversize-frame``      — send a header declaring a length over the
  server's frame ceiling (the server must refuse WITHOUT buffering).

All five plans are deterministic — offsets and junk derive from the
fault's own call counter, never an RNG (``faults.py`` sits in the
lock-lint scope; the chaos mesh stays replayable).

Injection points currently planted:

* ``device.probe``    — inside the backend probe thread
  (``batch_verifier.start_device_probe``);
* ``device.transfer`` — immediately before the h2d operand upload
  (``device_put`` — per-device on the sub-chunk path, once per
  assigned device on the coalesced per-mesh upload);
* ``device.dispatch`` — immediately before the jitted kernel call
  (device-attributed on the per-device mesh path);
* ``device.resolve``  — inside the (deadline-guarded) device-array
  fetch (device-attributed; also the ``corrupt_verdicts`` hook).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

__all__ = ["FaultInjected", "inject", "corrupt_verdicts", "is_active",
           "set_fault", "clear", "counters", "load_spec",
           "wire_plan", "send_mangled", "WIRE_MODES"]

PROBE = "device.probe"
TRANSFER = "device.transfer"
DISPATCH = "device.dispatch"
RESOLVE = "device.resolve"

WIRE_MODES = ("torn-frame", "slow-client", "disconnect-mid-batch",
              "garbage-prefix", "oversize-frame")
_MODES = ("raise", "hang", "flake", "failn",
          "fail-device", "flaky-device", "corrupt-device",
          "stall-device", "stall-transfer") + WIRE_MODES
_DEVICE_MODES = ("fail-device", "flaky-device", "corrupt-device",
                 "stall-device", "stall-transfer")

# default sleep for stall-device (set_fault's ``seconds`` overrides)
STALL_DEVICE_SECONDS = 0.05

_lock = threading.Lock()
_active: Dict[str, "_Fault"] = {}


class FaultInjected(RuntimeError):
    """The exception raised by armed ``raise``/``flake``/``failn``
    faults — deliberately NOT a subclass of anything the dispatch layer
    special-cases, so injected faults exercise the generic handlers."""


class _Fault:
    def __init__(self, point: str, mode: str, arg: Optional[float],
                 seconds: Optional[float] = None):
        if mode not in _MODES:
            raise ValueError(f"unknown fault mode {mode!r} "
                             f"(one of {_MODES})")
        if mode in _DEVICE_MODES and arg is None:
            raise ValueError(f"{mode} needs a device index "
                             f"({mode}:<idx>)")
        self.point = point
        self.mode = mode
        self.arg = arg
        self.seconds = seconds
        self.calls = 0   # times the injection point was reached
        self.fired = 0   # times it actually misbehaved

    def trip(self, device: Optional[int] = None) -> None:
        if self.mode in WIRE_MODES:
            # wire faults mangle client SENDS (wire_plan), they never
            # fire at an inject() site
            return
        if self.mode in _DEVICE_MODES:
            # device-scoped faults only see (and only count) calls
            # attributed to their device; corruption never raises —
            # it is applied to the fetched verdicts, see
            # corrupt_verdicts()
            if device is None or int(device) != int(self.arg) or \
                    self.mode == "corrupt-device":
                return
        with _lock:
            self.calls += 1
            n = self.calls
        if self.mode in ("raise", "hang", "fail-device",
                         "stall-device", "stall-transfer"):
            fire = True
        elif self.mode in ("flake", "flaky-device"):
            k = 2 if self.mode == "flaky-device" else \
                int(self.arg if self.arg else 2)
            fire = n % k == 0
        else:  # failn
            fire = n <= int(self.arg if self.arg is not None else 1)
        if not fire:
            return
        with _lock:
            self.fired += 1
        if self.mode == "hang":
            time.sleep(float(self.arg) if self.arg is not None else 30.0)
            return
        if self.mode in ("stall-device", "stall-transfer"):
            # a stall, not a failure: the dispatch/upload proceeds
            # after the sleep — the bubble profiler must SEE the
            # delay, nothing in the fault-tolerance machinery should
            # trip on it
            time.sleep(self.seconds if self.seconds is not None
                       else STALL_DEVICE_SECONDS)
            return
        raise FaultInjected(f"injected fault at {self.point} "
                            f"({self.mode}, call #{n})")


def inject(point: str, device: Optional[int] = None) -> None:
    """Trip the fault armed at ``point``; no-op when nothing is armed.
    This is the call production code plants at an injection site.
    ``device`` attributes the call to one mesh device so per-device
    fault shapes can single it out."""
    if not _active:  # fast path: chaos off
        return
    f = _active.get(point)
    if f is not None:
        f.trip(device=device)


def corrupt_verdicts(point: str, device: Optional[int], arr):
    """Result-corruption hook: with ``corrupt-device:<idx>`` armed at
    ``point`` and ``device`` matching, returns the verdict array
    BIT-FLIPPED (and counts a fire); otherwise returns ``arr``
    unchanged. Planted where the dispatch layer materializes device
    verdicts — the silently-wrong-bits chip that hangs nothing and
    raises nothing, detectable only by re-verifying results."""
    if not _active:  # fast path: chaos off
        return arr
    f = _active.get(point)
    if f is None or f.mode != "corrupt-device" or device is None or \
            int(device) != int(f.arg):
        return arr
    with _lock:
        f.calls += 1
        f.fired += 1
    return ~arr


def is_active(point: str) -> bool:
    return point in _active


def set_fault(point: str, mode: str, arg: Optional[float] = None,
              seconds: Optional[float] = None) -> None:
    """Arm ``point`` with ``mode`` (see module docstring);
    ``seconds`` overrides the stall-device sleep."""
    f = _Fault(point, mode, arg, seconds=seconds)
    with _lock:
        _active[point] = f


def clear(point: Optional[str] = None) -> None:
    """Disarm one point, or every point when ``point`` is None."""
    with _lock:
        if point is None:
            _active.clear()
        else:
            _active.pop(point, None)


def counters() -> Dict[str, dict]:
    """Per-point {calls, fired} — how often each armed site was reached
    and how often it actually misbehaved (chaos-test assertions)."""
    with _lock:
        return {p: {"mode": f.mode, "calls": f.calls, "fired": f.fired}
                for p, f in _active.items()}


def wire_plan(point: str, nbytes: int) -> Optional[dict]:
    """The mangling plan for one client send of ``nbytes`` at
    ``point`` — None when no wire fault is armed there. Plans are
    pure functions of the fault's own call counter (no RNG, no
    clock), so a chaos run's byte stream is replayable. Counts a
    call AND a fire per consult — every armed send misbehaves."""
    if not _active:  # fast path: chaos off
        return None
    f = _active.get(point)
    if f is None or f.mode not in WIRE_MODES:
        return None
    with _lock:
        f.calls += 1
        f.fired += 1
        n = f.calls
    if f.mode == "torn-frame":
        span = max(1, nbytes - 1)
        splits = sorted({1 + (n * 7) % span,
                         1 + (n * 13 + 3) % span,
                         1 + (n * 29 + 11) % span})
        return {"mode": "torn-frame", "splits": splits}
    if f.mode == "slow-client":
        rate = float(f.arg) if f.arg else 4096.0
        chunk = 16
        return {"mode": "slow-client", "chunk": chunk,
                "sleep_s": chunk / max(1.0, rate)}
    if f.mode == "disconnect-mid-batch":
        return {"mode": "disconnect-mid-batch",
                "cut": max(1, nbytes // 2)}
    if f.mode == "garbage-prefix":
        junk = bytes(16 + (n * 31 + i * 7) % 224 for i in range(8))
        return {"mode": "garbage-prefix", "junk": junk}
    # oversize-frame: a header declaring arg (default 2x the codec
    # ceiling) payload bytes, plus a little filler so the server's
    # reject provably fires on the DECLARATION, not a read timeout
    declared = int(f.arg) if f.arg else 2 * (1 << 20)
    return {"mode": "oversize-frame", "declared": declared}


def send_mangled(sock, data, point: str) -> bool:
    """Send ``data`` on ``sock`` through the wire fault armed at
    ``point`` (plain ``sendall`` when none is). Returns False when
    the plan deliberately closed the connection (the
    disconnect-mid-batch shape), True otherwise. Never called with a
    lock held — sends and pacing sleeps block."""
    plan = wire_plan(point, len(data))
    if plan is None:
        sock.sendall(data)
        return True
    mode = plan["mode"]
    if mode == "torn-frame":
        pos = 0
        for cut in plan["splits"] + [len(data)]:
            if cut > pos:
                sock.sendall(data[pos:cut])
                pos = cut
        return True
    if mode == "slow-client":
        for off in range(0, len(data), plan["chunk"]):
            sock.sendall(data[off:off + plan["chunk"]])
            time.sleep(plan["sleep_s"])
        return True
    if mode == "disconnect-mid-batch":
        sock.sendall(data[:plan["cut"]])
        # shutdown acts on the connection itself (close alone leaves
        # the kernel description alive while a reader thread is
        # blocked in recv — no FIN would reach the server)
        import socket as _socket
        try:
            sock.shutdown(_socket.SHUT_RDWR)
        except OSError:
            pass
        sock.close()
        return False
    if mode == "garbage-prefix":
        sock.sendall(plan["junk"] + bytes(data))
        return True
    # oversize-frame: bogus SUBMIT header + filler instead of data
    import struct as _struct
    sock.sendall(_struct.pack(">BI", 0x01, plan["declared"])
                 + b"\x00" * 16)
    return True


def load_spec(spec: str) -> None:
    """Parse a ``point=mode[:arg][;point=mode[:arg]...]`` spec string
    (the ``STELLAR_TPU_FAULTS`` grammar) and arm each entry."""
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        point, _, rhs = part.partition("=")
        mode, _, arg = rhs.partition(":")
        set_fault(point.strip(), mode.strip(),
                  float(arg) if arg else None)


_env_spec = os.environ.get("STELLAR_TPU_FAULTS", "")
if _env_spec:
    load_spec(_env_spec)
