"""The benchmark's harness: reads ``BENCHMARK.json``, finds a cell's
configuration, traffic mix and metric readers by name, drives the
timed path in a closed loop, checks every verdict against the plain
reference and prints one result line.

Driven by data: a configuration is ``configs/<name>.json`` (named by
the ``file`` of its entry), a traffic mix ``traffic/<name>.json``, a
metric ``metrics/<name>.py`` with ``read(ctx) -> float | None``. A
later PR adds any of them as new files and a new entry, and edits
nothing here.

The timed path is the entry the traffic mix names (``ProgramPath``)
with ``batch_verifier.default_verifier().install()`` done in set-up,
as ``apply-load --verify device`` wires it: one tx set through
``keys.batch_verify_into_cache``, or one SCP envelope through
``Herder.verify_envelope``, timed on the host clock. Verdicts are read
back and the result cache is flushed outside the timed interval, so
every call is a miss.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Callable, List, Optional


ROOT = Path(__file__).resolve().parent.parent
TRACE_WINDOW_S = 3.0    # a --trace 1 run measures this much of the mix,
                        # unless the mix gives its own trace_seconds
REF_WORKERS = 10        # reference processes after the window (13 cores)


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


# ---------------- the spec, found by name ----------------

def load_spec(root: Path) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def plan(root: Path, cell_name: str) -> dict:
    """Everything one cell needs, from the files ``BENCHMARK.json``
    names: the cell, its configuration and traffic mix, and the
    end-to-end and per-layer metrics it reports."""
    spec = load_spec(root)
    cells = {c["name"]: c for c in spec["workloads"]}
    if cell_name not in cells:
        raise SpecError(f"no cell {cell_name!r} in BENCHMARK.json")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs.get(cell["config"])
    if entry is None:
        raise SpecError(f"cell {cell_name!r} names no known config")
    config = _json_file(root / entry["file"])
    mix = _json_file(root / "benchmark" / "traffic" / f"{cell['traffic']}.json")

    def mine(m):
        return "workloads" not in m or cell_name in m["workloads"]

    return {"cell": cell, "config": config, "mix": mix,
            "run_seconds": spec["run_seconds"],
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def _json_file(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def reader(root: Path, name: str) -> Callable[[dict], Optional[float]]:
    path = root / "benchmark" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(root: Path, device_kind: str) -> dict:
    table = _json_file(root / "benchmark" / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        "benchmark/peaks.json")
    return table[device_kind]


# ---------------- accounting (copied from chip_smoke.py) ----------------

def snapshot() -> dict:
    from stellar_tpu.crypto import batch_verifier
    from stellar_tpu.utils.metrics import registry
    h = batch_verifier.dispatch_health()
    return {
        "served": dict(h["served"]),
        "fallback_chunks": h["fallback_chunks"],
        "deadline_misses": h["deadline_misses"],
        "retries": h["retries"],
        "short_circuits": h["short_circuits"],
        "audit_mismatches": h["audit"]["mismatches"],
        "breaker": h["breaker"]["state"],
        "quarantined": list(h["device_health"]["quarantined"]),
        "host_only": h["host_only"],
        "resident_hits": h["transfer"]["resident_hits"],
        "hot_rows": registry.meter(
            "crypto.verify.signer_table.hot_rows").count,
        "cold_rows": registry.meter(
            "crypto.verify.signer_table.cold_rows").count,
    }


def strict_problems(before: dict, after: dict) -> List[str]:
    """Everything in one call's accounting that says a row was not
    decided by a healthy device (``chip_smoke.strict_problems``)."""
    from stellar_tpu.utils import faults
    problems = []
    if os.environ.get("STELLAR_TPU_FAULTS"):
        problems.append("STELLAR_TPU_FAULTS is set")
    if faults.counters():
        problems.append(f"faults armed: {sorted(faults.counters())}")
    fb = after["served"]["host_fallback"] - before["served"]["host_fallback"]
    if fb:
        problems.append(f"host_fallback rows: {fb}")
    for key in ("fallback_chunks", "deadline_misses", "retries",
                "short_circuits", "audit_mismatches"):
        d = after[key] - before[key]
        if d:
            problems.append(f"{key}: {d}")
    if after["breaker"] != "closed":
        problems.append(f"breaker {after['breaker']}")
    if after["quarantined"]:
        problems.append(f"quarantined devices: {after['quarantined']}")
    if after["host_only"]:
        problems.append("host_only")
    return problems


# ---------------- timed paths ----------------

class ProgramPath:
    """The system under test in the program's default configuration
    (no ``VERIFY_*`` variable, no dispatch knob), with
    ``batch_verifier.default_verifier().install()`` done in set-up, as
    ``apply-load --verify device`` wires it. The traffic mix's
    ``entry`` names what one call drives:

    * ``batch_verify_into_cache`` (the default): the call's rows in one
      ``keys.batch_verify_into_cache``, the boundary the node's bulk
      paths call (``herder/tx_set.py:prefetch_signature_batch``,
      catchup); verdicts read back with ``keys.cached_verify_sig``;
    * ``verify_envelope``: each row as an ``SCPEnvelope`` through
      ``Herder.verify_envelope``, as ``recv_scp_envelope`` hands it
      every envelope that arrives; verdicts are its return values.

    ``prepare`` turns rows into what the call takes, outside the timed
    interval; ``readback`` flushes the result cache, so every call is
    a miss."""

    name = "program"
    on_device = True
    ENTRIES = ("batch_verify_into_cache", "verify_envelope")

    entry = "batch_verify_into_cache"
    network_id = b""
    _herder = None

    def bind(self, entry: str, network_id: bytes) -> None:
        if entry not in self.ENTRIES:
            raise SpecError(f"unknown entry {entry!r}")
        self.entry, self.network_id = entry, network_id

    def setup(self) -> None:
        from stellar_tpu.crypto import batch_verifier
        batch_verifier.default_verifier().install()

    def prepare(self, rows):
        if self.entry == "batch_verify_into_cache":
            return rows
        from stellar_tpu.herder.herder import Herder, scp_envelope_sign_payload
        from stellar_tpu.xdr.runtime import from_bytes
        from stellar_tpu.xdr.scp import SCPEnvelope, SCPStatement
        if self._herder is None:
            # verify_envelope reads only the herder's network id
            self._herder = Herder.__new__(Herder)
            self._herder.network_id = self.network_id
        envs = []
        for pk, msg, sig in rows:
            try:
                stmt = from_bytes(SCPStatement, msg[36:])
                ok = stmt.nodeID.value == pk and scp_envelope_sign_payload(
                    self.network_id, stmt) == msg
            except Exception:
                ok = False
            if not ok:
                raise SpecError("a row of the mix is no SCP envelope")
            envs.append(SCPEnvelope(statement=stmt, signature=sig))
        return envs

    def call(self, prepared) -> Optional[list]:
        if self.entry == "batch_verify_into_cache":
            from stellar_tpu.crypto import keys
            keys.batch_verify_into_cache(prepared)
            return None
        from stellar_tpu.herder.herder import Herder
        return [Herder.verify_envelope(self._herder, e) for e in prepared]

    def readback(self, rows, got) -> list:
        from stellar_tpu.crypto import keys
        if got is None:
            out = [keys.cached_verify_sig(pk, m, s) for pk, m, s in rows]
        else:
            out = list(got) + [None] * (len(rows) - len(got))
        keys.flush_verify_cache()
        return out


class HostOraclePath(ProgramPath):
    """The CPU rehearsal's timed path: the same entry, ``keys`` routing
    and result cache with the program's host oracle installed as
    backend, so a rehearsal compiles no kernel."""

    name = "host-oracle"
    on_device = False

    def setup(self) -> None:
        from stellar_tpu.crypto import ed25519_ref, keys
        keys.set_verifier_backend(ed25519_ref.verify)


class ControlPath:
    """The control put in the program's place: the reference with one
    guarantee broken (``benchmark.reference.CHECKS``)."""

    on_device = False

    def __init__(self, check: str):
        from benchmark import reference
        self.name = f"control:{check}"
        self._fn = reference.CHECKS[check]

    def bind(self, entry: str, network_id: bytes) -> None:
        pass

    def setup(self) -> None:
        pass

    def prepare(self, rows):
        return rows

    def call(self, rows) -> list:
        return [self._fn(pk, m, s) for pk, m, s in rows]

    def readback(self, rows, got) -> list:
        return got


# ---------------- the run ----------------

class _CompileCounter:
    """Traces, lowerings, compiles and persistent-cache reads, from
    JAX's monitoring events; the window should see none. Listeners
    cannot be removed, so one counter serves the process."""

    _installed = None

    def __init__(self):
        self.events = 0

    @classmethod
    def get(cls) -> "_CompileCounter":
        if cls._installed is None:
            import jax.monitoring as mon
            c = cls()

            def on_duration(event, _duration, **_kw):
                if event.startswith(("/jax/core/compile/",
                                     "/jax/compilation_cache/")):
                    c.events += 1

            def on_event(event, **_kw):
                if event.startswith("/jax/compilation_cache/"):
                    c.events += 1

            mon.register_event_duration_secs_listener(on_duration)
            mon.register_event_listener(on_event)
            cls._installed = c
        return cls._installed


def window(traffic, path, seconds: float, annotate) -> dict:
    """One caller. In a closed loop each call starts when the last one
    ended and its latency is its own duration. With ``arrivals`` a
    group of calls (a burst) arrives at a fixed instant of the window;
    the caller waits for it, then serves its calls in turn, and each
    call's latency runs from the burst's arrival to its verdict, so a
    call pays for the calls served before it. Rows are made and
    prepared before the arrival, verdicts read back after the group,
    both outside every latency. Returns the per-call records, the
    groups and the window's wall time (open to the end of the last
    call)."""
    calls, groups = [], []
    t_open = time.perf_counter()
    deadline = t_open + seconds
    i = 0
    while True:
        due = traffic.arrival_s(i)
        if (due is None and time.perf_counter() >= deadline) or \
                (due is not None and due >= seconds):
            break
        with annotate("bench.generate"):
            made = []
            for k in traffic.group(i):
                rows = traffic.call(k)
                made.append((rows, path.prepare(rows)))
        before = snapshot() if path.on_device else None
        if due is not None:
            with annotate("bench.wait"):
                _sleep_until(t_open + due)
        group = {"calls": [], "problems": []}
        got = []
        for rows, prepared in made:
            err = None
            with annotate("bench.call"):
                t0 = time.perf_counter()
                try:
                    out = path.call(prepared)
                except Exception:
                    out, err = None, traceback.format_exc(limit=6)
                t1 = time.perf_counter()
            start = t0 if due is None else t_open + due
            rec = {"rows": rows, "t0": t0, "dur_s": t1 - t0,
                   "latency_s": t1 - start, "problems": group["problems"]}
            if err is not None:
                group["problems"].append(err)
            got.append((out, err))
            group["calls"].append(len(calls))
            calls.append(rec)
        with annotate("bench.readback"):
            for (rows, _), (out, err), n in zip(made, got, group["calls"]):
                calls[n]["verdicts"] = [None] * len(rows) if err else \
                    path.readback(rows, out)
            after = snapshot() if path.on_device else None
        if before is not None:
            group["problems"] += strict_problems(before, after)
            group["device_rows"] = (after["served"]["device"]
                                    - before["served"]["device"])
        groups.append(group)
        i += len(made)
    return {"calls": calls, "groups": groups, "t_open": t_open,
            "window_s": time.perf_counter() - t_open}


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(left if left < 0.002 else left - 0.001)


def check(calls: list, groups: list, workers: int) -> dict:
    """Every row of every timed call against the plain reference. A
    verdict that never came (None) counts as unanswered. Also fails a
    group of calls whose device-served rows are fewer than its rows
    that pass the policy gate (work moved quietly to the host)."""
    from benchmark import reference
    rows = [r for c in calls for r in c["rows"]]
    got = [v for c in calls for v in c["verdicts"]]
    want, gate = reference.verdicts(rows, workers)
    mismatched = sum(1 for g, w in zip(got, want)
                     if g is not None and bool(g) != w)
    unanswered = sum(1 for g in got if g is None)
    passing, at = [], 0
    for c in calls:
        n = len(c["rows"])
        passing.append(sum(gate[at:at + n]))
        at += n
    for g in groups:
        want_rows = sum(passing[n] for n in g["calls"])
        if "device_rows" in g and g["device_rows"] < want_rows:
            g["problems"].append(
                f"device served {g['device_rows']} rows of {want_rows} "
                "that pass the policy gate")
    return {"rows": len(rows), "mismatched_rows": mismatched,
            "unanswered_rows": unanswered,
            "invalid_rows": sum(1 for w in want if not w)}


def main(argv=None, *, root: Path = ROOT, path=None,
         t_start: Optional[float] = None) -> int:
    """Run one cell; returns the exit code. ``path`` replaces the timed
    path (tests break it underneath); ``t_start`` is the process's
    start on the perf clock (``run.py`` takes it before any import)."""
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="stellar_tpu benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("no_policy_gate",),
                    help="put the control in the program's place")
    ap.add_argument("--cpu-rehearsal", type=int, metavar="UNITS",
                    help="builder's rehearsal on the CPU, UNITS per "
                    "call, host oracle behind keys.py; reports no "
                    "device number and exits 1")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also write the reduced trace events here")
    args = ap.parse_args(argv)

    try:
        p = plan(root, args.workload)
    except (SpecError, KeyError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    rehearsal = args.cpu_rehearsal is not None
    if rehearsal:
        from stellar_tpu.utils.jax_setup import force_cpu
        force_cpu()
        size = p["mix"].get("call_size") or p["config"]["call_size"]
        units = args.cpu_rehearsal
        if size["law"] == "fixed":
            units = min(units, int(size["units"]))
        p["mix"] = dict(p["mix"], call_size={"law": "fixed",
                                             "units": units})
    machine_cache = {k: os.environ.get(k) for k in (
        "JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE")}
    if not rehearsal:
        # the compile cache lives inside the checkout, at a fixed path,
        # without eviction (whose access-time files a cache set up by
        # the machine may lack)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"

    marks = {"start": time.perf_counter() - t_start}
    import jax
    devices = jax.devices()
    marks["jax_devices"] = time.perf_counter() - t_start
    platform = devices[0].platform
    chips = int(p["cell"]["chips"])
    if not rehearsal:
        if platform != "tpu" or len(devices) != chips:
            print(f"benchmark: cell {args.workload} needs {chips} TPU "
                  f"chip(s); JAX found {len(devices)} {platform} "
                  "device(s)", file=sys.stderr)
            return 3
        try:
            peak = peaks(root, devices[0].device_kind)
        except SpecError as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return 3
    else:
        peak = None
    from stellar_tpu.utils.jax_setup import enable_compile_cache
    enable_compile_cache()
    compiles = _CompileCounter.get()

    from benchmark import traffic as traffic_mod
    traffic = traffic_mod.Traffic(p["config"], p["mix"], args.seed)
    warm = traffic.warmup()
    marks["traffic"] = time.perf_counter() - t_start
    if path is None:
        if args.control:
            path = ControlPath(args.control)
        elif rehearsal:
            path = HostOraclePath()
        else:
            path = ProgramPath()
    try:
        path.bind(p["mix"].get("entry", "batch_verify_into_cache"),
                  getattr(traffic, "network_id", b""))
    except SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    path.setup()
    from stellar_tpu.utils import tracing
    for rows in warm:
        path.readback(rows, path.call(path.prepare(rows)))
    marks["warmup"] = time.perf_counter() - t_start
    seconds = min(args.seconds, float(p["mix"].get(
        "trace_seconds", TRACE_WINDOW_S))) if args.trace else args.seconds
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    spans0 = tracing.span_totals()
    acct0 = snapshot()
    compiles0 = compiles.events
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # annotations only: no per-call cost
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    else:
        def annotate(_name):
            return contextlib.nullcontext()
    setup_s = time.perf_counter() - t_start
    with annotate("bench.window"):
        w = window(traffic, path, seconds, annotate)
    if args.trace:
        jax.profiler.stop_trace()
    compiles_in_window = compiles.events - compiles0
    spans1 = tracing.span_totals()
    acct1 = snapshot()
    memory_peak = _memory_peak(devices)
    calls = w["calls"]

    trace = None
    if args.trace:
        from benchmark import trace_reduce
        trace = trace_reduce.from_run(
            trace_dir, platform, w, kernel_names(), tracing,
            keep=args.keep_trace)

    chk = check(calls, w["groups"], 0 if rehearsal else REF_WORKERS)
    failed = sum(1 for c in calls if c["problems"])
    ctx = {"calls": calls, "window_s": w["window_s"], "setup_s": setup_s,
           "spans": _delta(spans0, spans1),
           "accounting": {k: acct1[k] - acct0[k] for k in
                          ("hot_rows", "cold_rows", "resident_hits")},
           "trace": trace}
    wanted = p["per_layer"] if args.trace else p["end_to_end"]
    metrics = {}
    for m in wanted:
        value = reader(root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    info = {"cell": args.workload, "seed": args.seed, "trace": args.trace,
            "path": path.name, "calls": len(calls),
            "rows": chk["rows"], "invalid_rows": chk["invalid_rows"],
            "mismatched_rows": chk["mismatched_rows"],
            "unanswered_rows": chk["unanswered_rows"],
            "window_s": w["window_s"], "setup_s": setup_s,
            "setup_marks_s": marks,
            "compiles_in_window": compiles_in_window,
            "resident_hits": ctx["accounting"]["resident_hits"],
            "hot_rows": ctx["accounting"]["hot_rows"],
            "cold_rows": ctx["accounting"]["cold_rows"],
            "memory_peak_bytes": memory_peak, "peaks": peak,
            "machine_cache_env": machine_cache,
            "failed_examples": [c["problems"][:3] for c in calls
                                if c["problems"]][:3],
            "slowest_calls": _slowest(calls, w["t_open"])}
    if trace is not None:
        info["trace_summary"] = trace.get("summary")
        if trace.get("kernel_s"):
            from benchmark import kernel_macs
            per_row = kernel_macs.macs_per_row()
            macs = (ctx["accounting"]["hot_rows"] * per_row["hot"]
                    + ctx["accounting"]["cold_rows"] * per_row["cold"])
            info["kernel_macs_per_row"] = per_row
            info["achieved_macs_per_s"] = macs / trace["kernel_s"]
    print(json.dumps(info), flush=True)
    if rehearsal:
        print(json.dumps({"rehearsal_metrics": metrics}), flush=True)

    # one exact comparison: a verdict that differs from the reference
    # or never came is a wrong row (the split is on the info line)
    limits = {"wrong_rows": (chk["mismatched_rows"]
                             + chk["unanswered_rows"], 0)}
    correct = all(v <= lim for v, lim in limits.values())
    checks = {k: {"value": v, "limit": lim} for k, (v, lim) in limits.items()}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace is not None and trace.get("busy_s") is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    result = {"correct": correct, "attempted": len(calls),
              "failed": failed,
              "metrics": {} if rehearsal else metrics, "device": device}
    if trace is not None and trace.get("breakdown"):
        result["breakdown"] = trace["breakdown"]
    result["checks"] = checks
    for k, (v, lim) in limits.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 1 if rehearsal else 0


def kernel_names() -> list:
    """The jit names of the verify executables, from the program's
    kernel functions."""
    from stellar_tpu.ops import verify
    return [f"jit_{verify.verify_kernel.__name__}",
            f"jit_{verify.verify_kernel_hot.__name__}"]


def _slowest(calls: list, t_open: float, n: int = 5) -> list:
    """Where a tail comes from: the ``n`` longest calls as [seconds
    into the window, own duration ms, latency ms, ms between the end
    of the call before it (or its arrival, if later) and its start]."""
    out = []
    for k, c in enumerate(calls):
        prev = calls[k - 1]["t0"] + calls[k - 1]["dur_s"] if k else t_open
        arrived = c["t0"] + c["dur_s"] - c["latency_s"]
        out.append([c["t0"] - t_open, c["dur_s"] * 1e3,
                    c["latency_s"] * 1e3,
                    max(0.0, c["t0"] - max(prev, arrived)) * 1e3])
    return sorted(out, key=lambda r: -r[1])[:n]


def _delta(before: dict, after: dict) -> dict:
    out = {}
    for k, a in after.items():
        b = before.get(k, {"count": 0, "sum_ms": 0.0})
        c = a["count"] - b["count"]
        if c:
            out[k] = {"count": c, "sum_ms": a["sum_ms"] - b["sum_ms"]}
    return out


def _memory_peak(devices) -> Optional[int]:
    peaks_seen = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks_seen.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_seen) if peaks_seen else None
