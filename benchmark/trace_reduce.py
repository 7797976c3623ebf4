"""From a JAX profiler trace to the device numbers of a traced run.

Two stages, so the arithmetic can be tested on a small recorded copy:

1. :func:`extract` reads the ``.xplane.pb`` with
   ``jax.profiler.ProfileData`` and keeps plain lists: for each device
   plane (named ``/device:<PLATFORM>:<n>``) the events of its op line
   and of its module line, and the harness's ``bench.*`` annotations
   from the host plane. Nothing is matched by a guessed pattern: the
   device planes by the platform JAX reported, the verify executables
   by the jit names of the program's kernel functions.
2. :func:`reduce` works on those lists alone: busy time as the union
   of op intervals inside the ``bench.window`` annotation (averaged
   over devices), idle share, kernel time, the top device ops by self
   time, and idle time by what the host was doing (the innermost
   program span or harness annotation over each piece of each gap).

The interval union is ``tools/trace_kernel_time.py``'s arithmetic.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import shutil
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
# idle gaps shorter than this sit between the ops of one program and
# are counted together instead of being attributed one by one
SHORT_GAP_NS = 10_000

Interval = Tuple[float, float]


def union_ns(intervals: Sequence[Interval]) -> Tuple[float, List[Interval]]:
    """Covered length of [start, end) intervals, and the merged list."""
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def base_name(name: str) -> str:
    """A module event's executable name without XLA's run suffixes
    (``jit_f(12)``, ``jit_f.3``)."""
    name = name.split("(")[0].strip()
    return re.sub(r"\.\d+$", "", name)


def op_name(name: str) -> str:
    """An op event's name is its HLO text; keep the instruction name."""
    return name.split(" = ", 1)[0].strip()


def extract(xplane_path: str, platform: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    dev_re = re.compile(rf"^/device:{re.escape(platform.upper())}:(\d+)$")
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in pd.planes:
        m = dev_re.match(plane.name)
        lines = {ln.name: ln for ln in plane.lines}
        out["lines"][plane.name] = sorted(lines)
        if m:
            dev = {}
            for key, line in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                ln = lines.get(line)
                dev[key] = [] if ln is None else [
                    [op_name(ev.name), ev.start_ns, ev.duration_ns]
                    for ev in ln.events]
            out["devices"][m.group(1)] = dev
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        out["host"].append(
                            [ev.name, ev.start_ns, ev.duration_ns])
    out["host"].sort(key=lambda e: e[1])
    return out


def align_spans(events: dict, call_t0s: Sequence[float], spans: list,
                epoch: float) -> List[list]:
    """The program's flight-recorder spans on the trace clock. The
    offset between the perf clock and the trace clock is the median
    over calls of (``bench.call`` annotation start - the call's perf
    start); span starts are perf seconds ``epoch + start_ms / 1e3``."""
    starts = [e[1] for e in events["host"] if e[0] == "bench.call"]
    pairs = list(zip(starts, call_t0s))
    if not pairs:
        return []
    offs = sorted(s - t0 * 1e9 for s, t0 in pairs)
    off = offs[len(offs) // 2]
    out = []
    for r in spans:
        if r.get("dur_ms") is None or r.get("event"):
            continue
        start = (epoch + r["start_ms"] / 1e3) * 1e9 + off
        out.append([r["name"], start, r["dur_ms"] * 1e6])
    return out


def op_self_times(ops: Sequence[list], modules: Sequence[list], w0: float,
                  w1: float) -> Dict[str, float]:
    """Self time (an op's time less that of the ops nested in it, as a
    while loop holds its body) of each op inside [w0, w1), keyed
    ``<module>/<op>`` by the module execution it falls in."""
    mods = sorted((s, s + d, base_name(n)) for n, s, d in modules)
    evs = sorted(((max(s, w0), min(s + d, w1), n) for n, s, d in ops
                  if min(s + d, w1) > max(s, w0)),
                 key=lambda e: (e[0], -e[1]))
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []  # [end, key, self_ns]
    m = 0

    def close(entry):
        out[entry[1]] += entry[2] / 1e9

    for s, e, name in evs:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        while m < len(mods) and mods[m][1] <= s:
            m += 1
        mod = mods[m][2] if m < len(mods) and mods[m][0] <= s else "?"
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, f"{mod}/{name}", e - s])
    for entry in stack:
        close(entry)
    return out


def idle_by_context(gaps: Sequence[Interval], contexts: Sequence[list]
                    ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap is cut at the
    edges of the host contexts (harness annotations, program spans) that
    overlap it, and each piece goes to the innermost (shortest) one.
    Gaps under ``SHORT_GAP_NS`` lie between the ops of one program."""
    ctx = sorted((s, s + d, n) for n, s, d in contexts
                 if n != "bench.window")
    starts = [c[0] for c in ctx]
    longest = max((c[1] - c[0] for c in ctx), default=0.0)
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        if b - a < SHORT_GAP_NS:
            out["between ops (gap < 10 us)"] += (b - a) / 1e9
            continue
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_left(starts, b)
        over = [c for c in ctx[lo:hi] if c[1] > a]
        cuts = sorted({a, b} | {t for c in over for t in c[:2] if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            inner = [c for c in over if c[0] <= x and c[1] >= y]
            label = min(inner, key=lambda c: c[1] - c[0])[2] if inner \
                else "host (no span)"
            out[label] += (y - x) / 1e9
    return out


def reduce(events: dict, kernel_names: Sequence[str],
           spans: Optional[List[list]] = None) -> dict:
    """Device numbers of the traced window; ``busy_s`` None when the
    trace holds no device plane or no window annotation."""
    win = [e for e in events["host"] if e[0] == "bench.window"]
    out = {"busy_s": None, "window_s": None, "kernel_s": 0.0,
           "kernel_events": 0, "calls": 0, "breakdown": None}
    if not win or not events["devices"]:
        return out
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    # the profiler keeps a bounded number of device events (6,285,203
    # in traces of back-to-back one-row dispatches): past that the
    # trace goes silent while calls go on. The window then ends where the first call
    # with no device event left starts.
    last = max((s + d for dev in events["devices"].values()
                for _n, s, d in dev["ops"] or dev["modules"]), default=w0)
    calls = sorted(e[1] for e in events["host"] if e[0] == "bench.call")
    after = [c for c in calls if c > last]
    out["truncated"] = len(after) > 1
    if out["truncated"]:
        w1 = min(w1, after[0])
    out["calls"] = sum(1 for c in calls if w0 <= c < w1)
    out["window_s"] = (w1 - w0) / 1e9
    kernels = set(kernel_names)
    busy, gaps = [], []
    op_time: Dict[str, float] = defaultdict(float)
    for dev in events["devices"].values():
        ops = dev["ops"] or dev["modules"]
        total, merged = union_ns([(max(s, w0), min(s + d, w1))
                                  for _n, s, d in ops
                                  if min(s + d, w1) > max(s, w0)])
        busy.append(total)
        edge = w0
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if w1 > edge:
            gaps.append((edge, w1))
        for k, v in op_self_times(ops, dev["modules"], w0, w1).items():
            op_time[k] += v
        for name, s, d in dev["modules"]:
            if base_name(name) in kernels and w0 <= s < w1:
                out["kernel_s"] += d / 1e9
                out["kernel_events"] += 1
    out["busy_s"] = sum(busy) / len(busy) / 1e9
    idle = idle_by_context(gaps, list(events["host"]) + list(spans or []))
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gap_list = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    out["breakdown"] = {"device_ops": [[n, s] for n, s in ops],
                        "idle_gaps": [[n, s] for n, s in gap_list]}
    longest = sorted(((b - a) / 1e9 for a, b in gaps), reverse=True)
    out["summary"] = {"devices": sorted(events["devices"]),
                      "gaps": len(gaps), "longest_gaps_s": longest[:5],
                      "lines": events.get("lines")}
    return out


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def trim(events: dict, seconds: float) -> dict:
    """The first ``seconds`` of the traced window, as a small fixture."""
    w = [e for e in events["host"] if e[0] == "bench.window"][0]
    w0, w1 = w[1], w[1] + min(w[2], seconds * 1e9)

    def inside(evs):
        return [e for e in evs if w0 <= e[1] < w1]

    return {"devices": {d: {k: inside(v) for k, v in dev.items()}
                        for d, dev in events["devices"].items()},
            "host": [["bench.window", w0, w1 - w0]] + [
                e for e in inside(events["host"]) if e[0] != "bench.window"],
            "spans": inside(events.get("spans", [])),
            "lines": events.get("lines")}


def from_run(trace_dir: str, platform: str, w: dict,
             kernel_names: Sequence[str], tracing,
             keep: Optional[str] = None) -> dict:
    """Extract and reduce the trace a run wrote, then delete it. With
    ``keep`` a trimmed copy of the events is written there."""
    import time
    t0 = time.perf_counter()
    try:
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            return {"busy_s": None, "window_s": None, "kernel_s": 0.0,
                    "kernel_events": 0, "breakdown": None}
        size = os.path.getsize(found[0])
        events = extract(found[0], platform)
        t1 = time.perf_counter()
        rec = tracing.flight_recorder.snapshot(
            limit=tracing.flight_recorder.stats()["capacity"])["recent"]
        events["spans"] = align_spans(
            events, [c["t0"] for c in w["calls"]], rec, tracing._EPOCH)
        out = reduce(events, kernel_names, events["spans"])
        if keep:
            os.makedirs(keep, exist_ok=True)
            save(trim(events, 0.3), os.path.join(keep, "events.json.gz"))
        if out.get("summary") is not None:
            out["summary"].update(
                xplane_bytes=size, extract_s=t1 - t0,
                reduce_s=time.perf_counter() - t1,
                events=sum(len(v) for dev in events["devices"].values()
                           for v in dev.values()))
        return out
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
