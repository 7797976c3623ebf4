#!/usr/bin/env python
"""Extract device-side kernel time from a jax.profiler perfetto trace.

VERDICT r4 #1c: the kernel-time claim must come from the profiler, not
from subtracting a dispatch floor.  Usage:

    python tools/trace_kernel_time.py TRACE.trace.json.gz [n_iters]

Prints one JSON line: per-device-process busy time (union of complete
event intervals, so nested events are not double-counted) divided by
``n_iters`` (the number of traced kernel invocations).
"""
import gzip
import json
import re
import sys

DEVICE_PAT = re.compile(r"/device:|TPU|tpu", re.I)
HOST_PAT = re.compile(r"python|host|CUPTI", re.I)


def union_ms(intervals):
    """Total covered time of [start, end) intervals, in ms."""
    if not intervals:
        return 0.0
    intervals.sort()
    total = 0.0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    total += cur_e - cur_s
    return total / 1000.0  # trace ts/dur are microseconds


def analyze(path, n_iters):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        trace = json.load(f)
    events = trace if isinstance(trace, list) else \
        trace.get("traceEvents", [])
    pid_names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev.get("pid")] = ev.get("args", {}).get("name", "")
    per_pid = {}
    top_events = {}
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        pid = ev.get("pid")
        per_pid.setdefault(pid, []).append(
            (float(ev["ts"]), float(ev["ts"]) + float(ev["dur"])))
        name = ev.get("name", "")
        rec = top_events.setdefault((pid, name), [0, 0.0])
        rec[0] += 1
        rec[1] += float(ev["dur"]) / 1000.0
    out = {"trace": path, "n_iters": n_iters, "processes": {}}
    device_busy = 0.0
    for pid, ivals in per_pid.items():
        name = pid_names.get(pid, f"pid{pid}")
        busy = union_ms(ivals)
        out["processes"][name] = {
            "busy_ms_total": round(busy, 3),
            "busy_ms_per_iter": round(busy / max(1, n_iters), 3),
            "n_events": len(ivals),
        }
        if DEVICE_PAT.search(name) and not HOST_PAT.search(name):
            device_busy += busy
    out["device_busy_ms_per_iter"] = round(
        device_busy / max(1, n_iters), 3)
    # top 8 device ops by total duration, for the "where does the time
    # go" question
    dev_ops = [(n, c, d) for (pid, n), (c, d) in top_events.items()
               if DEVICE_PAT.search(pid_names.get(pid, ""))
               and not HOST_PAT.search(pid_names.get(pid, ""))]
    dev_ops.sort(key=lambda t: -t[2])
    out["top_device_ops"] = [
        {"name": n[:80], "count": c, "total_ms": round(d, 3)}
        for n, c, d in dev_ops[:8]]
    return out


def main():
    if len(sys.argv) < 2:
        print(__doc__)
        return 2
    n_iters = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    print(json.dumps(analyze(sys.argv[1], n_iters)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
