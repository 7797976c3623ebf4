"""The trace reduction: interval union, busy and idle share, kernel time,
top ops and idle gaps, on synthetic events, on a trace recorded here
(CPU: no device plane) and on a trimmed trace recorded on the chip."""

from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

FIXTURE = Path(__file__).parent / "fixtures" / "tpu_txset_events.json.gz"
KERNELS = ["jit_verify_kernel", "jit_verify_kernel_hot"]
# the fixture's numbers as reduced when it was recorded (0.1 s: one
# 2,000-row call, its hot and its cold kernel execution)
EXPECTED = {"busy_s": 0.024637, "window_s": 0.1, "kernel_s": 0.024641}


def test_union_merges_overlaps_and_keeps_gaps():
    total, merged = tr.union_ns([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert total == 3 + 3 + 1
    assert merged == [(0, 3), (5, 8), (10, 11)]
    assert tr.union_ns([]) == (0, [])


def test_base_name_drops_run_suffixes():
    assert tr.base_name("jit_verify_kernel_hot(1234)") == \
        "jit_verify_kernel_hot"
    assert tr.base_name("jit_verify_kernel.3") == "jit_verify_kernel"


def _events():
    # window [0, 1000); ops cover [100, 300) and [500, 600) (nested op
    # at 150-200 counted once); one module of each kernel
    return {
        "devices": {"0": {
            "ops": [["fusion.1", 100, 200], ["fusion.2", 150, 50],
                    ["copy.3", 500, 100], ["late", 990, 50]],
            "modules": [["jit_verify_kernel_hot(7)", 100, 200],
                        ["jit_verify_kernel(9)", 500, 100],
                        ["jit_other(1)", 700, 10]]}},
        "host": [["bench.window", 0, 1000], ["bench.call", 50, 600],
                 ["bench.generate", 700, 250]],
    }


def test_reduce_busy_idle_kernel_ops_and_gaps():
    spans = [["span.verify.prep", 20, 60], ["span.verify.audit", 320, 150]]
    out = tr.reduce(_events(), KERNELS, spans)
    assert out["window_s"] == pytest.approx(1000e-9)
    # [100,300) + [500,600) + [990,1000) clipped to the window
    assert out["busy_s"] == pytest.approx(310e-9)
    assert out["kernel_s"] == pytest.approx(300e-9)
    assert out["kernel_events"] == 2
    # self time, keyed by the module execution the op falls in: the
    # nested fusion.2 is taken out of fusion.1; "late" is in no module
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["jit_verify_kernel_hot/fusion.1"] == pytest.approx(150e-9)
    assert ops["jit_verify_kernel_hot/fusion.2"] == pytest.approx(50e-9)
    assert ops["jit_verify_kernel/copy.3"] == pytest.approx(100e-9)
    assert ops["?/late"] == pytest.approx(10e-9)
    # gaps: [0,100) under prep... all shorter than 10 us here
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(690e-9)


def test_gaps_are_labelled_by_the_innermost_host_context():
    ev = _events()
    scale = 100  # make every gap longer than SHORT_GAP_NS
    for dev in ev["devices"].values():
        for key in ("ops", "modules"):
            dev[key] = [[n, s * scale, d * scale] for n, s, d in dev[key]]
    ev["host"] = [[n, s * scale, d * scale] for n, s, d in ev["host"]]
    spans = [["span.verify.audit", 320 * scale, 150 * scale]]
    gaps = dict(tr.reduce(ev, KERNELS, spans)["breakdown"]["idle_gaps"])
    # gaps [0,100) [300,500) [600,990), each cut at the context edges:
    # call [50,650), audit [320,470), generate [700,950)
    ns = 1e-9 * scale
    assert gaps["bench.call"] == pytest.approx((50 + 20 + 30 + 50) * ns)
    assert gaps["span.verify.audit"] == pytest.approx(150 * ns)
    assert gaps["bench.generate"] == pytest.approx(250 * ns)
    assert gaps["host (no span)"] == pytest.approx((50 + 50 + 40) * ns)


def test_a_trace_that_goes_silent_ends_the_window():
    """Past the profiler's event budget the device trace stops while
    calls go on: the window ends at the first call with no event."""
    ev = _events()
    ev["host"] += [["bench.call", 800, 20], ["bench.call", 900, 20]]
    ev["devices"]["0"]["ops"] = ev["devices"]["0"]["ops"][:3]  # end 600
    out = tr.reduce(ev, KERNELS)
    assert out["truncated"] and out["window_s"] == pytest.approx(800e-9)
    assert out["calls"] == 1  # the call at 50; 800 and 900 are cut off
    assert out["busy_s"] == pytest.approx(300e-9)
    assert not tr.reduce(_events(), KERNELS)["truncated"]


def test_no_device_plane_reads_nothing():
    ev = _events()
    ev["devices"] = {}
    out = tr.reduce(ev, KERNELS)
    assert out["busy_s"] is None and out["breakdown"] is None


def test_extract_reads_a_recorded_cpu_trace(tmp_path):
    import glob
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.call"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    ev = tr.extract(path, "cpu")
    names = [e[0] for e in ev["host"]]
    assert names == ["bench.window", "bench.call"]
    assert ev["devices"] == {}  # the CPU backend has no device plane
    assert tr.reduce(ev, KERNELS)["busy_s"] is None


def _sweep_busy(intervals):
    """Busy time by a sweep over +1/-1 endpoints: another algorithm
    than the merge it checks."""
    points = sorted([(s, 1) for s, e in intervals] +
                    [(e, -1) for s, e in intervals])
    busy, depth, last = 0, 0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded chip trace")
def test_recorded_chip_trace():
    """0.1 s of a traced txset-multisig run on the chip (my chip run,
    PR 22), trimmed by ``trace_reduce.trim``."""
    ev = tr.load(str(FIXTURE))
    out = tr.reduce(ev, KERNELS, ev.get("spans"))
    (_n, w0, wd), = [e for e in ev["host"] if e[0] == "bench.window"]
    dev = ev["devices"]["0"]
    clipped = [(max(s, w0), min(s + d, w0 + wd)) for _n, s, d in dev["ops"]
               if min(s + d, w0 + wd) > max(s, w0)]
    assert out["window_s"] == pytest.approx(wd / 1e9)
    assert out["busy_s"] == pytest.approx(_sweep_busy(clipped) / 1e9)
    kernel = [d for n, s, d in dev["modules"]
              if n.split("(")[0] in KERNELS and w0 <= s < w0 + wd]
    assert out["kernel_events"] == len(kernel) > 0
    assert out["kernel_s"] == pytest.approx(sum(kernel) / 1e9)
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert out["kernel_s"] <= out["window_s"]
    ops = out["breakdown"]["device_ops"]
    assert len(ops) == tr.TOP and ops == sorted(ops, key=lambda o: -o[1])
    _t, merged = tr.union_ns(clipped)
    edges = [w0] + [x for iv in merged for x in iv] + [w0 + wd]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    idle = tr.idle_by_context(gaps, ev["host"] + ev["spans"])
    assert sum(idle.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    assert EXPECTED == {k: round(out[k], 6) for k in EXPECTED}
    assert ops[0][0] == "jit_verify_kernel/%while.72"  # self time, cold
    labels = [n for n, _s in out["breakdown"]["idle_gaps"]]
    assert labels[:3] == ["span.verify.audit", "span.verify.prep",
                          "bench.generate"]
