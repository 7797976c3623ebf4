"""Operand placement in the batch engine: every dispatched operand is
one ``device_put`` to its destination, recorded once by the transfer
ledger and once by the engine's own byte tally, and no device buffer
outlives the call that used it. One plain jit wrapper serves each
(variant, dispatch shape).

The workload is a trivial first-column kernel over one ``(n, W)``
uint8 operand, so nothing here compiles a real verify kernel. The mesh
cases use the 8 CPU devices ``conftest`` forces.
"""

import gc

import numpy as np
import pytest

from stellar_tpu.crypto import batch_verifier as bv
from stellar_tpu.parallel import batch_engine
from stellar_tpu.utils import faults, tracing
from stellar_tpu.utils.transfer_ledger import transfer_ledger

# an operand width no other test uses, so a live-array scan for the
# padded operand shape sees only this file's buffers
WIDTH = 5
N_DEV = 8

# placement path -> (bucket sizes, items per call); the engine picks
# the path from the mesh and the chunk:
# * default: no mesh, one whole-bucket upload to the default device;
# * mesh_coalesced: a full bucket on a healthy mesh, one sharded upload;
# * mesh_per_device: a short chunk (3 of 8 sub-chunks carry rows), one
#   upload per serving device.
PATHS = {
    "default": ((4, 8), 4),
    "mesh_coalesced": ((8, 16), 8),
    "mesh_per_device": ((16,), 5),
}


class _PlaceWorkload(batch_engine.Workload):
    metrics_ns = "test.place"
    span_ns = "place_t"

    def encode(self, items):
        arr = np.array([[v + k for k in range(WIDTH)] for v in items],
                       dtype=np.uint8)
        return np.ones(len(items), dtype=bool), (arr,)

    def pad_rows(self):
        return (np.zeros((1, WIDTH), dtype=np.uint8),)

    def kernel_fn(self):
        def k(a):
            return a[:, 0]
        return k

    def empty_result(self, n):
        return np.zeros(n, dtype=np.uint8)

    def host_result(self, items):
        return np.array(list(items), dtype=np.uint8)

    def finalize(self, gate, out, items):
        return out


class _VariantWorkload(_PlaceWorkload):
    variant_name = "place_variant"


@pytest.fixture(autouse=True)
def clean_state():
    bv._reset_dispatch_state_for_testing()
    bv.configure_dispatch(dispatch_retries=1)
    tracing.flight_recorder.clear()
    yield
    faults.clear()
    tracing.flight_recorder.clear()
    bv._reset_dispatch_state_for_testing()


def _mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:N_DEV]), ("batch",))


def _engine(path):
    buckets, _ = PATHS[path]
    mesh = None if path == "default" else _mesh()
    return batch_engine.BatchEngine(_PlaceWorkload(), mesh=mesh,
                                    bucket_sizes=buckets)


def _items(n, base):
    return [base + 7 * i for i in range(n)]


def _place_span_bytes():
    recs = tracing.flight_recorder.snapshot(limit=4096)["recent"]
    return sum(r["attrs"]["h2d_bytes"] for r in recs
               if r["name"] == "span.place_t.place")


@pytest.mark.parametrize("path", sorted(PATHS))
def test_each_dispatch_uploads_every_operand_once(path):
    eng = _engine(path)
    _, n = PATHS[path]
    items = _items(n, 3)
    led0, shipped0 = transfer_ledger.totals(), eng.shipped_bytes
    assert list(eng.compute_batch(items)) == items
    led1, shipped1 = transfer_ledger.totals(), eng.shipped_bytes
    first = led1["bytes_h2d"] - led0["bytes_h2d"]
    # one call: the ledger, the engine tally and the place spans agree
    assert first > 0
    assert first == shipped1 - shipped0 == _place_span_bytes()
    assert led1["redundant_constant_bytes"] == \
        led0["redundant_constant_bytes"]
    assert eng.coalesced_dispatches == int(path == "mesh_coalesced")
    # identical content again: uploaded again, convicted as redundant
    assert list(eng.compute_batch(items)) == items
    led2 = transfer_ledger.totals()
    assert led2["bytes_h2d"] - led1["bytes_h2d"] == first
    assert eng.shipped_bytes - shipped1 == first
    assert led2["redundant_constant_bytes"] - \
        led1["redundant_constant_bytes"] == first
    assert led2["device_puts"] - led1["device_puts"] == \
        led1["device_puts"] - led0["device_puts"]
    assert eng.served["host-fallback"] == 0


@pytest.mark.parametrize("path", sorted(PATHS))
def test_no_device_buffer_outlives_its_call(path):
    import jax
    eng = _engine(path)
    _, n = PATHS[path]
    bucket = eng._bucket(n)
    shapes = {(bucket, WIDTH)}
    if path != "default":
        shapes.add((bucket // N_DEV, WIDTH))
    for base in range(4):
        items = _items(n, 10 * base)
        assert list(eng.compute_batch(items)) == items
    gc.collect()
    live = [a.shape for a in jax.live_arrays()
            if a.dtype == np.uint8 and a.shape in shapes]
    assert live == []


@pytest.mark.parametrize("mesh", [False, True], ids=["default", "mesh"])
def test_one_executable_per_shape(mesh, monkeypatch):
    import jax
    builds = []
    real_jit = jax.jit

    def recording_jit(fn, *args, **kwargs):
        builds.append(kwargs)
        return real_jit(fn, *args, **kwargs)

    monkeypatch.setattr(jax, "jit", recording_jit)
    eng = batch_engine.BatchEngine(
        _PlaceWorkload(), mesh=_mesh() if mesh else None,
        bucket_sizes=(8, 16))
    for n in (8, 16, 8, 16):
        items = _items(n, n)
        assert list(eng.compute_batch(items)) == items
    shapes = [1, 2] if mesh else [8, 16]
    assert sorted(eng._kernels) == shapes
    variant = _VariantWorkload()
    for shape in shapes:
        assert eng._kernel_for(shape) is eng._kernels[shape]
        assert eng._kernel_for(shape, plugin=variant) is \
            eng._kernel_for(shape, plugin=variant)
    assert sorted(eng._kernels_variants) == ["place_variant"]
    assert sorted(eng._kernels_variants["place_variant"]) == shapes
    # one wrapper per (variant, shape), none of them donating
    assert len(builds) == 2 * len(shapes)
    assert all("donate_argnums" not in kw for kw in builds)


@pytest.mark.parametrize("mesh", [False, True], ids=["default", "mesh"])
def test_transient_dispatch_fault_is_retried(mesh):
    eng = batch_engine.BatchEngine(
        _PlaceWorkload(), mesh=_mesh() if mesh else None,
        bucket_sizes=(8,))
    items = _items(8, 40)
    faults.set_fault(faults.DISPATCH, "failn", 1)
    assert list(eng.compute_batch(items)) == items
    assert faults.counters()[faults.DISPATCH]["fired"] == 1
    assert eng.retries == 1
    assert eng.served == {"device": 8, "host-fallback": 0}


@pytest.mark.parametrize("name,value", [
    ("VERIFY_RESIDENT_CACHE_BYTES", "134217728"),
    ("VERIFY_RESIDENT_MAX_ITEM_BYTES", "1048576"),
    ("VERIFY_RESIDENT_CONSTANTS", "true"),
    ("VERIFY_DONATE_BUFFERS", '"auto"'),
])
def test_removed_placement_knob_is_rejected(name, value, tmp_path):
    from stellar_tpu.main.config import Config
    path = tmp_path / "node.cfg"
    path.write_text(f"{name} = {value}\n")
    with pytest.raises(ValueError, match=f"unknown config key {name}"):
        Config.from_toml(str(path))
    with pytest.raises(TypeError):
        Config(**{name: 1})
