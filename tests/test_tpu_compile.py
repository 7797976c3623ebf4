"""The verify kernels compile for a TPU v5e that is described, not
attached: what the chip's compiler would refuse (an unsupported op, a
program that does not fit HBM) fails here at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker
imports this file. Keep these compiles in this one file (~35 s each).
"""

import os

import pytest

HBM_BYTES = 16 << 30   # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(kernel, shapes, sharding):
    import jax
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    compiled = jax.jit(kernel).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < total <= HBM_BYTES, mem
    return mem


def _cold_shapes(n):
    import numpy as np
    return [((n, 32), np.uint8)] * 4


def _hot_shapes(n):
    import numpy as np

    from stellar_tpu.ops import edwards as ed
    from stellar_tpu.ops import field25519 as fe
    table = (n, ed.TABLE_ENTRIES256, ed.AFFINE_COORDS, fe.NLIMBS)
    return [(table, np.int16)] + [((n, 32), np.uint8)] * 3


@pytest.mark.parametrize("rows", [128, 2048])
def test_cold_verify_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                             rows):
    from stellar_tpu.ops.verify import verify_kernel
    _compile_for_chip(verify_kernel, _cold_shapes(rows), one_chip)


def test_hot_verify_kernel_compiles_for_v5e(one_chip, no_persistent_cache):
    from stellar_tpu.ops.verify import verify_kernel_hot
    _compile_for_chip(verify_kernel_hot, _hot_shapes(128), one_chip)
