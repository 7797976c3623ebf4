"""The verify kernels name their stages: ``jax.named_scope`` puts
``decompress`` (cold kernel only), ``dsm`` and ``compress_compare`` into
the op metadata, where a profiler trace can group device ops by stage.
Lowered at bucket 128 (no compile)."""

import re

import numpy as np
import pytest

BUCKET = 128


def _cold():
    return [((BUCKET, 32), np.uint8)] * 4


def _hot():
    from stellar_tpu.ops import edwards as ed
    from stellar_tpu.ops import field25519 as fe
    table = (BUCKET, ed.TABLE_ENTRIES256, ed.AFFINE_COORDS, fe.NLIMBS)
    return [(table, np.int16)] + [((BUCKET, 32), np.uint8)] * 3


@pytest.mark.parametrize("kernel,shapes,stages", [
    ("verify_kernel", _cold, ("decompress", "dsm", "compress_compare")),
    ("verify_kernel_hot", _hot, ("dsm", "compress_compare")),
])
def test_kernel_stages_are_named_in_the_op_metadata(kernel, shapes, stages):
    import jax
    from stellar_tpu.ops import verify
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes()]
    text = jax.jit(getattr(verify, kernel)).lower(*args).as_text(
        debug_info=True)
    named = set(re.findall(rf'loc\("jit\({kernel}\)/(\w+)/', text))
    assert set(stages) <= named, named
    if "decompress" not in stages:
        assert "decompress" not in named
