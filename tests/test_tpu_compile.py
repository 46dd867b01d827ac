"""Compile-only guards: the fused CDC kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: tiles not aligned to 128 lanes, shape casts Mosaic
cannot lay out, more fast memory than a kernel may use. Each test here
compiles one kernel of the serving round at h2o-danube-1.8b widths
(k=2560, T=4, r=2; attention m_l=640 and 160, FFN m_l=1728, head
m_l=8000) for one chip of a described — not attached — ``v5e:2x2``, and
checks that the program holds the Mosaic kernel and fits its VMEM.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cdc_decode import cdc_fused_head_argmax_pallas
from repro.kernels.cdc_matmul import (cdc_coded_matmul_pallas,
                                      cdc_decode_merge_pallas)

T, R, K, SLOTS = 4, 2, 2560, 4
DTYPES = (jnp.bfloat16, jnp.float32)
DTYPE_IDS = ("bf16", "f32")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text(), \
        "the kernel must compile for Mosaic, not inline"
    return compiled


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("m_l", (640, 160, 1728))
@pytest.mark.parametrize("fold_norm", (False, True), ids=("plain", "norm"))
def test_coded_matmul_compiles(one_chip, m_l, dtype, fold_norm):
    # parity weights are f32 whatever the model dtype (CodeSpec.parity_dtype)
    shapes = [((SLOTS, K), dtype), ((T, K, m_l), dtype),
              ((R, K, m_l), jnp.float32),
              ((R, T), jnp.float32), ((m_l,), jnp.int32),
              ((m_l,), jnp.float32), ((T,), jnp.bool_)]
    if fold_norm:
        shapes.append(((K,), dtype))

    def fn(x, w, pw, gen, esel, coef, valid, *gamma):
        return cdc_coded_matmul_pallas(x, w, pw, gen, esel, coef, valid,
                                       gamma=gamma[0] if gamma else None)

    compiled = _compile(fn, one_chip, *shapes)
    # no HBM copy of the weights: the kernel reads them in place
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("m_l", (640, 1728))
def test_decode_merge_compiles(one_chip, m_l, dtype):
    _compile(cdc_decode_merge_pallas, one_chip,
             ((T, SLOTS, m_l), dtype), ((R, SLOTS, m_l), dtype),
             ((R, T), jnp.float32), ((m_l,), jnp.int32),
             ((m_l,), jnp.float32), ((T,), jnp.bool_))


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("cols", (8000, 8064), ids=("m_l", "lane_padded"))
def test_fused_head_compiles(one_chip, cols, dtype):
    def fn(x, w, pw, valid):
        return cdc_fused_head_argmax_pallas(x, w, pw, valid, vocab=32000,
                                            shard_width=8000)

    compiled = _compile(fn, one_chip, ((SLOTS, K), jnp.float32),
                        ((T, K, cols), dtype), ((K, cols), jnp.float32),
                        ((T,), jnp.bool_))
    if cols % 128 == 0:
        # lane-aligned shards (what the executor passes) are read in place
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
