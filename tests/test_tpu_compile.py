"""The rate-resolution kernels compile for a TPU v5e chip.

Nothing runs: the TPU compiler compiles each kernel for one chip of a
described ``v5e:2x2`` topology, so a kernel the chip's compiler would refuse
fails here, without the chip.  The shapes are the campaign's: the smallest
Pallas tile, the largest one a 10,000-job CLUSTER2048 lane group asks for
(``chip_smoke.py``), a 2^16-value segment-max, and a 512-link × 4096-flow
water-filling solve.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fairshare import _maxmin_kernel, _segment_max_kernel
from repro.kernels.phase_max import _row_max_call


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("nseg_pad,k_pad", [(128, 128), (512, 2048)])
def test_row_max_kernel_compiles_for_v5e(one_chip, nseg_pad, k_pad):
    compiled = _row_max_call(nseg_pad, k_pad, False).lower(
        _arg((nseg_pad, k_pad), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_max_kernel_compiles_for_v5e(one_chip):
    n = 1 << 16
    compiled = _segment_max_kernel.lower(
        _arg((n,), jnp.int32, one_chip), _arg((n,), jnp.int32, one_chip),
        num_segments=1024).compile()
    assert compiled.as_text()


def test_maxmin_kernel_compiles_for_v5e(one_chip):
    links, flows = 512, 4096
    compiled = _maxmin_kernel.lower(
        _arg((links, flows), jnp.float32, one_chip),
        _arg((links,), jnp.float32, one_chip),
        _arg((), jnp.float32, one_chip)).compile()
    assert compiled.as_text()
