"""The Pallas flash kernel compiled for a TPU v5e that is described, not
attached: Mosaic refuses here what the chip's compiler would refuse.

Every TPU-topology call sits in a fixture of this one file: a call made
while a module is imported would load libtpu in every test worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    from jax.experimental import topologies

    # the TPU compiler logs under /tmp unless told otherwise
    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("seq", [1024, 8192])
def test_flash_kernel_compiles_for_v5e(one_chip, seq, head_dim):
    from traceml_tpu.ops.pallas_attention import BLOCK, _flash_bhsd

    x = jax.ShapeDtypeStruct((8, seq, head_dim), jnp.bfloat16, sharding=one_chip)
    compiled = _flash_bhsd.lower(
        x, x, x, blk_q=BLOCK, blk_k=BLOCK, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
