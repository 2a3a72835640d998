"""The simulator's one device program, compiled for a described TPU v5e.

``unet._apply_jit`` (the U-Net forward every a100/h100 profiling window
calls) is compiled for one chip of a ``v5e:2x2`` topology that is
described, not attached, at the batch buckets the main path uses: 1 (a
single window), 8 (the largest bucket ``warm_jit_cache`` warms) and 32
(the largest that the 512-GPU replay of ``chip_smoke.py`` reaches, at
B=8).  A compile the chip's compiler refuses fails here at no chip time.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import this
file.  These compiles stay in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.predictor import unet

BUCKETS = (1, 8, 32)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("bucket", BUCKETS)
def test_unet_forward_compiles_for_v5e(one_chip, no_persistent_cache,
                                       bucket):
    shapes = jax.eval_shape(lambda k: unet.init(k)[0],
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    m = jax.ShapeDtypeStruct((bucket, 3, 7), jnp.float32, sharding=one_chip)
    lowered = unet._apply_jit.lower(params, m, levels=3, jobs=7)
    # every convolution keeps float32 accuracy on the chip (the pin in
    # unet.PRECISION): 9 convolutions, two operands each
    assert lowered.as_text().count("precision HIGHEST") == 18
    compiled = lowered.compile()
    assert compiled.output_shardings.device_set == {one_chip._device}
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= bucket * 3 * 7 * 4
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 * 2**30      # one v5e: 16 GB HBM
