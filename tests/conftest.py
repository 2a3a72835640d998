import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# entry points under test turn the persistent compile cache on; tests run
# without it (the described-TPU compiles could not read their entries back)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax
import pytest

from repro.configs.base import RunConfig


@pytest.fixture(scope="session")
def run32():
    """Small-shape fp32 run config for CPU tests."""
    return RunConfig(param_dtype="float32", activation_dtype="float32",
                     attn_block_q=8, attn_block_kv=8, loss_chunk=16)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
