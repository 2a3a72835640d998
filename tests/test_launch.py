"""Entry-point plumbing of ``repro.launch``: the persistent compile cache
and ``cluster --show-meshes``."""
import os

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the cache settings that the entry points change."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_location(monkeypatch, tmp_path, cache_config,
                                env_dir):
    """Without JAX_COMPILATION_CACHE_DIR the cache goes to the checkout's
    fixed ``.jax_cache/``; with it, the helper leaves the location to JAX."""
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    before = jax.config.jax_compilation_cache_dir
    compile_cache.enable()
    if env_dir is None:
        assert compile_cache.CACHE_DIR == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
    else:
        assert jax.config.jax_compilation_cache_dir == before
    # every U-Net compile is sub-second: persist them all
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_show_meshes_needs_no_devices(capsys, cache_config):
    """Each slice's mesh shape and axes are printed from the partition
    space alone, on a process that holds a single device."""
    from repro.launch import cluster

    assert jax.device_count() == 1
    cluster.main(["--space", "tpu", "--show-meshes", "--jobs", "10"])
    out = capsys.readouterr().out
    for name, rows in (("1u.512gb", 2), ("2u.1024gb", 4), ("3u.1536gb", 6),
                       ("4u.2048gb", 8), ("8u.4096gb", 16)):
        assert (f"  {name}: mesh ({rows}, 16) axes ('data', 'model') = "
                f"{rows * 16} devices") in out
