"""Test configuration: force an 8-device virtual CPU mesh.

All sharding tests run against ``jax.sharding.Mesh`` over 8 virtual CPU
devices so multi-chip paths are exercised without TPU hardware (the
chip itself is reached only through ``chip_smoke.py``).  The settings
go through ``jax.config.update`` so they hold even where jax was
imported before this file.
"""
import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


@pytest.fixture
def jax_cache_config():
    """Put JAX's persistent-cache settings (and the package's memo of
    the wired directory) back after a test that turns the cache on."""
    from jax.experimental.compilation_cache import compilation_cache

    import isotope_tpu.compiler.cache as cache_mod

    knobs = (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {k: getattr(jax.config, k) for k in knobs}
    memo = (cache_mod._persistent_dir, cache_mod._switched_off)
    cache_mod._persistent_dir, cache_mod._switched_off = None, False
    yield cache_mod
    cache_mod._persistent_dir, cache_mod._switched_off = memo
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
