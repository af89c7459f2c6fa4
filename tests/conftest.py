"""Test config: run JAX on 8 virtual CPU devices so the full multi-chip
sharding story is exercised without a TPU pod (SURVEY.md §4 implication).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

from distributed_embeddings_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache)

# persistent compilation cache: CPU test compiles of grad-of-shard_map are
# slow; cache them across pytest runs (a cold suite takes ~20 min)
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-minute tests (multi-process spawns)")

assert len(jax.devices()) >= 8, (
    f"tests need 8 virtual CPU devices, got {jax.devices()}")
