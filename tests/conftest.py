"""Test configuration.

Tests run on the CPU, on a virtual 8-device mesh, so multi-chip sharding
(shard_map all-to-all repartition, sharded state stores) is exercised
without TPU hardware.  Both are set before jax is imported.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

# Parity with SQL DOUBLE/BIGINT semantics in tests.
jax.config.update("jax_enable_x64", True)

# The suite compiles hundreds of store-shaped jits; the persistent cache,
# placed as every entry point places it, cuts their cost across workers
# and runs.  Most take well under JAX's default one-second floor.
from ksql_tpu.runtime import compile_cache  # noqa: E402

compile_cache.place()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
