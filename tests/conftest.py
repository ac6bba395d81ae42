"""Test harness: force an 8-device virtual CPU mesh.

Multi-chip hardware is unavailable in CI; the sharding layer is validated
on virtual CPU devices (the driver separately dry-runs multi-chip via
__graft_entry__.dryrun_multichip).  The platform and the device count
are fixed here, before any test initialises a backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

from antidote_tpu.config import (  # noqa: E402
    XLA_CACHE_DIR,
    enable_compilation_cache,
)

# own cache directory, next to the servers': the 8-virtual-device test
# config compiles with different machine-feature flags than 1-device
# server processes, and cross-loading the other config's AOT entries
# spams feature-mismatch warnings on every load
enable_compilation_cache(XLA_CACHE_DIR + "_t8")

import pytest  # noqa: E402


@pytest.fixture
def cfg():
    from antidote_tpu.config import AntidoteConfig

    return AntidoteConfig(
        n_shards=4, max_dcs=3, ops_per_key=8, snap_versions=2,
        set_slots=8, mv_slots=4, rga_slots=16, keys_per_table=64,
        batch_buckets=(16, 64),
    )
