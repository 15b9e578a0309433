"""Test configuration.

Multi-chip sharding tests run on a virtual 8-device CPU mesh — real TPU
hardware is single-chip in CI, so `--xla_force_host_platform_device_count=8`
provides the device mesh (the driver's `dryrun_multichip` does the same).
Setting JAX_PLATFORMS / XLA_FLAGS must happen before jax initializes.
"""

import os
import sys

# Keep subprocesses spawned by tests on the CPU backend too.  Single source
# of truth for the virtual-mesh env lives next to the driver entry points.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from __graft_entry__ import virtual_cpu_env  # noqa: E402

# Also points JAX's persistent compile cache at the checkout's .jax_cache
# unless JAX_COMPILATION_CACHE_DIR is given from outside (env-var config,
# so tests that never touch jax don't pay its import here).
virtual_cpu_env(8, os.environ)

import pytest  # noqa: E402

from lambda_ethereum_consensus_tpu.config import (  # noqa: E402
    mainnet_spec,
    minimal_spec,
    use_chain_spec,
)


@pytest.fixture
def mainnet():
    with use_chain_spec(mainnet_spec()) as spec:
        yield spec


@pytest.fixture
def minimal():
    with use_chain_spec(minimal_spec()) as spec:
        yield spec
