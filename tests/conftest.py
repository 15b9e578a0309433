"""Test configuration.

Multi-chip sharding tests run on a virtual 8-device CPU mesh — real TPU
hardware is single-chip in CI, so `--xla_force_host_platform_device_count=8`
provides the device mesh (the driver's `dryrun_multichip` does the same).
Setting JAX_PLATFORMS / XLA_FLAGS must happen before jax initializes.
"""

import os
import shutil
import subprocess
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

NATIVE = os.path.join(os.path.dirname(__file__), "..", "native")
_native_note = "present"


def pytest_configure(config):
    """The suite builds what it tests: ``native/build/*.so`` is git-ignored,
    so on a fresh checkout ``make -C native`` (~13 s) runs once, here — in
    the xdist controller, before it starts a worker — and the native BLS /
    KV tests count on a fresh checkout as on a used one.  The libraries
    load when their Python bindings are first imported, which is after
    this hook in every process."""
    global _native_note
    if hasattr(config, "workerinput"):
        return  # a worker: its controller has built already
    built = os.path.join(NATIVE, "build")
    if all(os.path.exists(os.path.join(built, lib))
           for lib in ("libbls381.so", "libkvstore.so")):
        return
    if not (shutil.which("make") and shutil.which(os.environ.get("CXX", "g++"))):
        _native_note = "NOT built (no make or C++ compiler here): the native tests skip"
        return
    made = subprocess.run(["make", "-C", NATIVE], capture_output=True, text=True)
    _native_note = "built by this run" if made.returncode == 0 else (
        f"make -C native FAILED ({made.returncode}), the native tests skip: "
        + made.stderr.strip()[-300:])


def pytest_report_header(config):
    return f"native/build: {_native_note}"

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
