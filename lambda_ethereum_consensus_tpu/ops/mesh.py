"""Shared device-mesh plumbing for the sharded crypto plane.

Three call sites grew private copies of the same two facts (the
process-wide ``dp`` mesh and whether sharding is the default here):
``ops/bls_shard.py``, the SHA-256 tree engine and the driver's
``__graft_entry__`` dryrun.  This module is the one copy.

Policy helpers (:func:`shard_enabled`, :func:`device_count`) ask the
backend directly, so one process gets one answer whatever ran before the
question.  Only a CPU-pinned process (``JAX_PLATFORMS`` set and not naming
``tpu`` — utils/env.tpu_backend) is answered from the environment,
without importing jax.
"""

from __future__ import annotations

import threading

import numpy as np

from ..utils.env import env_flag

__all__ = [
    "default_mesh",
    "device_count",
    "mesh_devices",
    "shard_enabled",
    "shard_plane_store_enabled",
    "state_shard_enabled",
]

_DEFAULT_MESH = None
_DEFAULT_MESH_LOCK = threading.Lock()


def default_mesh():
    """One process-wide ``("dp",)`` mesh over every local device — a fresh
    Mesh per call would defeat every id-keyed stage cache downstream
    (each drain would re-jit).  Double-checked: the warm-up thread and
    the first drain race to build it."""
    global _DEFAULT_MESH
    if _DEFAULT_MESH is not None:
        return _DEFAULT_MESH
    with _DEFAULT_MESH_LOCK:
        if _DEFAULT_MESH is None:
            import jax
            from jax.sharding import Mesh

            _DEFAULT_MESH = Mesh(np.array(jax.devices()), axis_names=("dp",))
            # one timeline instant on the flight recorder: the mesh
            # coming up is the moment the sharded plane's program
            # identities are fixed, so every later retrace/compile
            # instant reads against it
            from ..tracing import get_recorder

            get_recorder().record(
                "inst", 0, "mesh_init",
                {"devices": int(_DEFAULT_MESH.devices.size),
                 "backend": jax.default_backend()},
            )
    return _DEFAULT_MESH


def device_count() -> int:
    """Device count of this process's jax backend (initializes it)."""
    import jax

    return len(jax.devices())


def mesh_devices(mesh=None) -> int:
    """Device count of ``mesh`` (or the default mesh)."""
    if mesh is None:
        mesh = default_mesh()
    return int(mesh.devices.size)


def _multi_device_tpu(n_devices: int | None = None) -> bool:
    """True when this process's backend is a multi-device TPU — the only
    configuration where sharding defaults on.  A virtual
    ``--xla_force_host_platform_device_count`` CPU mesh (every test
    process under conftest) must NOT flip production routing by itself;
    CPU meshes opt in explicitly."""
    from ..utils.env import tpu_backend

    if not tpu_backend():
        return False
    if n_devices is None:
        n_devices = device_count()
    return n_devices > 1


def shard_enabled(n_devices: int | None = None) -> bool:
    """Should the crypto plane route through the mesh-sharded pipeline?

    - ``BLS_NO_SHARD=1`` always wins (single-device fallback, identical
      results);
    - ``BLS_SHARD=1`` force-enables (CI's virtual 8-CPU mesh);
    - default: sharded exactly when the backend is a multi-device TPU.
      ``n_devices`` lets callers pass a count they already hold (a live
      mesh) instead of re-asking the backend.
    """
    if env_flag("BLS_NO_SHARD"):
        return False
    if env_flag("BLS_SHARD"):
        return True
    return _multi_device_tpu(n_devices)


def shard_plane_store_enabled() -> bool:
    """Should registry pubkey planes be PLACED sharded across the mesh?

    Opt-in only (``BLS_SHARD_PLANES=1``).  Not a default on any backend:
    the planes' consumers (``committee_sums``, ``agg_corrected``) are
    single-device programs built on Pallas kernels, and the TPU compiler
    refuses a mesh-sharded operand to them ("Mosaic kernels cannot be
    automatically partitioned") — found by compiling for a described
    v5e:2x2 and on four chips (PR 21).  Until a ``shard_map`` consumer
    exists the flag is for the CPU mesh's interpret-mode tests."""
    if env_flag("BLS_NO_SHARD"):
        return False
    return env_flag("BLS_SHARD_PLANES")


def state_shard_enabled() -> bool:
    """Should the per-validator STATE planes (resident epoch columns,
    SSZ chunk rows — round 21) be placed sharded across the mesh?

    Same polarity ladder as ``BLS_SHARD``: ``GRAFT_STATE_NO_SHARD=1``
    always wins (single-device residency, identical results),
    ``GRAFT_STATE_SHARD=1`` force-enables (CI's virtual 8-CPU mesh),
    default on exactly for a multi-device TPU backend."""
    if env_flag("GRAFT_STATE_NO_SHARD"):
        return False
    if env_flag("GRAFT_STATE_SHARD"):
        return True
    return _multi_device_tpu()
