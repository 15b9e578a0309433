"""Environment-flag parsing shared by the device-routing switches
(BLS_DEVICE_MSM, BLS_DEVICE_PAIRING, BIGINT_NO_PALLAS, ...)."""

from __future__ import annotations

import os
import threading

__all__ = [
    "compile_cache_dirs",
    "device_default",
    "enable_compile_cache",
    "env_flag",
    "tpu_backend",
]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def env_flag(name: str) -> bool:
    """One truthiness parse for every routing flag, so spellings like
    ``off``/``False`` never enable a path by accident."""
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "no", "off",
    )


_TPU_BACKEND: bool | None = None
_TPU_BACKEND_LOCK = threading.Lock()


def tpu_backend() -> bool:
    """Is this process's jax backend a TPU?  Asked of the backend itself,
    once (memoized), so every routing decision in the process gets the
    same answer whatever ran before it.  CPU-pinned processes
    (``JAX_PLATFORMS`` set and not naming ``tpu``) short-circuit without
    ever importing jax — a pure-host node must not pay XLA backend init
    inside its verification path."""
    global _TPU_BACKEND
    if _TPU_BACKEND is None:
        # double-checked: the warm-up thread, executor duty/API threads,
        # and the event loop can all ask first — only one may pay (and
        # observe a half-initialized) jax backend probe
        platforms = os.environ.get("JAX_PLATFORMS", "").strip().lower()
        with _TPU_BACKEND_LOCK:
            if _TPU_BACKEND is None:
                if platforms and "tpu" not in platforms:
                    _TPU_BACKEND = False
                else:
                    import jax

                    _TPU_BACKEND = jax.default_backend() == "tpu"
    return _TPU_BACKEND


def device_default() -> bool:
    """Device crypto routing polarity: ON by default on a TPU host, off
    elsewhere; ``BLS_NO_DEVICE=1`` opts out, per-path flags
    (``BLS_DEVICE_MSM=1`` etc.) still force-enable on any backend.

    A node started on TPU hardware dispatches its hot paths to the chip
    with no configuration — the TPU is the engine, not a sidecar."""
    return not env_flag("BLS_NO_DEVICE") and tpu_backend()


def compile_cache_dirs() -> tuple[str, str]:
    """``(JAX persistent cache dir, AOT executable dir)`` — the ONE place
    both compile caches are located.  ``JAX_COMPILATION_CACHE_DIR`` given
    from outside roots both (the AOT tier in its ``aot/`` subdirectory);
    unset, they sit at fixed paths in the checkout, never a temporary
    name: the path is part of JAX's cache key, so a directory that moves
    never hits."""
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if root:
        return root, os.path.join(root, "aot")
    return (
        os.path.join(_CHECKOUT, ".jax_cache"),
        os.path.join(_CHECKOUT, ".aot_cache"),
    )


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return its directory.  With ``JAX_COMPILATION_CACHE_DIR`` set JAX has
    already read it and nothing here sets another; unset, the checkout
    default is configured (before the first compile — JAX latches the
    cache at first use)."""
    jax_dir = compile_cache_dirs()[0]
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", jax_dir)
    return jax_dir
