"""AOT executable cache: serialize compiled XLA programs across processes.

The big staged programs of this package (the RLC ladders, the Miller
loop) spend most of a cold start in host-side tracing and lowering of
their unrolled Pallas kernels, not in the XLA compiler — and JAX's
persistent compilation cache is keyed on the lowered module, so it can
only skip the compile.  This module keys on what is known BEFORE tracing:
each jitted function is lowered+compiled once per argument-shape
signature, the compiled PjRt executable is pickled via
``jax.experimental.serialize_executable``, and any later process
deserializes it instead of tracing, lowering and compiling again.

Keys are OURS (stable): function name + flattened arg shapes/dtypes +
backend + device kind + jax version + a source-content hash of this
``ops`` package.  The key deliberately does NOT hash the lowered HLO —
computing it requires the tracing a disk hit exists to skip.  The source
hash keeps a code change from serving stale executables (coarser than
per-function identity, so any-file edit in ops/ invalidates the whole
cache — the safe direction).

A corrupt or unreadable cache file is counted and falls through to a
fresh compile, so this layer can never make a result wrong — only a cold
start slower.  A program that does not lower or compile is an error and
raises.

Placement: with ``JAX_COMPILATION_CACHE_DIR`` set, this tier lives in its
``aot/`` subdirectory, beside JAX's own persistent cache; unset, in
``.aot_cache`` at the checkout root (utils/env.compile_cache_dirs).

Role in the reference mapping: the reference's NIF .so files are its
"compile once, load forever" boundary (ref: native/bls_nif/src/lib.rs:147-158);
this cache is the TPU build's equivalent for XLA programs.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import pickle
import sys
import threading
import time

from ..telemetry import inc, observe
from ..utils.env import compile_cache_dirs

__all__ = [
    "aot_jit",
    "aot_dir",
    "aot_stats",
    "compile_context",
    "compile_profile",
    "register_shape_bucket",
    "shape_buckets",
]

_LOCK = threading.Lock()
# "retraces": how often a batch-verify entry point had to LOWER (trace) a
# program for a new argument-shape signature — the per-tick jit-retrace
# gauge; disk loads deliberately skip tracing and don't count.
# Kept as a plain dict for aot_stats() consumers (bench_chain's summary);
# the process-wide telemetry counters (aot_retraces_total & co, emitted at
# the increment sites below) are the durable copies — they live on the
# default registry, so retrace/compile counts survive and scrape without
# a running node tick loop.
_STATS = {"loads": 0, "compiles": 0, "saves": 0, "errors": 0, "retraces": 0}

# The compile/retrace attribution table, keyed (entry point, argument
# signature): one row per program the cache has ever resolved, carrying
# who caused it (call site), under which context (live drain vs warmup),
# what it cost (lower/compile/load seconds) and how the cache behaved
# (hit/miss/load/compile counts, last use).  Served at /debug/compile.
_PROFILE: dict[tuple[str, str], dict] = {}

# Compile-context label (thread-local: the warmer runs on its own daemon
# thread while live traffic may compile concurrently on another).
_CTX = threading.local()


@contextlib.contextmanager
def compile_context(label: str):
    """Tag compiles/retraces performed inside the block with ``label``
    (e.g. ``"warmup:drain"``) so the attribution table can tell a
    planned warmup compile from a mid-drain retrace — the latter is the
    dead-air failure mode the shape-bucket discipline exists to
    prevent."""
    prev = getattr(_CTX, "label", None)
    _CTX.label = label
    try:
        yield
    finally:
        _CTX.label = prev


def _ctx_label() -> str:
    return getattr(_CTX, "label", None) or "live"


def _caller_site(depth: int = 2) -> str:
    """``pkg-relative/file.py:line`` of the nearest frame outside this
    module — the call site charged with a retrace/compile.  Only runs on
    the cache-miss path (misses cost seconds; a stack probe costs ns)."""
    try:
        f = sys._getframe(depth)
    except ValueError:
        return "?"
    here = _caller_site.__code__.co_filename
    while f is not None and f.f_code.co_filename == here:
        f = f.f_back
    if f is None:
        return "?"
    fname = f.f_code.co_filename.replace(os.sep, "/")
    marker = "lambda_ethereum_consensus_tpu/"
    idx = fname.rfind(marker)
    tail = fname[idx:] if idx >= 0 else "/".join(fname.rsplit("/", 2)[-2:])
    return f"{tail}:{f.f_lineno}"


def _profile_entry(name: str, sig: str, caller: str) -> dict:
    with _LOCK:
        entry = _PROFILE.get((name, sig))
        if entry is None:
            entry = _PROFILE[(name, sig)] = {
                "entry": name,
                "signature": sig,
                "caller": caller,
                "context": _ctx_label(),
                "source": None,  # disk | compile
                "hits": 0,
                "misses": 0,
                "loads": 0,
                "compiles": 0,
                "saves": 0,
                "errors": 0,
                "lower_seconds": 0.0,
                "compile_seconds": 0.0,
                "load_seconds": 0.0,
                "created": time.time(),
                "last_use": 0.0,
            }
        return entry


def compile_profile() -> list[dict]:
    """Snapshot of the attribution table, most-recently-used first (the
    ``/debug/compile`` payload).  Rows are copies — callers may mutate."""
    with _LOCK:
        entries = [dict(e) for e in _PROFILE.values()]
    entries.sort(key=lambda e: (e["last_use"], e["created"]), reverse=True)
    return entries


def _note_retrace(name: str, sig: str, caller: str, lower_s: float) -> None:
    """One program TRACE (lower) for a new shape signature: the event the
    shape-bucket discipline tries to keep off the live drain path.  Emits
    the process-wide counter plus a flight-recorder instant so retraces
    land on the /debug/trace Perfetto timeline next to the batches they
    stalled."""
    inc("aot_retraces_total")
    from ..tracing import get_recorder

    get_recorder().record(
        "inst", 0, "retrace",
        {
            "entry": name,
            "caller": caller,
            "context": _ctx_label(),
            "lower_s": round(lower_s, 3),
            "signature": sig,
        },
    )


def _note_compile(name: str, compile_s: float) -> None:
    inc("aot_compiles_total")
    observe("aot_compile_seconds", compile_s, entry=name)
    from ..tracing import get_recorder

    get_recorder().record(
        "inst", 0, "xla_compile",
        {"entry": name, "context": _ctx_label(),
         "compile_s": round(compile_s, 3)},
    )


def _note_load(name: str, load_s: float) -> None:
    inc("aot_loads_total")
    observe("aot_load_seconds", load_s, entry=name)


def _note_cost(name: str, sig: str, executable) -> None:
    """Feed one resolved executable's compile-time HLO cost/memory
    analysis to the round-18 observatory (ops/profile.py).  Compiled and
    deserialized executables both answer the analyses; anything that
    doesn't (test fakes) is silently skipped — cost attribution must
    never break a dispatch."""
    try:
        from .profile import record_entry_cost

        record_entry_cost(name, sig, executable)
    except Exception:
        pass


def _note_save() -> None:
    inc("aot_saves_total")


def _note_error(stage: str) -> None:
    inc("aot_errors_total", stage=stage)

# Warmed batch-shape buckets, by kind (e.g. "attestation_entries"):
# node/warmup.py advertises the shapes its dummy drain loads, and the
# ingest scheduler (pipeline/policy.snap_batch) snaps flush sizes onto
# them — an off-bucket flush would trace+compile a fresh program
# mid-drain.
_SHAPE_BUCKETS: dict[str, set[int]] = {}


def register_shape_bucket(kind: str, size: int) -> None:
    """Advertise that a device program for batches of ``size`` items of
    ``kind`` is warmed (or about to be — the warmer registers before its
    background dispatch so the scheduler shapes batches for the programs
    that will be resident by the time real traffic arrives)."""
    size = int(size)
    if size <= 0:
        raise ValueError(f"shape bucket must be positive, got {size}")
    with _LOCK:
        _SHAPE_BUCKETS.setdefault(kind, set()).add(size)


def shape_buckets(kind: str) -> tuple[int, ...]:
    """Ascending warmed bucket sizes for ``kind`` (empty when nothing
    was warmed — the scheduler then flushes unsnapped)."""
    with _LOCK:
        return tuple(sorted(_SHAPE_BUCKETS.get(kind, ())))


def all_shape_buckets() -> dict[str, tuple[int, ...]]:
    """Every registered bucket family, ascending per kind — the
    /debug/compile inventory (hard-coding families there meant each new
    plane silently vanished from the warmup report)."""
    with _LOCK:
        return {k: tuple(sorted(v)) for k, v in sorted(_SHAPE_BUCKETS.items())}


def aot_dir() -> str | None:
    """Cache directory, or None when disabled (BLS_NO_AOT=1)."""
    if os.environ.get("BLS_NO_AOT"):
        return None
    return compile_cache_dirs()[1]


def aot_stats() -> dict:
    return dict(_STATS)


def _env_tag() -> str:
    import jax

    devs = jax.devices()
    return (
        f"{jax.__version__}-{jax.default_backend()}-"
        f"{devs[0].device_kind}-n{len(devs)}"
    )


_SRC_VERSION: str | None = None


def _src_version() -> str:
    """Content hash of this package's source files (code identity for
    cache keys — computed once per process, no tracing needed)."""
    global _SRC_VERSION
    if _SRC_VERSION is None:
        h = hashlib.sha256()
        pkg_dir = os.path.dirname(os.path.abspath(__file__))
        crypto_dir = os.path.join(
            os.path.dirname(pkg_dir), "crypto", "bls"
        )  # traced programs bake in fields.py constants/functions too
        for d in (pkg_dir, crypto_dir):
            if not os.path.isdir(d):
                continue
            for fname in sorted(os.listdir(d)):
                if fname.endswith(".py"):
                    with open(os.path.join(d, fname), "rb") as fh:
                        h.update(f"{os.path.basename(d)}/{fname}".encode())
                        h.update(fh.read())
        _SRC_VERSION = h.hexdigest()[:16]
    return _SRC_VERSION


def _sig(args) -> str:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = [str(treedef)]
    for leaf in leaves:
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", type(leaf).__name__)
        parts.append(f"{shape}:{dtype}")
    return "|".join(parts)


def _save(compiled, path: str) -> None:
    """Pickle one compiled executable together with the ids of the
    devices it was compiled for, in assignment order."""
    from jax.experimental.serialize_executable import serialize

    payload, in_tree, out_tree = serialize(compiled)
    device_ids = [
        d.id for d in compiled._executable._unloaded_executable.device_list
    ]
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "wb") as fh:
        pickle.dump((payload, in_tree, out_tree, device_ids), fh)
    os.replace(tmp, path)


def _load(path: str):
    """Deserialize one executable onto the devices it was compiled for.
    ``deserialize_and_load`` defaults to EVERY device of the backend, which
    turns a single-device program into an N-device one on any host that
    sees more than one device."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load

    with open(path, "rb") as fh:
        payload, in_tree, out_tree, device_ids = pickle.load(fh)
    by_id = {d.id: d for d in jax.devices()}
    return deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids],
    )


def aot_jit(fn, name: str, disk: bool = True):
    """Wrap a ``jax.jit``-ed callable with a per-shape AOT executable cache.

    ``fn`` must support ``.lower(*args)`` (any jitted function does).  The
    wrapper keeps one loaded/compiled executable per argument signature in
    memory and one pickle per signature on disk.

    ``disk=False`` keeps only the in-memory tier.  Used for programs
    jitted with ``donate_argnums``: round 13 saw a
    ``deserialize_and_load``-ed executable's donated buffers read garbage
    after a disk round-trip (the resident sweep corrupted balance
    hi-limbs by exactly the aliased carry words) on the stack of that
    time, while the same executable straight from ``lowered.compile()``
    was correct.  Donated kernels therefore recompile once per process —
    they are small element-wise programs, and the boot warmer compiles
    them off the critical path.

    The wrapped function stays reachable as ``.jitted`` (lower it for a
    described device without going through the cache).
    """
    compiled_by_sig: dict = {}
    profile_by_sig: dict = {}  # sig -> its _PROFILE row (hit-path handle)

    def _log(msg: str) -> None:
        if os.environ.get("BLS_AOT_LOG"):
            import sys
            import time

            print(f"[aot {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)

    def call(*args):
        sig = _sig(args)
        hit = compiled_by_sig.get(sig)
        if hit is not None:
            prof_hit = profile_by_sig.get(sig)
            if prof_hit is not None:
                # two dict ops against a ms-scale device dispatch.
                # Deliberately lock-free: `+=` is a read-modify-write, so
                # concurrent hits (warmer thread + live drain) can lose an
                # increment — acceptable for a diagnostic attribution
                # count, not worth a lock on the dispatch hot path
                prof_hit["hits"] += 1
                prof_hit["last_use"] = time.time()
            return hit(*args)

        prof = _profile_entry(name, sig, _caller_site())
        prof["misses"] += 1
        prof["last_use"] = time.time()
        profile_by_sig[sig] = prof

        base = aot_dir() if disk else None
        path = None
        if base is not None:
            key = hashlib.sha256(
                f"{name}||{_env_tag()}||{sig}||{_src_version()}".encode()
            ).hexdigest()[:32]
            path = os.path.join(base, f"{name}-{key}.aot")

        # 1) disk hit: deserialize — BEFORE any lowering, which is the
        # dominant warm-start cost of the big staged programs
        if path is not None and os.path.exists(path):
            try:
                t1 = time.perf_counter()
                loaded = _load(path)
                load_s = time.perf_counter() - t1
                _log(f"{name}: AOT loaded in {load_s:.1f}s")
                with _LOCK:
                    _STATS["loads"] += 1
                prof["loads"] += 1
                prof["load_seconds"] += load_s
                prof["source"] = "disk"
                _note_load(name, load_s)
                _note_cost(name, sig, loaded)
                compiled_by_sig[sig] = loaded
            except Exception as e:
                _log(f"{name}: AOT load FAILED ({type(e).__name__}: {e})")
                with _LOCK:
                    _STATS["errors"] += 1
                prof["errors"] += 1
                _note_error("load")
                loaded = None  # fall through to a fresh compile
            if loaded is not None:
                # invoke OUTSIDE the try: a genuine runtime error from the
                # program must surface, not masquerade as a load failure
                # and trigger a silent recompile + second execution
                return loaded(*args)

        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        lower_s = time.perf_counter() - t0
        _log(f"{name}: lowered in {lower_s:.1f}s")
        with _LOCK:
            _STATS["retraces"] += 1
        prof["lower_seconds"] += lower_s
        _note_retrace(name, sig, prof["caller"], lower_s)

        # 2) compile (and best-effort persist)
        t2 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t2
        _log(f"{name}: COMPILED in {compile_s:.1f}s")
        prof["compiles"] += 1
        prof["compile_seconds"] += compile_s
        prof["source"] = "compile"
        _note_compile(name, compile_s)
        with _LOCK:
            _STATS["compiles"] += 1
        _note_cost(name, sig, compiled)
        compiled_by_sig[sig] = compiled
        if path is not None:
            try:
                _save(compiled, path)
                with _LOCK:
                    _STATS["saves"] += 1
                prof["saves"] += 1
                _note_save()
            except Exception:
                with _LOCK:
                    _STATS["errors"] += 1
                prof["errors"] += 1
                _note_error("save")
        return compiled(*args)

    call.__name__ = f"aot_{name}"
    call.jitted = fn
    return call
