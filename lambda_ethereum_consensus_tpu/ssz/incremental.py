"""Incremental container Merkleization: delta-driven subtree reuse.

``process_slot`` needs ``hash_tree_root(BeaconState)`` every slot; a full
rehash of a 1M-validator state costs tens of seconds even with the device
backend (BENCH_r03: 50.4 s warm — 4.2x the 12 s slot budget), while the
slot-to-slot *delta* is tiny: a couple of history rows, the balances the
epoch touched, the validators an operation replaced.  The reference stays
inside the budget because its Rust ``tree_hash`` crate recomputes roots
natively per slot (ref: native/ssz_nif/src/lib.rs:26-153); the TPU build
gets there by not recomputing at all.

``IncrementalStateRoot`` keeps, per big field, the packed chunk array and
every Merkle level of its populated subtree.  Deltas arrive two ways:

- **Pushed** (round 13): the big list fields ride in
  ``state_transition.mutable.TrackedList`` objects, each logging its own
  touched indices and pointing at the list it was adopt-copied from.
  The engine stamps the exact instance its cache last matched; a later
  root walks the adopt chain back to the stamp and applies the unioned
  index logs — no comparison pass at all, and an untouched field
  returns its cached root in O(1).
- **Diffed** (fallback): fields whose chain can't vouch (foreign lists,
  branched lineages, slice/structural mutations, a second engine) are
  compared against the cached chunks exactly as before — value diff for
  packed uint columns, identity diff for lists of immutable containers.

Either way only the paths from dirty leaves to the root are rehashed:
O(k log N) host hashes instead of O(N).  Wholesale changes (epoch
balance sweeps) fall back to a full field rebuild through the configured
backend — the device path for big arrays — chosen automatically when a
quarter of the chunks moved.  The epoch boundary's two structural moves
are cheaper still: :meth:`IncrementalStateRoot.rotate_participation`
adopts the current-participation subtree as previous's and installs a
zero subtree (pure ``ZERO_HASHES`` rows, no hashing) for current.

The engine is exact, not approximate: tracking degrades to ``full`` on
any mutation it cannot describe per-index, a false-positive delta only
costs extra hashes, and every strategy's output is pinned against the
plain ``hash_tree_root`` oracle in tests/unit/test_incremental.py.

The pushed-delta chain has three consumers, all through the one walk
``mutable.dirty_superset``: this engine (the state's root), the resident
epoch plane's sync, and the encoded image (ssz/encoded.py: the state's
*serialization*, patched from the same logs so that persisting a
post-state does not walk the registry either).
"""

from __future__ import annotations

import hashlib

import numpy as np

from ..telemetry import inc, span
from .core import (
    ByteVector,
    List,
    SSZError,
    Uint,
    Vector,
    _element_roots,
    _resolve,
    _typ,
    mix_in_length,
)
from .hash import ZERO_HASHES, get_hash_backend, HashlibBackend

__all__ = ["IncrementalStateRoot"]

# a field whose dirty fraction exceeds this rebuilds through the backend
# instead of per-path host hashing
_REBUILD_FRACTION = 4
# full-field rebuilds route to the device backend only above this chunk
# count: a dispatch has a fixed cost and the host hashes ~1.5M nodes/s.
# The crossover was set when every dispatch crossed a remote link; on a
# local chip it is not measured (ROADMAP C1).
_DEVICE_CHUNKS = 1 << 18
# ...but never below this even on the widest mesh: tiny dispatches lose
# to the host regardless of how many devices split them
_DEVICE_CHUNKS_MIN = 1 << 12


def _device_chunk_floor() -> int:
    """The host/device routing crossover, shard-aware (round 21): with
    mesh-sharded state residency on, each device hashes only its block
    of the chunk rows, so the per-device crossover divides by the live
    mesh width — a rebuild big enough to beat the host on ONE chip at
    2^18 chunks beats it at 2^18/8 when eight chips split the rows."""
    from ..ops.mesh import device_count, state_shard_enabled

    if not state_shard_enabled():
        return _DEVICE_CHUNKS
    n = device_count()
    return max(_DEVICE_CHUNKS_MIN, _DEVICE_CHUNKS // max(1, n))


def _sha(pair: bytes) -> bytes:
    return hashlib.sha256(pair).digest()


def _build_levels(chunks: np.ndarray, backend) -> list[np.ndarray]:
    """All levels of the populated subtree, bottom (chunks) first."""
    levels = [chunks]
    level = chunks
    d = 0
    while level.shape[0] > 1:
        if level.shape[0] % 2:
            zrow = np.frombuffer(ZERO_HASHES[d], np.uint8).reshape(1, 32)
            level = np.concatenate([level, zrow], axis=0)
        level = backend.hash_level(level.reshape(-1, 64))
        levels.append(level)
        d += 1
    return levels


def _zero_levels(m: int) -> list[np.ndarray]:
    """The populated-subtree levels of ``m`` all-zero chunks — every row
    of level ``d`` is ``ZERO_HASHES[d]``, so no hashing happens at all
    (the epoch participation reset installs this in O(m) memset)."""
    levels = [np.zeros((max(m, 0), 32), np.uint8)]
    rows, d = m, 0
    while rows > 1:
        rows = (rows + 1) // 2
        d += 1
        row = np.frombuffer(ZERO_HASHES[d], np.uint8).reshape(1, 32)
        levels.append(np.repeat(row, rows, axis=0))
    return levels


def _update_paths(levels: list[np.ndarray], dirty: np.ndarray) -> None:
    """Rehash the root paths of ``dirty`` leaf indices in place (host)."""
    for d in range(len(levels) - 1):
        parents = np.unique(dirty >> 1)
        src, dst = levels[d], levels[d + 1]
        n = src.shape[0]
        for p in parents:
            li = 2 * int(p)
            ri = li + 1
            left = src[li].tobytes()
            right = src[ri].tobytes() if ri < n else ZERO_HASHES[d]
            dst[p] = np.frombuffer(_sha(left + right), np.uint8)
        dirty = parents


def _cap_root(levels: list[np.ndarray], limit_chunks: int) -> bytes:
    """Extend the populated-subtree root to the type's limit depth."""
    depth = max(limit_chunks - 1, 0).bit_length()
    if not levels or levels[0].shape[0] == 0:
        return ZERO_HASHES[depth]
    root = levels[-1][0].tobytes()
    for d in range(len(levels) - 1, depth):
        root = _sha(root + ZERO_HASHES[d])
    return root


class _FieldCache:
    __slots__ = (
        "strategy", "name", "prev", "chunks", "levels", "count", "root",
        "last_list", "stamp_gen",
    )

    def __init__(self, strategy: str):
        self.strategy = strategy
        self.name = ""  # the field it serves now (a rotated cache changes fields)
        self.prev = None  # identity snapshot (object-element strategies)
        self.chunks = None  # packed (m, 32) leaf chunks — ALWAYS levels[0]
        self.levels = None
        self.count = -1
        self.root = None
        # pushed-delta snapshot point: the exact TrackedList instance the
        # cache last matched, and its mutation generation at that instant
        self.last_list = None
        self.stamp_gen = -1


def _uint_dtype(t: Uint) -> str | None:
    return {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}.get(t.size)


class IncrementalStateRoot:
    """Stateful ``hash_tree_root`` for one evolving container instance.

    ``backend`` is used for full-field (re)builds — pass the device
    backend for 1M-validator states; dirty-path updates always hash on
    host (a path is ~20 nodes; a device dispatch costs more than the
    hashes).  One engine tracks ONE logical state lineage:
    feed it successive snapshots of the same advancing state, not
    unrelated states.
    """

    def __init__(self, cls: type, backend=None):
        self.cls = cls
        self.backend = backend
        self._host = HashlibBackend()
        self._fields: dict[str, _FieldCache] = {}
        self._spec_name = None
        # container-level rows retained by the last root() call: the
        # witness plane reads top-level multiproof siblings from here
        # instead of re-deriving every field root
        self._top_levels: list[np.ndarray] | None = None

    # ------------------------------------------------------------- public
    def root(self, state, spec=None) -> bytes:
        with span("state_root_incremental"):
            return self._root(state, spec)

    def _root(self, state, spec=None) -> bytes:
        from ..config import get_chain_spec

        spec = spec or get_chain_spec()
        if self._spec_name != spec.name:
            # config swap invalidates every cached limit/shape
            self._fields.clear()
            self._top_levels = None
            self._spec_name = spec.name
        backend = self.backend or get_hash_backend()
        schema = self.cls.__ssz_schema__
        roots = np.empty((len(schema), 32), np.uint8)
        for i, (fname, ftype) in enumerate(schema.items()):
            roots[i] = np.frombuffer(
                self._field_root(fname, _typ(ftype), getattr(state, fname), spec, backend),
                np.uint8,
            )
        # top-level container tree: ~32 leaves, host hashing
        levels = _build_levels(roots, self._host)
        self._top_levels = levels
        return _cap_root(levels, len(schema))

    # ---- witness-plane accessors (lambda_ethereum_consensus_tpu.witness):
    # every Merkle level is already resident per big field, so a
    # multiproof planner can read arbitrary interior nodes without
    # rebuilding any part of the tree.

    def top_levels(self) -> list[np.ndarray] | None:
        """Container-level rows (field roots upward) as of the last
        :meth:`root` call, or ``None`` before any root was computed."""
        return self._top_levels

    def field_levels(self, fname: str) -> list[np.ndarray] | None:
        """The retained populated-subtree levels of one big field
        (bottom = packed chunks / element roots), or ``None`` when the
        field is uncached (small-field strategy, or no root yet)."""
        cache = self._fields.get(fname)
        if cache is None or cache.levels is None:
            return None
        return cache.levels

    def retained_bytes(self) -> int:
        """Bytes held by the retained tree rows (per-field subtree
        levels + container-level rows) — the witness plane's entry in
        the round-18 memory accounting."""
        total = 0
        if self._top_levels:
            total += sum(int(lvl.nbytes) for lvl in self._top_levels)
        for cache in self._fields.values():
            if cache.levels:
                total += sum(int(lvl.nbytes) for lvl in cache.levels)
        return total

    def rotate_participation(self, new_current, spec=None) -> bool:
        """Epoch participation reset as two structural moves: the cached
        current-participation subtree becomes previous's (the lists were
        just aliased by ``process_participation_flag_updates``, so the
        moved cache's snapshot point travels with it), and a zero subtree
        — no hashing — is installed for current, stamped against the
        brand-new all-zero list so the very next root is an O(1) cache
        hit.  Returns False (caller falls back to ordinary diffing) when
        the current cache isn't in a movable state."""
        cur = self._fields.get("current_epoch_participation")
        if cur is None or cur.strategy != "uint" or cur.chunks is None:
            # no movable subtree: drop both caches, let diffing rebuild
            self._fields.pop("current_epoch_participation", None)
            self._fields.pop("previous_epoch_participation", None)
            return False
        self._fields["previous_epoch_participation"] = cur
        fresh = _FieldCache("uint")
        n = len(new_current)
        m = (n + 31) // 32  # participation elements are uint8
        fresh.levels = _zero_levels(m)
        fresh.chunks, fresh.count = fresh.levels[0], m
        self._fields["current_epoch_participation"] = fresh
        self._stamp(fresh, new_current)
        return True

    @staticmethod
    def _stamp(cache: _FieldCache, value) -> None:
        """Record that ``cache`` matches ``value`` at this instant; later
        mutations logged on the instance (or its adopt-copies) are the
        exact superset of what can differ."""
        gen = getattr(value, "gen", None)
        if gen is None:
            cache.last_list, cache.stamp_gen = None, -1
        else:
            cache.last_list, cache.stamp_gen = value, gen

    # ------------------------------------------------------------ fields
    def _field_root(self, fname, ftype, value, spec, backend) -> bytes:
        strategy = self._classify(ftype, spec)
        if strategy == "small":
            return ftype.hash_tree_root(value, spec, self._host)
        cache = self._fields.get(fname)
        if cache is None or cache.strategy != strategy:
            cache = self._fields[fname] = _FieldCache(strategy)
        cache.name = fname
        if strategy == "uint":
            return self._uint_field(cache, ftype, value, spec, backend)
        return self._object_field(cache, ftype, value, spec, backend)

    def _classify(self, ftype, spec) -> str:
        if isinstance(ftype, (List, Vector)):
            elem = _typ(ftype.elem)
            n_max = _resolve(
                ftype.limit if isinstance(ftype, List) else ftype.length, spec
            )
            if n_max < 4096:
                return "small"  # full recompute is microseconds
            if isinstance(elem, Uint) and _uint_dtype(elem) is not None:
                return "uint"
            is_container = getattr(elem, "cls", None) is not None
            if is_container or isinstance(elem, ByteVector):
                # containers (via adapter) and ByteVector elements: one
                # leaf per element, identity-diffed
                return "object"
        return "small"

    def _rebuild(self, cache: _FieldCache, leaves: np.ndarray, backend):
        """A whole field's levels through the configured backend above the
        device floor, else on the host; counted by where it was hashed."""
        m = leaves.shape[0]
        chosen = backend if m > _device_chunk_floor() else self._host
        if m:
            inc(
                "state_root_rebuilt_chunks_total", m, field=cache.name,
                where="host" if isinstance(chosen, HashlibBackend) else "device",
            )
        return _build_levels(leaves, chosen)

    @staticmethod
    def _repath(cache: _FieldCache, dirty: np.ndarray) -> None:
        inc(
            "state_root_rebuilt_chunks_total", int(dirty.shape[0]),
            field=cache.name, where="paths",
        )
        _update_paths(cache.levels, dirty)

    def _consume_delta(self, cache: _FieldCache, value) -> frozenset | None:
        """The pushed-delta channel: a superset of the indices at which
        ``value`` may differ from the cached snapshot.  One shared walk
        (``mutable.dirty_superset``) serves this engine, the resident
        plane's shard-aware sync and the encoded image (ssz/encoded.py);
        ``None`` means the chain can't vouch
        and the caller value-diffs, which is always exact."""
        from ..state_transition.mutable import dirty_superset

        return dirty_superset(value, cache.last_list, cache.stamp_gen)

    # ---- packed basic columns: balances, participation, inactivity, slashings
    def _uint_field(self, cache, ftype, value, spec, backend) -> bytes:
        elem = _typ(ftype.elem)
        dtype = _uint_dtype(elem)
        is_list = isinstance(ftype, List)
        n = len(value)
        if is_list:
            limit = _resolve(ftype.limit, spec)
            if n > limit:
                raise SSZError(f"{ftype!r} over limit: {n}")
            limit_chunks = (limit * elem.size + 31) // 32
        else:
            if n != _resolve(ftype.length, spec):
                raise SSZError(f"{ftype!r} length mismatch: {n}")
            limit_chunks = (n * elem.size + 31) // 32
        m = (n * elem.size + 31) // 32
        per_chunk = 32 // elem.size

        delta = self._consume_delta(cache, value)
        if delta is not None and cache.chunks is not None and cache.count == m:
            if len(delta) > max((m * per_chunk) // _REBUILD_FRACTION, 8):
                delta = None  # wholesale change: one vector rebuild wins
            else:
                if delta:
                    view = cache.chunks.reshape(-1).view(dtype)
                    lim = 1 << (8 * elem.size)
                    dirty_chunks: set[int] = set()
                    for i in delta:
                        if i >= n:
                            continue  # shrink paths mark full; guard anyway
                        v = int(value[i])
                        if not 0 <= v < lim:
                            raise SSZError(
                                f"{ftype!r}: element {v} out of uint{elem.size * 8} range"
                            )
                        view[i] = v
                        dirty_chunks.add(i // per_chunk)
                    if dirty_chunks:
                        self._repath(
                            cache,
                            np.fromiter(dirty_chunks, np.int64, len(dirty_chunks)),
                        )
                self._stamp(cache, value)
                root = _cap_root(cache.levels, limit_chunks)
                return mix_in_length(root, n) if is_list else root

        try:
            # numpy >= 2 raises on out-of-range Python ints instead of
            # silently wrapping, so this conversion doubles as validation
            arr = np.asarray(value, dtype)
        except (OverflowError, ValueError, TypeError) as e:
            raise SSZError(f"{ftype!r}: {e}") from None
        raw = arr.tobytes()
        pad = (-len(raw)) % 32
        chunks = np.frombuffer(raw + b"\x00" * pad, np.uint8).reshape(-1, 32)
        if cache.chunks is None or cache.count != m:
            cw = chunks.copy()  # writable: the pushed-delta path edits in place
            cache.levels = self._rebuild(cache, cw, backend)
            cache.chunks, cache.count = cw, m
        else:
            dirty = np.nonzero(np.any(cache.chunks != chunks, axis=1))[0]
            if dirty.size:
                if dirty.size > m // _REBUILD_FRACTION:
                    cw = chunks.copy()
                    cache.levels = self._rebuild(cache, cw, backend)
                    cache.chunks = cw
                else:
                    cache.chunks[dirty] = chunks[dirty]
                    self._repath(cache, dirty)
        self._stamp(cache, value)
        root = _cap_root(cache.levels, limit_chunks)
        return mix_in_length(root, n) if is_list else root

    # ---- element-rooted lists/vectors: validators, block_roots, randao_mixes
    def _object_field(self, cache, ftype, value, spec, backend) -> bytes:
        elem = ftype.elem  # raw schema entry: _element_roots' batched
        # fast path matches on the Container CLASS, not the adapter
        is_list = isinstance(ftype, List)
        n = len(value)
        if is_list:
            limit = _resolve(ftype.limit, spec)
            if n > limit:
                raise SSZError(f"{ftype!r} over limit: {n}")
            limit_chunks = limit
        else:
            if n != _resolve(ftype.length, spec):
                raise SSZError(f"{ftype!r} length mismatch: {n}")
            limit_chunks = n

        delta = self._consume_delta(cache, value)
        if delta is not None and cache.prev is not None and cache.count == n:
            dirty = sorted(
                i for i in delta if i < n and value[i] is not cache.prev[i]
            )
            if len(dirty) > max(n // _REBUILD_FRACTION, 8):
                delta = None  # wholesale: rebuild through the backend below
            else:
                if dirty:
                    sub = self._element_leaves(
                        elem, [value[i] for i in dirty], spec, self._host
                    )
                    cache.levels[0][dirty] = sub
                    self._repath(cache, np.asarray(dirty, np.int64))
                    for i in dirty:
                        cache.prev[i] = value[i]
                self._stamp(cache, value)
                root = _cap_root(cache.levels, limit_chunks)
                return mix_in_length(root, n) if is_list else root

        if cache.prev is None or cache.count != n:
            leaves = self._element_leaves(elem, value, spec, backend)
            cache.levels = self._rebuild(cache, leaves, backend)
            cache.prev, cache.count = list(value), n
        else:
            prev = cache.prev
            dirty = [i for i in range(n) if value[i] is not prev[i]]
            if dirty:
                if len(dirty) > max(n // _REBUILD_FRACTION, 8):
                    leaves = self._element_leaves(elem, value, spec, backend)
                    cache.levels = self._rebuild(cache, leaves, backend)
                else:
                    sub = self._element_leaves(
                        elem, [value[i] for i in dirty], spec, self._host
                    )
                    cache.levels[0][dirty] = sub
                    self._repath(cache, np.asarray(dirty, np.int64))
                cache.prev = list(value)
        self._stamp(cache, value)
        root = _cap_root(cache.levels, limit_chunks)
        return mix_in_length(root, n) if is_list else root

    def _element_leaves(self, elem, values, spec, backend) -> np.ndarray:
        if not values:
            return np.zeros((0, 32), np.uint8)
        t = _typ(elem)
        if isinstance(t, ByteVector) and _resolve(t.length, spec) == 32:
            # Bytes32 history/randao rows ARE their own leaves
            raws = []
            for v in values:
                b = bytes(v)
                if len(b) != 32:
                    raise SSZError("Bytes32 row of wrong length")
                raws.append(b)
            # copy: frombuffer views are read-only, but these leaves are
            # updated in place on later dirty-path passes
            return np.frombuffer(b"".join(raws), np.uint8).reshape(-1, 32).copy()
        return _element_roots(elem, values, spec, backend)
