"""SSZ (SimpleSerialize) type system, codec and Merkleization engine.

This replaces the reference's Rust ``ssz_nif`` (serialize/deserialize/
hash_tree_root for every container, generic over Mainnet/Minimal configs —
ref: native/ssz_nif/src/lib.rs:26-153) with one engine that is:

- **config-late-bound**: ``List``/``Vector`` sizes may name a ChainSpec
  constant (e.g. ``List(Validator, "VALIDATOR_REGISTRY_LIMIT")``) resolved at
  call time, so a single set of container definitions serves every preset —
  where the reference duplicates types per config via Rust generics
  (native/ssz_nif/src/ssz_types/config.rs:15-48).
- **backend-pluggable for hashing**: Merkleization consumes whole tree levels
  as ``(N, 64) → (N, 32)`` batches, so large trees (validator registry,
  balances) dispatch to the TPU SHA-256 kernel while small trees stay on host.

Value model: ``uintN`` → int, ``boolean`` → bool, byte types → bytes,
``Vector``/``List`` → list (or numpy fast paths when packing), bitfields →
:class:`~.bitfields.Bitvector`/:class:`~.bitfields.Bitlist`, containers →
instances of :class:`Container` subclasses.
"""

from __future__ import annotations

import struct
import threading
from functools import partial
from operator import attrgetter
from typing import Any, Callable, Sequence

import numpy as np

from ..config import ChainSpec, get_chain_spec
from ..telemetry import get_metrics as _get_metrics
from .bitfields import Bitlist as BitlistValue
from .bitfields import Bitvector as BitvectorValue
from .hash import ZERO_HASHES, HashBackend, get_hash_backend, sha256

__all__ = [
    "SSZType",
    "Uint",
    "Boolean",
    "ByteVector",
    "ByteList",
    "Vector",
    "List",
    "Bitvector",
    "Bitlist",
    "Container",
    "uint8",
    "uint16",
    "uint32",
    "uint64",
    "uint128",
    "uint256",
    "boolean",
    "SSZError",
    "merkleize_chunks",
    "mix_in_length",
    "pack_bytes",
]

BYTES_PER_CHUNK = 32
OFFSET_SIZE = 4

# per-Container-class BoundSpans for the top-level hash_tree_root entry,
# and the default registry pinned at import (a process singleton — the
# only registry product code records to): the no-op fast path is then one
# module-global read + one attribute check per root call
_ROOT_SPANS: dict[type, object] = {}
_METRICS = _get_metrics()


class SSZError(ValueError):
    """Malformed SSZ input or value outside its type's bounds."""


def _resolve(n: int | str | Callable[[ChainSpec], int], spec: ChainSpec) -> int:
    """Resolve a possibly spec-late-bound size to a concrete int."""
    if isinstance(n, int):
        return n
    if isinstance(n, str):
        return int(spec[n])
    return int(n(spec))


def mix_in_length(root: bytes, length: int) -> bytes:
    return sha256(root + length.to_bytes(32, "little"))


def pack_bytes(data: bytes) -> np.ndarray:
    """Right-pad serialized bytes to a whole number of 32-byte chunks."""
    n = len(data)
    nchunks = max(1, (n + BYTES_PER_CHUNK - 1) // BYTES_PER_CHUNK)
    buf = np.zeros(nchunks * BYTES_PER_CHUNK, dtype=np.uint8)
    buf[:n] = np.frombuffer(data, dtype=np.uint8)
    return buf.reshape(nchunks, BYTES_PER_CHUNK)


def merkleize_chunks(
    chunks: np.ndarray,
    limit_chunks: int | None = None,
    backend: HashBackend | None = None,
) -> bytes:
    """Binary Merkle tree root of ``(N, 32)`` chunks, zero-padded to
    ``next_pow2(limit_chunks)`` leaves per the SSZ spec.

    One backend call per level — the batched shape that the TPU backend turns
    into a single device op per level.
    """
    backend = backend or get_hash_backend()
    count = int(chunks.shape[0])
    limit = count if limit_chunks is None else int(limit_chunks)
    if count > limit:
        raise SSZError(f"{count} chunks exceed limit {limit}")
    depth = max(limit - 1, 0).bit_length()
    if count == 0:
        return ZERO_HASHES[depth]
    level = np.ascontiguousarray(chunks, dtype=np.uint8)
    # Large trees: reduce the populated subtree in one fused device call,
    # then extend to the limit depth with precomputed zero-subtree roots.
    subtree = getattr(backend, "merkle_subtree_root", None)
    if (
        subtree is not None
        and depth > 0
        and count >= getattr(backend, "tree_threshold", 1 << 62)
    ):
        root, sub_depth = subtree(level)
        for d in range(sub_depth, depth):
            root = sha256(root + ZERO_HASHES[d])
        return root
    for d in range(depth):
        if level.shape[0] % 2:
            zrow = np.frombuffer(ZERO_HASHES[d], np.uint8).reshape(1, 32)
            level = np.concatenate([level, zrow], axis=0)
        level = backend.hash_level(level.reshape(-1, 64))
    return level[0].tobytes()


class SSZType:
    """Base descriptor. Subclasses implement the SSZ spec for one type kind."""

    def is_fixed_size(self, spec: ChainSpec) -> bool:
        raise NotImplementedError

    def fixed_length(self, spec: ChainSpec) -> int:
        raise NotImplementedError

    def serialize(self, value: Any, spec: ChainSpec | None = None) -> bytes:
        raise NotImplementedError

    def deserialize(self, data: bytes, spec: ChainSpec | None = None) -> Any:
        raise NotImplementedError

    def hash_tree_root(
        self, value: Any, spec: ChainSpec | None = None, backend: HashBackend | None = None
    ) -> bytes:
        raise NotImplementedError

    def default(self, spec: ChainSpec | None = None) -> Any:
        raise NotImplementedError

    # Basic types pack multiple values per chunk.
    is_basic = False


class Uint(SSZType):
    is_basic = True

    def __init__(self, bits: int):
        assert bits in (8, 16, 32, 64, 128, 256)
        self.bits = bits
        self.size = bits // 8

    def is_fixed_size(self, spec):
        return True

    def fixed_length(self, spec):
        return self.size

    def serialize(self, value, spec=None):
        v = int(value)
        if not 0 <= v < (1 << self.bits):
            raise SSZError(f"uint{self.bits} out of range: {v}")
        return v.to_bytes(self.size, "little")

    def deserialize(self, data, spec=None):
        if len(data) != self.size:
            raise SSZError(f"uint{self.bits}: expected {self.size} bytes, got {len(data)}")
        return int.from_bytes(data, "little")

    def hash_tree_root(self, value, spec=None, backend=None):
        return self.serialize(value).ljust(32, b"\x00")

    def default(self, spec=None):
        return 0

    def __repr__(self):
        return f"uint{self.bits}"


class Boolean(SSZType):
    is_basic = True
    size = 1

    def is_fixed_size(self, spec):
        return True

    def fixed_length(self, spec):
        return 1

    def serialize(self, value, spec=None):
        if value not in (True, False, 0, 1):
            raise SSZError(f"invalid boolean: {value!r}")
        return b"\x01" if value else b"\x00"

    def deserialize(self, data, spec=None):
        if data == b"\x00":
            return False
        if data == b"\x01":
            return True
        raise SSZError(f"invalid boolean encoding: {data!r}")

    def hash_tree_root(self, value, spec=None, backend=None):
        return self.serialize(value).ljust(32, b"\x00")

    def default(self, spec=None):
        return False

    def __repr__(self):
        return "boolean"


uint8 = Uint(8)
uint16 = Uint(16)
uint32 = Uint(32)
uint64 = Uint(64)
uint128 = Uint(128)
uint256 = Uint(256)
boolean = Boolean()


class ByteVector(SSZType):
    """``Bytes1`` … ``Bytes96``: fixed-length opaque byte strings."""

    def __init__(self, length: int | str):
        self.length = length

    def is_fixed_size(self, spec):
        return True

    def fixed_length(self, spec):
        return _resolve(self.length, spec)

    def serialize(self, value, spec=None):
        spec = spec or get_chain_spec()
        n = _resolve(self.length, spec)
        b = bytes(value)
        if len(b) != n:
            raise SSZError(f"ByteVector[{n}]: got {len(b)} bytes")
        return b

    def deserialize(self, data, spec=None):
        spec = spec or get_chain_spec()
        n = _resolve(self.length, spec)
        if len(data) != n:
            raise SSZError(f"ByteVector[{n}]: got {len(data)} bytes")
        return bytes(data)

    def hash_tree_root(self, value, spec=None, backend=None):
        spec = spec or get_chain_spec()
        return merkleize_chunks(pack_bytes(self.serialize(value, spec)), backend=backend)

    def default(self, spec=None):
        spec = spec or get_chain_spec()
        return b"\x00" * _resolve(self.length, spec)

    def __repr__(self):
        return f"ByteVector[{self.length}]"


class ByteList(SSZType):
    """Variable-length byte string with a maximum length (e.g. extra_data)."""

    def __init__(self, limit: int | str):
        self.limit = limit

    def is_fixed_size(self, spec):
        return False

    def serialize(self, value, spec=None):
        spec = spec or get_chain_spec()
        b = bytes(value)
        if len(b) > _resolve(self.limit, spec):
            raise SSZError(f"ByteList over limit {self.limit}")
        return b

    def deserialize(self, data, spec=None):
        spec = spec or get_chain_spec()
        if len(data) > _resolve(self.limit, spec):
            raise SSZError(f"ByteList over limit {self.limit}")
        return bytes(data)

    def hash_tree_root(self, value, spec=None, backend=None):
        spec = spec or get_chain_spec()
        b = self.serialize(value, spec)
        limit_chunks = (_resolve(self.limit, spec) + BYTES_PER_CHUNK - 1) // BYTES_PER_CHUNK
        chunks = pack_bytes(b) if b else np.zeros((0, 32), np.uint8)
        return mix_in_length(merkleize_chunks(chunks, limit_chunks, backend), len(b))

    def default(self, spec=None):
        return b""

    def __repr__(self):
        return f"ByteList[{self.limit}]"


def _pack_basics(elem: Uint | Boolean, values: Sequence, spec: ChainSpec) -> np.ndarray:
    """Pack a homogeneous basic-type sequence into chunks (numpy fast path)."""
    if isinstance(elem, Uint) and elem.bits <= 64:
        try:
            arr = np.asarray([int(v) for v in values], dtype=np.uint64)
        except (OverflowError, TypeError) as e:
            raise SSZError(f"value out of range for {elem!r}: {e}") from None
        if elem.bits < 64 and len(values) and int(arr.max(initial=0)) >= (1 << elem.bits):
            raise SSZError(f"value out of range for {elem!r}")
        data = arr.astype(f"<u{elem.size}").tobytes()
    elif isinstance(elem, Boolean):
        if any(v not in (True, False, 0, 1) for v in values):
            raise SSZError("invalid boolean in sequence")
        data = bytes(1 if v else 0 for v in values)
    else:  # uint128/uint256
        data = b"".join(elem.serialize(v, spec) for v in values)
    if not data:
        return np.zeros((0, 32), np.uint8)
    return pack_bytes(data)


# sequences shorter than this keep the element loop: it is fine there
# and simpler (the batched root path draws the same line)
_BATCH_MIN = 64


def _serialize_rows(elem, values: Sequence, spec: ChainSpec) -> np.ndarray | None:
    """The serialized elements of a fixed-size sequence as ONE ``(n, size)``
    byte array, for Uint<=64 / Boolean / ByteVector elements and FLAT
    containers of those (e.g. ``Validator``): a container is filled a
    field COLUMN at a time — one pass per field over the list, the shape
    ``_element_roots_batched`` uses for roots — where the element loop
    costs ~9 us a validator (8 s of a 2^20-validator state's encode).

    ``None`` for a type not specialized here AND for any malformed value
    (wrong byte length, uint out of range, boolean not 0/1, a value the
    column conversion refuses): the caller then runs the element loop,
    which raises the typed ``SSZError`` — validity must not depend on
    the path.  The array may be a read-only view of joined bytes."""
    n = len(values)
    try:
        if isinstance(elem, type) and issubclass(elem, Container):
            cols = []
            for fname, ftype in elem.__ssz_schema__.items():
                ftype = _typ(ftype)
                if not isinstance(ftype, (Uint, Boolean, ByteVector)):
                    return None  # nested / variable-size field: not flat
                col = _serialize_rows(ftype, list(map(attrgetter(fname), values)), spec)
                if col is None:
                    return None
                cols.append(col)
            if not cols:
                return None
            # strided column writes: np.concatenate(axis=1) is 5x slower
            out = np.empty((n, sum(c.shape[1] for c in cols)), np.uint8)
            at = 0
            for col in cols:
                out[:, at : at + col.shape[1]] = col
                at += col.shape[1]
            return out
        if isinstance(elem, Uint) and elem.size <= 8:
            # int() is the loop's own conversion; fromiter refuses what
            # uint64 cannot hold (negative, >= 2**64) with OverflowError
            ints = np.fromiter(map(int, values), np.uint64, n)
            if elem.size < 8 and n and int(ints.max()) >> elem.bits:
                return None
            return ints.astype(f"<u{elem.size}").view(np.uint8).reshape(n, elem.size)
        if isinstance(elem, Boolean):
            if not set(values) <= {0, 1}:  # hash-equal to True/False too
                return None
            return np.fromiter(map(bool, values), np.uint8, n).reshape(n, 1)
        if isinstance(elem, ByteVector):
            length = _resolve(elem.length, spec)
            # per-element checks: compensating length errors must not
            # slip through an aggregate-only count, and len() of a
            # buffer that is not ``bytes`` need not be its byte count
            if not length or set(map(type, values)) != {bytes}:
                return None
            if set(map(len, values)) != {length}:
                return None
            return np.frombuffer(b"".join(values), np.uint8).reshape(n, length)
    except (OverflowError, TypeError, ValueError, AttributeError):
        return None  # let the loop path produce the typed error
    return None


def _serialize_elements(elem: SSZType, values: Sequence, spec: ChainSpec) -> bytes:
    if elem.is_fixed_size(spec):
        if len(values) >= _BATCH_MIN:
            rows = _serialize_rows(elem, values, spec)
            if rows is not None:
                return rows.tobytes()
        return b"".join(elem.serialize(v, spec) for v in values)
    parts = [elem.serialize(v, spec) for v in values]
    offset = OFFSET_SIZE * len(parts)
    out = bytearray()
    for p in parts:
        out += offset.to_bytes(OFFSET_SIZE, "little")
        offset += len(p)
    for p in parts:
        out += p
    return bytes(out)


def _deserialize_elements(elem: SSZType, data: bytes, spec: ChainSpec) -> list:
    if len(data) == 0:
        return []
    plan = _plan_of(elem, spec)  # a container element decodes by its plan
    if elem.is_fixed_size(spec):
        size = elem.fixed_length(spec)
        if size == 0 or len(data) % size:
            raise SSZError(f"sequence length {len(data)} not a multiple of element size {size}")
        if plan is not None:
            return plan.decode_rows(data)
        return [elem.deserialize(data[i : i + size], spec) for i in range(0, len(data), size)]
    # variable-size elements: offset table
    decode = plan.decode if plan is not None else partial(elem.deserialize, spec=spec)
    first = int.from_bytes(data[:OFFSET_SIZE], "little")
    if first == 0 or first % OFFSET_SIZE or first > len(data):
        raise SSZError("bad first offset")
    count = first // OFFSET_SIZE
    offsets = [
        int.from_bytes(data[i * OFFSET_SIZE : (i + 1) * OFFSET_SIZE], "little")
        for i in range(count)
    ] + [len(data)]
    values = []
    for i in range(count):
        a, b = offsets[i], offsets[i + 1]
        if a > b or b > len(data):
            raise SSZError("offsets not monotonic or out of bounds")
        values.append(decode(data[a:b]))
    return values


def _element_roots(elem: SSZType, values: Sequence, spec, backend) -> np.ndarray:
    batched = _element_roots_batched(elem, values, spec, backend)
    if batched is not None:
        return batched
    roots = np.empty((len(values), 32), np.uint8)
    for i, v in enumerate(values):
        roots[i] = np.frombuffer(elem.hash_tree_root(v, spec, backend), np.uint8)
    return roots


def _element_roots_batched(elem, values, spec, backend) -> np.ndarray | None:
    """Vectorized roots for lists of FLAT fixed-shape containers (every
    field a Uint/Boolean/ByteVector<=64B — e.g. ``Validator``).

    The naive path merkleizes each element separately: at 1M validators
    that is ~4M tiny python ``merkleize_chunks``/``hash_level`` calls and
    was measured at 51 s for a mainnet-state root — pure host overhead
    (the device hashes 7B nodes/s).  Here each FIELD becomes one (N, 32)
    chunk column via numpy, and each Merkle level of the little
    per-element trees is ONE ``backend.hash_level`` call over all
    elements at once — so the device backend sees N*width/2-block
    batches instead of single pairs."""
    if not (isinstance(elem, type) and issubclass(elem, Container)):
        return None
    schema = elem.__ssz_schema__
    n = len(values)
    if n < _BATCH_MIN or not schema:
        return None  # small lists: the loop is fine and simpler
    be = backend or get_hash_backend()
    columns: list[np.ndarray] = []
    for fname, ftype in schema.items():
        ftype = _typ(ftype)
        if not isinstance(ftype, (Uint, Boolean, ByteVector)):
            return None
        # the field's serialized column; None for uint128/256 and for any
        # malformed value — validity must not depend on list size, so the
        # loop path then raises the typed error
        rows = _serialize_rows(ftype, list(map(attrgetter(fname), values)), spec)
        if rows is None or rows.shape[1] > 64:
            return None
        size = rows.shape[1]
        if size <= 32:
            col = np.zeros((n, 32), np.uint8)
            col[:, :size] = rows
        else:  # two chunks -> one batched hash level
            pair = np.zeros((n, 64), np.uint8)
            pair[:, :size] = rows
            col = be.hash_level(pair)
        columns.append(col)
    width = 1
    while width < len(columns):
        width *= 2
    mat = np.zeros((n, width, 32), np.uint8)
    for j, col in enumerate(columns):
        mat[:, j] = col
    while width > 1:
        mat = be.hash_level(mat.reshape(n * width // 2, 64)).reshape(
            n, width // 2, 32
        )
        width //= 2
    return mat[:, 0]


class Vector(SSZType):
    def __init__(self, elem: SSZType, length: int | str):
        self.elem = elem
        self.length = length

    def is_fixed_size(self, spec):
        return self.elem.is_fixed_size(spec)

    def fixed_length(self, spec):
        return self.elem.fixed_length(spec) * _resolve(self.length, spec)

    def _check_len(self, value, spec):
        n = _resolve(self.length, spec)
        if len(value) != n:
            raise SSZError(f"Vector[{self.elem!r},{n}]: got {len(value)} elements")
        return n

    def serialize(self, value, spec=None):
        spec = spec or get_chain_spec()
        self._check_len(value, spec)
        return _serialize_elements(self.elem, value, spec)

    def deserialize(self, data, spec=None):
        spec = spec or get_chain_spec()
        values = _deserialize_elements(self.elem, data, spec)
        self._check_len(values, spec)
        return values

    def hash_tree_root(self, value, spec=None, backend=None):
        spec = spec or get_chain_spec()
        self._check_len(value, spec)
        if self.elem.is_basic:
            return merkleize_chunks(_pack_basics(self.elem, value, spec), backend=backend)
        return merkleize_chunks(_element_roots(self.elem, value, spec, backend), backend=backend)

    def default(self, spec=None):
        spec = spec or get_chain_spec()
        return [self.elem.default(spec) for _ in range(_resolve(self.length, spec))]

    def __repr__(self):
        return f"Vector[{self.elem!r},{self.length}]"


class List(SSZType):
    def __init__(self, elem: SSZType, limit: int | str | Callable):
        self.elem = elem
        self.limit = limit

    def is_fixed_size(self, spec):
        return False

    def _check_limit(self, value, spec):
        limit = _resolve(self.limit, spec)
        if len(value) > limit:
            name = getattr(self.elem, "__name__", None) or repr(self.elem)
            raise SSZError(f"List[{name}] over limit {limit}: {len(value)}")
        return limit

    def serialize(self, value, spec=None):
        spec = spec or get_chain_spec()
        self._check_limit(value, spec)
        return _serialize_elements(self.elem, value, spec)

    def deserialize(self, data, spec=None):
        spec = spec or get_chain_spec()
        values = _deserialize_elements(self.elem, data, spec)
        self._check_limit(values, spec)
        return values

    def chunk_limit(self, spec) -> int:
        limit = _resolve(self.limit, spec)
        if self.elem.is_basic:
            return (limit * self.elem.fixed_length(spec) + BYTES_PER_CHUNK - 1) // BYTES_PER_CHUNK
        return limit

    def hash_tree_root(self, value, spec=None, backend=None):
        spec = spec or get_chain_spec()
        self._check_limit(value, spec)
        if self.elem.is_basic:
            chunks = _pack_basics(self.elem, value, spec)
        else:
            chunks = _element_roots(self.elem, value, spec, backend)
        root = merkleize_chunks(chunks, self.chunk_limit(spec), backend)
        return mix_in_length(root, len(value))

    def default(self, spec=None):
        return []

    def __repr__(self):
        return f"List[{self.elem!r},{self.limit}]"


class Bitvector(SSZType):
    def __init__(self, length: int | str):
        self.length = length

    def is_fixed_size(self, spec):
        return True

    def fixed_length(self, spec):
        return (_resolve(self.length, spec) + 7) // 8

    def _coerce(self, value, n) -> BitvectorValue:
        try:
            if isinstance(value, (bytes, bytearray)):
                value = BitvectorValue(n, value)
            elif not isinstance(value, BitvectorValue):
                value = BitvectorValue.from_bools(value)
        except ValueError as e:
            raise SSZError(f"Bitvector[{n}]: {e}") from None
        if len(value) != n:
            raise SSZError(f"Bitvector[{n}]: got {len(value)} bits")
        return value

    def serialize(self, value, spec=None):
        spec = spec or get_chain_spec()
        n = _resolve(self.length, spec)
        return self._coerce(value, n).to_bytes()

    def deserialize(self, data, spec=None):
        spec = spec or get_chain_spec()
        n = _resolve(self.length, spec)
        if len(data) != (n + 7) // 8:
            raise SSZError(f"Bitvector[{n}]: wrong byte length {len(data)}")
        try:
            return BitvectorValue(n, data)
        except ValueError as e:
            raise SSZError(f"Bitvector[{n}]: {e}") from None

    def hash_tree_root(self, value, spec=None, backend=None):
        spec = spec or get_chain_spec()
        n = _resolve(self.length, spec)
        limit_chunks = (n + 255) // 256
        return merkleize_chunks(pack_bytes(self.serialize(value, spec)), limit_chunks, backend)

    def default(self, spec=None):
        spec = spec or get_chain_spec()
        return BitvectorValue(_resolve(self.length, spec))

    def __repr__(self):
        return f"Bitvector[{self.length}]"


class Bitlist(SSZType):
    def __init__(self, limit: int | str | Callable):
        self.limit = limit

    def is_fixed_size(self, spec):
        return False

    def _coerce(self, value) -> BitlistValue:
        if isinstance(value, BitlistValue):
            return value
        return BitlistValue.from_bools(value)

    def serialize(self, value, spec=None):
        spec = spec or get_chain_spec()
        bits = self._coerce(value)
        if len(bits) > _resolve(self.limit, spec):
            raise SSZError(f"Bitlist over limit {self.limit}")
        # sentinel bit marks the length
        as_int = int.from_bytes(bits.to_bytes(), "little") | (1 << len(bits))
        return as_int.to_bytes(len(bits) // 8 + 1, "little")

    def deserialize(self, data, spec=None):
        spec = spec or get_chain_spec()
        if not data:
            raise SSZError("empty bitlist encoding")
        as_int = int.from_bytes(data, "little")
        if as_int == 0:
            raise SSZError("bitlist missing sentinel bit")
        n = as_int.bit_length() - 1
        if n > _resolve(self.limit, spec):
            raise SSZError(f"Bitlist over limit {self.limit}")
        if len(data) != n // 8 + 1:
            raise SSZError("bitlist has trailing zero bytes")
        payload = as_int ^ (1 << n)
        try:
            return BitlistValue(n, payload.to_bytes((n + 7) // 8, "little"))
        except ValueError as e:
            raise SSZError(f"Bitlist: {e}") from None

    def hash_tree_root(self, value, spec=None, backend=None):
        spec = spec or get_chain_spec()
        bits = self._coerce(value)
        if len(bits) > _resolve(self.limit, spec):
            raise SSZError(f"Bitlist over limit {self.limit}")
        limit_chunks = (_resolve(self.limit, spec) + 255) // 256
        chunks = pack_bytes(bits.to_bytes()) if len(bits) else np.zeros((0, 32), np.uint8)
        return mix_in_length(merkleize_chunks(chunks, limit_chunks, backend), len(bits))

    def default(self, spec=None):
        return BitlistValue(0)

    def __repr__(self):
        return f"Bitlist[{self.limit}]"


def _assemble(cls, value, spec: ChainSpec, field_bytes=None) -> bytes:
    """A container's SSZ from its fields' serializations: fixed parts and
    offsets first, variable parts after.  Each field's bytes come from its
    type's own ``serialize`` or, when given, from ``field_bytes(fname,
    type, value)`` (any flat byte buffer): the encoded image
    (ssz/encoded.py) hands back views of arrays it keeps — one join
    copies them out, so the result never aliases a live buffer."""
    head: list = []
    variable: list = []
    for fname, ftype in cls.__ssz_schema__.items():
        t = _typ(ftype)
        v = getattr(value, fname)
        part = t.serialize(v, spec) if field_bytes is None else field_bytes(fname, t, v)
        if t.is_fixed_size(spec):
            head.append(part)
        else:
            head.append(None)
            variable.append(part)
    offset = sum(OFFSET_SIZE if p is None else len(p) for p in head)
    sizes = iter(map(len, variable))
    for i, p in enumerate(head):
        if p is None:
            head[i] = offset.to_bytes(OFFSET_SIZE, "little")
            offset += next(sizes)
    return b"".join(head + variable)


def _container_cls(t) -> "type[Container] | None":
    """The Container class a schema entry stands for (the class itself or
    its adapter), else None."""
    if isinstance(t, _ContainerAdapter):
        return t.cls
    return t if isinstance(t, type) and issubclass(t, Container) else None


def _late_sizes(t) -> tuple:
    """Every size under ``t`` that is resolved against a ChainSpec (a
    constant's name or a callable), in schema order."""
    cls = _container_cls(t)
    if cls is not None:
        return cls.__ssz_late_sizes__
    sizes = [getattr(t, a) for a in ("length", "limit") if hasattr(t, a)]
    late = tuple(n for n in sizes if not isinstance(n, int))
    return late + (_late_sizes(t.elem) if hasattr(t, "elem") else ())


def _plan_of(t, spec: ChainSpec) -> "_DecodePlan | None":
    """The decode plan of a schema entry that is a container, else None."""
    cls = _container_cls(t)
    return None if cls is None else cls._decode_plan(spec)


def _boolean_of(byte: int) -> bool:
    # struct's "?" takes any non-zero byte for true: SSZ takes 0 and 1
    if byte > 1:
        raise SSZError(f"invalid boolean encoding: {byte:#04x}")
    return byte == 1


_STRUCT_CODES = {1: "B", 2: "H", 4: "I", 8: "Q"}
_PLAN_LOCK = threading.RLock()  # re-entrant: a plan builds its fields' plans
_new = object.__new__


class _DecodePlan:
    """What decoding one container class under one spec needs, derived
    from the schema once (``Container._decode_plan``) and not per message.

    The fixed part is ONE ``struct.Struct``: a ``Uint`` <= 64, ``Boolean``
    or ``ByteVector`` field is one struct item, a nested fixed-size
    container is its own items in line (``AttestationData`` with its two
    ``Checkpoint``s: ``QQ32sQ32sQ32s``), any other fixed-size field
    (``uint128/256``, ``Vector``, ``Bitvector``) is an ``Ns`` item — the
    slice its own type's ``deserialize`` is then given (``convs``) — and a
    variable-size field is its ``I`` offset, replaced after the offset
    checks by what its decoder makes of its span (``var``).  The instances
    are then built from the flat item sequence in schema order, without
    ``__init__`` (as ``Container.copy``).

    ``kind``: ``flat`` — every item is a struct leaf, the container is one
    struct; ``fields`` — none is (offsets and slices only); else ``mixed``.
    """

    __slots__ = (
        "cls", "kind", "fixed_len", "codes", "convs", "var", "leaves",
        "names", "subs", "unpack_from", "iter_unpack",
    )

    def __init__(self, cls, spec: ChainSpec):
        self.cls = cls
        self.codes: list[str] = []  # struct item per slot
        self.convs: list[tuple[int, Callable]] = []  # (slot, bytes -> value)
        self.var: list[tuple[int, Callable]] = []  # (offset's slot, decoder)
        self.leaves = 0
        self.names = tuple(cls.__ssz_schema__)
        subs = []  # per field: a nested fixed container's build, else None
        for ftype in cls.__ssz_schema__.values():
            t = _typ(ftype)
            sub = _plan_of(t, spec)
            at = len(self.codes)
            if not t.is_fixed_size(spec):
                self.codes.append("I")
                decode = sub.decode if sub is not None else partial(t.deserialize, spec=spec)
                self.var.append((at, decode))
                subs.append(None)
            elif sub is not None:
                self.codes += sub.codes
                self.convs += [(at + i, conv) for i, conv in sub.convs]
                self.leaves += sub.leaves
                subs.append(sub.build)
            else:
                size = t.fixed_length(spec)
                if isinstance(t, Uint) and size in _STRUCT_CODES:
                    self.codes.append(_STRUCT_CODES[size])
                    self.leaves += 1
                elif isinstance(t, Boolean):
                    self.codes.append("B")
                    self.convs.append((at, _boolean_of))
                    self.leaves += 1
                else:
                    self.codes.append(f"{size}s")
                    if isinstance(t, ByteVector):
                        self.leaves += 1
                    else:
                        self.convs.append((at, partial(t.deserialize, spec=spec)))
                subs.append(None)
        self.subs = tuple(subs) if any(subs) else None
        fixed = struct.Struct("<" + "".join(self.codes))
        self.fixed_len = fixed.size
        self.unpack_from = fixed.unpack_from
        self.iter_unpack = fixed.iter_unpack
        if not self.leaves:
            self.kind = "fields"
        else:
            self.kind = "flat" if self.leaves == len(self.codes) else "mixed"
        _METRICS.inc("ssz_decode_plans_total", type=cls.__name__, kind=self.kind)

    def decode(self, data: bytes):
        n = len(data)
        if n < self.fixed_len:
            raise SSZError(f"{self.cls.__name__}: truncated ({n} < {self.fixed_len})")
        if not self.var and n != self.fixed_len:
            raise SSZError(f"{self.cls.__name__}: {n - self.fixed_len} trailing bytes")
        return self._finish(self.unpack_from(data), data)

    def decode_rows(self, data: bytes) -> list:
        """Fixed-size elements back to back (a multiple of ``fixed_len``)."""
        finish = self._finish
        return [finish(row, None) for row in self.iter_unpack(data)]

    def _finish(self, slots, data):
        if self.convs or self.var:
            slots = list(slots)
            for at, conv in self.convs:
                slots[at] = conv(slots[at])
            if self.var:
                starts = [slots[at] for at, _ in self.var]
                if starts[0] != self.fixed_len:
                    raise SSZError(
                        f"{self.cls.__name__}: first offset {starts[0]} != fixed size {self.fixed_len}"
                    )
                starts.append(len(data))
                if starts != sorted(starts):  # each span ends where the next starts
                    raise SSZError(f"{self.cls.__name__}: invalid offsets")
                for (at, decode), a, b in zip(self.var, starts, starts[1:]):
                    slots[at] = decode(data[a:b])
        return self.build(iter(slots))

    def build(self, slots):
        """One instance from the iterator of slot values, which it leaves
        just past its own."""
        obj = _new(self.cls)
        if self.subs is None:
            # zip stops at the names' end and takes no slot beyond
            obj.__dict__.update(zip(self.names, slots))
        else:
            fields = obj.__dict__
            for name, sub in zip(self.names, self.subs):
                fields[name] = next(slots) if sub is None else sub(slots)
        return obj


class ContainerMeta(type):
    """Collects SSZ field descriptors from class annotations into a schema."""

    def __new__(mcls, name, bases, ns):
        cls = super().__new__(mcls, name, bases, ns)
        schema: dict[str, SSZType] = {}
        for base in reversed(cls.__mro__[1:]):
            schema.update(getattr(base, "__ssz_schema__", {}))
        for fname, ftype in ns.get("__annotations__", {}).items():
            if isinstance(ftype, SSZType) or (isinstance(ftype, type) and issubclass(ftype, Container)):
                schema[fname] = ftype
            elif not fname.startswith("_"):
                # A dropped field would silently change the wire layout and
                # every Merkle root — fail at class definition instead.
                raise TypeError(
                    f"{name}.{fname}: annotation {ftype!r} is not an SSZ type "
                    "(string annotations — e.g. from `from __future__ import "
                    "annotations` — are not supported in container modules)"
                )
        cls.__ssz_schema__ = schema
        # the decode plans' key and cache (Container._decode_plan), per class
        cls.__ssz_late_sizes__ = tuple(
            dict.fromkeys(n for t in schema.values() for n in _late_sizes(t))
        )
        cls.__ssz_decode_plans__ = {}
        return cls


class Container(SSZType, metaclass=ContainerMeta):
    """SSZ container: subclass and declare fields as annotations.

    The class doubles as the type descriptor and the value type — methods on
    instances (``.hash_tree_root()``, ``.encode()``) call the classmethod codec
    with ``self``, giving the ergonomic surface of the reference's
    ``Ssz.to_ssz/1`` / ``Ssz.hash_tree_root/1`` (ref: lib/ssz.ex:8-90).
    """

    __ssz_schema__: dict[str, SSZType] = {}

    def __init__(self, **kwargs):
        schema = type(self).__ssz_schema__
        unknown = set(kwargs) - set(schema)
        if unknown:
            raise TypeError(f"{type(self).__name__}: unknown fields {sorted(unknown)}")
        for fname, ftype in schema.items():
            if fname in kwargs:
                object.__setattr__(self, fname, kwargs[fname])
            else:
                object.__setattr__(self, fname, _typ(ftype).default())

    # Containers are compared/updated functionally (immutable-ish).
    def __setattr__(self, k, v):
        raise AttributeError(
            f"{type(self).__name__} is immutable; use .copy({k}=...) instead"
        )

    def copy(self, **updates) -> "Container":
        fields = {f: getattr(self, f) for f in type(self).__ssz_schema__}
        fields.update(updates)
        out = object.__new__(type(self))
        for k, v in fields.items():
            object.__setattr__(out, k, v)
        return out

    def __eq__(self, other):
        if type(self) is not type(other):
            return NotImplemented
        return all(
            getattr(self, f) == getattr(other, f) for f in type(self).__ssz_schema__
        )

    def __hash__(self):
        # Cached per (spec, instance): containers are immutable by contract
        # (in-place mutation of nested lists is unsupported; use .copy()).
        spec = get_chain_spec()
        cache = self.__dict__.setdefault("_root_cache", {})
        root = cache.get(spec.name)
        if root is None:
            root = cache[spec.name] = self.hash_tree_root(spec)
        return hash(root)

    def __repr__(self):
        inner = ", ".join(f"{f}={getattr(self, f)!r}" for f in type(self).__ssz_schema__)
        return f"{type(self).__name__}({inner})"

    # -- SSZType protocol (operating on instances of this class)
    @classmethod
    def fields(cls) -> dict[str, SSZType]:
        return dict(cls.__ssz_schema__)

    @classmethod
    def is_fixed_size(cls, spec=None):
        spec = spec or get_chain_spec()
        return all(_typ(t).is_fixed_size(spec) for t in cls.__ssz_schema__.values())

    @classmethod
    def fixed_length(cls, spec=None):
        spec = spec or get_chain_spec()
        return sum(_typ(t).fixed_length(spec) for t in cls.__ssz_schema__.values())

    @classmethod
    def serialize(cls, value, spec=None):
        spec = spec or get_chain_spec()
        return _assemble(cls, value, spec)

    @classmethod
    def deserialize(cls, data, spec=None):
        if spec is None:  # not ``or``: a Mapping's truth is a Python call
            spec = get_chain_spec()
        return cls._decode_plan(spec).decode(bytes(data))

    @classmethod
    def _decode_plan(cls, spec) -> "_DecodePlan":
        """The decode plan of this class under ``spec``, compiled on first
        use.  Keyed on the spec's name AND on every late-bound size of the
        schema resolved against it: two specs share a plan only when both
        agree (``ChainSpec.replace`` keeps the name)."""
        key = (spec.name, *[_resolve(n, spec) for n in cls.__ssz_late_sizes__])
        plan = cls.__ssz_decode_plans__.get(key)
        if plan is None:
            with _PLAN_LOCK:  # a plan is built, and counted, once
                plan = cls.__ssz_decode_plans__.get(key)
                if plan is None:
                    plan = cls.__ssz_decode_plans__[key] = _DecodePlan(cls, spec)
        return plan

    @classmethod
    def decode_plan_kind(cls, spec=None) -> str:
        """``flat`` / ``mixed`` / ``fields``: how ``decode`` reads this type
        (see :class:`_DecodePlan`); builds the plan if there is none yet."""
        return cls._decode_plan(spec or get_chain_spec()).kind

    @classmethod
    def _hash_tree_root_of(cls, value, spec=None, backend=None):
        spec = spec or get_chain_spec()
        roots = np.empty((len(cls.__ssz_schema__), 32), np.uint8)
        for i, (fname, ftype) in enumerate(cls.__ssz_schema__.items()):
            r = _typ(ftype).hash_tree_root(getattr(value, fname), spec, backend)
            roots[i] = np.frombuffer(r, np.uint8)
        return merkleize_chunks(roots, backend=backend)

    @classmethod
    def default(cls, spec=None):
        return cls()

    # -- instance ergonomics
    def encode(self, spec=None) -> bytes:
        return type(self).serialize(self, spec)

    @classmethod
    def decode(cls, data: bytes, spec=None):
        return cls.deserialize(data, spec)

    def hash_tree_root(self, spec=None, backend=None) -> bytes:  # type: ignore[override]
        # only the OUTERMOST root is spanned: nested fields recurse via
        # _ContainerAdapter._hash_tree_root_of, so one state/block root is
        # one histogram sample, not thousands of sub-tree samples.  The
        # explicit enabled guard keeps the no-op cost of this per-item
        # hot path to one attribute check, and the per-class BoundSpan
        # cache keeps the enabled cost to two clock reads + one histogram
        # insert (bench_telemetry_overhead.py holds both under budget)
        cls = type(self)
        m = _METRICS
        if not m._enabled:
            return cls._hash_tree_root_of(self, spec, backend)
        bound = _ROOT_SPANS.get(cls)
        if bound is None:
            bound = _ROOT_SPANS[cls] = m.bound_span(
                "ssz_hash_tree_root", type=cls.__name__
            )
        with bound.time():
            return cls._hash_tree_root_of(self, spec, backend)


class _ContainerAdapter(SSZType):
    """Wraps a Container class so it fits the descriptor protocol uniformly."""

    __slots__ = ("cls",)

    def __init__(self, cls):
        self.cls = cls

    def is_fixed_size(self, spec):
        return self.cls.is_fixed_size(spec)

    def fixed_length(self, spec):
        return self.cls.fixed_length(spec)

    def serialize(self, value, spec=None):
        return self.cls.serialize(value, spec)

    def deserialize(self, data, spec=None):
        return self.cls.deserialize(data, spec)

    def hash_tree_root(self, value, spec=None, backend=None):
        return self.cls._hash_tree_root_of(value, spec, backend)

    def default(self, spec=None):
        return self.cls()

    def __repr__(self):
        return self.cls.__name__


_adapters: dict[type, _ContainerAdapter] = {}


def _typ(t) -> SSZType:
    """Normalize a schema entry (descriptor instance or Container class)."""
    if isinstance(t, SSZType):
        return t
    if isinstance(t, type) and issubclass(t, Container):
        ad = _adapters.get(t)
        if ad is None:
            ad = _adapters[t] = _ContainerAdapter(t)
        return ad
    raise TypeError(f"not an SSZ type: {t!r}")
