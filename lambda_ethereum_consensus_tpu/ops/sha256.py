"""Batched SHA-256 for SSZ Merkleization on TPU (JAX + Pallas).

The Merkleization hot path (ref: ``Ssz.hash_tree_root`` → Rust ``tree_hash``,
native/ssz_nif/src/lib.rs:26-153) reduces to one primitive: hash N independent
64-byte nodes, ``(N, 64) → (N, 32)``.  Every node is a single 64-byte message,
so SHA-256 is exactly **two** compression calls — one over the data block and
one over a *constant* padding block whose message schedule is precomputed
host-side and folded into the kernel as 64 scalar constants.

Layouts:

- **word-plane**: a batch of blocks is 16 ``uint32`` planes, each plane shaped
  ``(rows, 128)`` so a plane tile is exactly the TPU VPU's native ``(8, 128)``
  vector registers.  All round arithmetic is elementwise ``uint32`` adds,
  rotates and boolean ops over planes — there is no cross-lane traffic at all,
  which is why SHA-256 batches perfectly onto the VPU.
- the pure ``jax.numpy`` path uses the same plane functions on ``(N,)``
  vectors and runs on any backend (CPU correctness oracle, and XLA already
  fuses the whole 128-round chain into a couple of kernels).

Entry points:

- :func:`hash_blocks` — batched node hash, auto device/host.
- :func:`merkle_root_device` — a full (sub)tree reduced on-device: level
  ``k+1``'s words are level ``k``'s digests re-paired by a stride-2 gather,
  so the whole tree is one fused XLA computation with zero host round-trips.
- :class:`DeviceHashBackend` — plugs into the SSZ engine's
  :class:`~lambda_ethereum_consensus_tpu.ssz.hash.HashBackend` protocol.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..ssz.hash import hashlib_level

__all__ = [
    "hash_blocks",
    "hash_blocks_jnp",
    "hash_blocks_pallas",
    "merkle_root_device",
    "merkle_root_words_sharded",
    "DeviceHashBackend",
    "install_device_backend",
]

# fmt: off
_K = [
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
    0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
    0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
    0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
    0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
    0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
]
_IV = [
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
]
# fmt: on

_MASK = 0xFFFFFFFF


def _py_rotr(x: int, n: int) -> int:
    return ((x >> n) | (x << (32 - n))) & _MASK


def _py_schedule(words: list[int]) -> list[int]:
    w = list(words)
    for t in range(16, 64):
        s0 = _py_rotr(w[t - 15], 7) ^ _py_rotr(w[t - 15], 18) ^ (w[t - 15] >> 3)
        s1 = _py_rotr(w[t - 2], 17) ^ _py_rotr(w[t - 2], 19) ^ (w[t - 2] >> 10)
        w.append((w[t - 16] + s0 + w[t - 7] + s1) & _MASK)
    return w


#: Message schedule of the constant second block of a 64-byte message:
#: 0x80 delimiter, zeros, and a 512-bit length field.  Folded to constants.
_PAD_SCHEDULE: list[int] = _py_schedule([0x80000000] + [0] * 14 + [512])


def _rotr(x, n: int):
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _schedule(w: list):
    """Extend 16 word planes to the full 64-entry schedule (unrolled)."""
    w = list(w)
    for t in range(16, 64):
        s0 = _rotr(w[t - 15], 7) ^ _rotr(w[t - 15], 18) ^ (w[t - 15] >> jnp.uint32(3))
        s1 = _rotr(w[t - 2], 17) ^ _rotr(w[t - 2], 19) ^ (w[t - 2] >> jnp.uint32(10))
        w.append(w[t - 16] + s0 + w[t - 7] + s1)
    return w


def _compress(state: list, schedule: list) -> list:
    """One SHA-256 compression over planes; ``schedule`` entries may be planes
    or scalar ``jnp.uint32`` (the constant padding block)."""
    a, b, c, d, e, f, g, h = state
    for t in range(64):
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + jnp.uint32(_K[t]) + schedule[t]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + t2
    return [x + y for x, y in zip(state, [a, b, c, d, e, f, g, h])]


def _digest_planes(word_planes: list) -> list:
    """SHA-256 of 64-byte messages given as 16 word planes → 8 digest planes."""
    shape = jnp.shape(word_planes[0])
    iv = [jnp.full(shape, jnp.uint32(v)) for v in _IV]
    mid = _compress(iv, _schedule(word_planes))
    return _compress(mid, [jnp.uint32(v) for v in _PAD_SCHEDULE])


# ---------------------------------------------------------------------------
# Pure-jnp path: (N, 16) uint32 → (N, 8) uint32
#
# Rolled into lax.fori_loop so the traced graph stays small — the unrolled
# 128-round chain compiles for minutes on CPU backends; the loop compiles in
# milliseconds and XLA still keeps the whole batch resident on device.
# ---------------------------------------------------------------------------


def _schedule_rolled(words: jax.Array) -> jax.Array:
    """``(N, 16)`` message words → full ``(N, 64)`` schedule."""
    w0 = jnp.zeros(words.shape[:-1] + (64,), jnp.uint32).at[..., :16].set(words)

    def body(t, w):
        w15 = w[..., t - 15]
        w2 = w[..., t - 2]
        s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> jnp.uint32(3))
        s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> jnp.uint32(10))
        return w.at[..., t].set(w[..., t - 16] + s0 + w[..., t - 7] + s1)

    return jax.lax.fori_loop(16, 64, body, w0)


_K_ARR = np.array(_K, dtype=np.uint32)
_PAD_SCHEDULE_ARR = np.array(_PAD_SCHEDULE, dtype=np.uint32)


def _compress_rolled(state: jax.Array, schedule: jax.Array) -> jax.Array:
    """``(N, 8)`` state × ``(N, 64)``-or-``(64,)`` schedule → ``(N, 8)``."""
    k = jnp.asarray(_K_ARR)

    def body(t, s):
        a, b, c, d, e, f, g, h = (s[..., i] for i in range(8))
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k[t] + schedule[..., t]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        return jnp.stack([t1 + s0 + maj, a, b, c, d + t1, e, f, g], axis=-1)

    return state + jax.lax.fori_loop(0, 64, body, state)


@jax.jit
def hash_blocks_jnp(blocks: jax.Array) -> jax.Array:
    """Hash ``(..., 16) uint32`` big-endian message words → ``(..., 8)``."""
    iv = jnp.broadcast_to(
        jnp.asarray(np.array(_IV, np.uint32)), blocks.shape[:-1] + (8,)
    )
    mid = _compress_rolled(iv, _schedule_rolled(blocks))
    return _compress_rolled(mid, jnp.asarray(_PAD_SCHEDULE_ARR))


# ---------------------------------------------------------------------------
# Pallas TPU kernel: word-major (16, R, 128) → (8, R, 128)
# ---------------------------------------------------------------------------

_SUBLANES = 8
_LANES = 128
_TILE_ROWS = _SUBLANES * _LANES  # blocks per grid step


def _sha256_kernel(in_ref, out_ref):
    words = [in_ref[i] for i in range(16)]
    digest = _digest_planes(words)
    for i in range(8):
        out_ref[i] = digest[i]


@functools.partial(jax.jit, static_argnames=("interpret",))
def hash_blocks_pallas(words: jax.Array, interpret: bool = False) -> jax.Array:
    """Pallas kernel over word-plane layout.

    ``words``: ``(16, R, 128) uint32`` with ``R % 8 == 0``; returns
    ``(8, R, 128)``.  Each grid step owns an ``(8, 128)`` tile of every plane
    — the VPU's native register shape — and runs the fully unrolled 128
    rounds in VMEM.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, rows, lanes = words.shape
    assert lanes == _LANES and rows % _SUBLANES == 0, words.shape
    grid = rows // _SUBLANES
    return pl.pallas_call(
        _sha256_kernel,
        out_shape=jax.ShapeDtypeStruct((8, rows, _LANES), jnp.uint32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((16, _SUBLANES, _LANES), lambda i: (0, i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((8, _SUBLANES, _LANES), lambda i: (0, i, 0), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(words)


# ---------------------------------------------------------------------------
# Host-side byte-layout marshalling
# ---------------------------------------------------------------------------


def _bucket_rows(n_blocks: int) -> int:
    """Pad the batch to a small set of sizes so jit caches stay warm."""
    rows = max(1, -(-n_blocks // _LANES))  # lanes-wide rows
    bucket = _SUBLANES
    while bucket < rows:
        bucket *= 2
    return bucket


def _to_word_planes(blocks: np.ndarray, rows: int) -> np.ndarray:
    """``(N, 64) uint8`` → ``(16, rows, 128) uint32`` (big-endian words)."""
    n = blocks.shape[0]
    words = np.ascontiguousarray(blocks).view(">u4").astype(np.uint32)  # (N, 16)
    out = np.zeros((rows * _LANES, 16), np.uint32)
    out[:n] = words
    return np.ascontiguousarray(out.T).reshape(16, rows, _LANES)


def _from_digest_planes(planes: np.ndarray, n: int) -> np.ndarray:
    """``(8, rows, 128) uint32`` → ``(N, 32) uint8``."""
    flat = planes.reshape(8, -1).T[:n]  # (N, 8) native-endian
    return np.ascontiguousarray(flat.astype(">u4")).view(np.uint8).reshape(n, 32)


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def hash_blocks(blocks: np.ndarray) -> np.ndarray:
    """Batched node hash ``(N, 64) uint8 → (N, 32) uint8`` on device."""
    n = blocks.shape[0]
    if _use_pallas():
        rows = _bucket_rows(n)
        planes = _to_word_planes(blocks, rows)
        digests = hash_blocks_pallas(planes)
        return _from_digest_planes(np.asarray(digests), n)
    words = np.ascontiguousarray(blocks).view(">u4").astype(np.uint32)  # (N, 16)
    npad = 1 << max(3, (n - 1).bit_length())  # pow2 buckets keep jit cache warm
    buf = np.zeros((npad, 16), np.uint32)
    buf[:n] = words
    digests = np.asarray(hash_blocks_jnp(buf))[:n]
    return np.ascontiguousarray(digests.astype(">u4")).view(np.uint8).reshape(n, 32)


# ---------------------------------------------------------------------------
# Full-tree device Merkleization
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("depth",))
def _merkle_tree_jnp(words: jax.Array, depth: int) -> jax.Array:
    """Reduce ``(M, 16) uint32`` leaf blocks (M = 2**depth) to the root digest
    ``(8,) uint32``, entirely on device.

    Children ``2j``/``2j+1`` are adjacent rows, so level ``k+1``'s words are
    just level ``k``'s ``(m, 8)`` digests reshaped to ``(m/2, 16)`` — the
    whole tree is one fused XLA computation with zero host round-trips.
    """
    level = words
    for _ in range(depth):
        level = hash_blocks_jnp(level).reshape(-1, 16)
    return hash_blocks_jnp(level)[0]


# ---- mesh-sharded subtree reduction (the round-11 sharded Merkle plane)
#
# The leaf-block batch axis is the tree's only data-parallel axis: shard
# it over ``dp``, let each device reduce its LOCAL subtree with zero
# communication, all_gather the per-device subtree roots (n_devices x 32
# bytes — the whole collective), and run the final log2(n_devices)
# levels replicated.  Bit-identical to the single-device reduction
# because a Merkle tree's value is independent of which chip hashed
# which subtree; the driver's dryrun asserts exactly that equality.

_SHARDED_TREES: dict = {}


def _sharded_tree_fn(mesh, depth_local: int, depth_global: int):
    """One compiled sharded-tree program per (mesh, shape) key."""
    key = (tuple(d.id for d in mesh.devices.flat), depth_local, depth_global)
    fn = _SHARDED_TREES.get(key)
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as P

    def shard_fn(local):  # (local_blocks, 16) per device
        level = local
        for _ in range(depth_local):
            level = hash_blocks_jnp(level).reshape(-1, 16)
        root = hash_blocks_jnp(level)  # (1, 8) local subtree root
        if depth_global == 0:
            return root
        roots = jax.lax.all_gather(root, "dp", axis=0, tiled=True)
        level = roots.reshape(-1, 16)
        for _ in range(depth_global - 1):
            level = hash_blocks_jnp(level).reshape(-1, 16)
        return hash_blocks_jnp(level)  # (1, 8) replicated

    fn = jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh, in_specs=P("dp", None), out_specs=P(),
            check_vma=False,
        )
    )
    _SHARDED_TREES[key] = fn
    return fn


def merkle_root_words_sharded(words, mesh=None) -> jax.Array:
    """``(M, 16) uint32`` leaf blocks -> ``(8,)`` root digest, reduced
    over the mesh.  M must be a power of two with at least one block per
    device.  Shared by :func:`merkle_root_device`'s multi-device route
    and the driver's ``dryrun_multichip`` step (one copy of the sharded
    tree program — the dryrun validates the code the node serves with).
    """
    from .mesh import default_mesh

    if mesh is None:
        mesh = default_mesh()
    d = int(mesh.devices.size)
    m = int(words.shape[0])
    assert d & (d - 1) == 0, "dp axis size must be a power of two"
    assert m % d == 0 and m // d >= 1, (m, d)
    depth_global = d.bit_length() - 1
    depth_local = (m // d).bit_length() - 1
    # placement through the round-21 partition-rule table: the chunk
    # rows are a legislated plane, not an ad-hoc device_put
    from . import shard_rules

    words = shard_rules.place("ssz/chunk_rows", jnp.asarray(words), mesh)
    return _sharded_tree_fn(mesh, depth_local, depth_global)(words)[0]


def _shard_tree_min_blocks() -> int:
    """Below this many leaf blocks the all_gather + replicated-tail
    bookkeeping beats the win from splitting the level-0 hashing; also
    keeps small-container SSZ tests off the sharded program (the
    conftest CPU mesh makes every test process "multi-device")."""
    import os

    return int(os.environ.get("SSZ_SHARD_MIN_BLOCKS", "8192"))


def _shard_tree_enabled(n_blocks: int) -> bool:
    from ..utils.env import env_flag

    if env_flag("SSZ_NO_SHARD"):
        return False
    from .mesh import _multi_device_tpu, device_count

    n = device_count()
    if n <= 1:
        return False
    if env_flag("SSZ_SHARD"):
        return True
    # default-on only for a multi-device TPU mesh: the conftest-forced
    # virtual CPU mesh must not silently reroute every big-tree test
    return _multi_device_tpu(n) and n_blocks >= _shard_tree_min_blocks()


def merkle_root_device(chunks: np.ndarray) -> tuple[bytes, int]:
    """Root of ``(N, 32) uint8`` chunks padded to the next power of two with
    zero chunks.  Returns ``(root, depth_of_padded_subtree)`` — the caller
    extends with precomputed zero-subtree hashes up to the SSZ limit depth.

    Registry-scale subtrees (the 1M-validator planes) route through the
    mesh-sharded reduction when more than one device is live
    (``SSZ_SHARD=1`` forces, ``SSZ_NO_SHARD=1`` falls back — results are
    bit-identical either way).
    """
    n = chunks.shape[0]
    pairs = max(1, -(-n // 2))
    m = 1 << (pairs - 1).bit_length()  # blocks at leaf level, power of two
    depth = m.bit_length() - 1
    buf = np.zeros((m, 64), np.uint8)
    flat = np.ascontiguousarray(chunks).reshape(-1)
    buf.reshape(-1)[: flat.shape[0]] = flat
    words = buf.view(">u4").astype(np.uint32)
    if _shard_tree_enabled(m):
        from .mesh import default_mesh

        mesh = default_mesh()
        if m >= mesh.devices.size:
            digest = np.asarray(merkle_root_words_sharded(words, mesh))
            return (
                np.ascontiguousarray(digest.astype(">u4"))
                .view(np.uint8)
                .tobytes(),
                depth + 1,
            )
    digest = np.asarray(_merkle_tree_jnp(words, depth))
    return np.ascontiguousarray(digest.astype(">u4")).view(np.uint8).tobytes(), depth + 1


# ---------------------------------------------------------------------------
# SSZ HashBackend integration
# ---------------------------------------------------------------------------


class DeviceHashBackend:
    """SSZ hash backend dispatching large batches to the device.

    Below ``threshold`` blocks the per-call dispatch overhead beats the
    hashlib loop, so small trees (most containers: ≤ 16 fields) stay on host;
    the validator registry, balances and participation lists go to TPU.
    """

    name = "jax-device"

    def __init__(self, threshold: int = 256, tree_threshold: int = 512):
        self.threshold = int(threshold)
        self.tree_threshold = int(tree_threshold)

    def hash_level(self, blocks: np.ndarray) -> np.ndarray:
        if blocks.shape[0] < self.threshold:
            return hashlib_level(blocks)
        return hash_blocks(blocks)

    def merkle_subtree_root(self, chunks: np.ndarray) -> tuple[bytes, int]:
        """Whole-subtree device reduction; see :func:`merkle_root_device`."""
        return merkle_root_device(chunks)


def install_device_backend(**kwargs) -> DeviceHashBackend:
    """Create a :class:`DeviceHashBackend` and make it the SSZ default."""
    from ..ssz.hash import set_hash_backend

    backend = DeviceHashBackend(**kwargs)
    set_hash_backend(backend)
    return backend
