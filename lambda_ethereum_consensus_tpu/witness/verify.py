"""Batched multiproof verification: B proofs as one SHA-256 plane.

A multiproof verifies as a short sequence of Merkle levels — sequential
in depth, embarrassingly parallel across proofs and across the ops
inside one level.  The batched plane exploits exactly that: all B
proofs' node values live in one batch-major ``(B, S, 32)`` buffer
(S slots per proof), and each round gathers the round's
``(left, right)`` pairs across the WHOLE batch, hashes them as one
level, and scatters the digests back.  The per-proof op schedules are
*data* (int32 index arrays from :func:`..multiproof.plan_rounds`), so
one compiled program serves any mix of index sets inside a shape
bucket.

Three execution paths, all running the SAME plan (bit-exact by
construction; tests pin verdict equality on valid and corrupted proofs):

- **device plane** (``_verify_plane_device``): a jitted kernel — word
  buffer resident, rounds under ``lax.fori_loop``, each round one
  :func:`~lambda_ethereum_consensus_tpu.ops.sha256.hash_blocks_jnp`
  batch — behind the AOT executable cache (``aot_jit``).  Default on a
  TPU backend; on a multi-device mesh the same round body runs
  mesh-sharded over ``dp`` (the batch axis is the plane's only
  data-parallel axis, so the shards need no collective at all —
  ``WITNESS_SHARD``/``WITNESS_NO_SHARD``, crypto-plane polarity).
- **host plane** (``_verify_plane_host``): the CPU fallback — the same
  padded index arrays driven through numpy gathers + ``hashlib_level``
  (OpenSSL SHA-NI, ~5x the XLA-CPU hash rate).  Default elsewhere.
- **host oracle** (:func:`..multiproof.verify_host`): per-proof
  sequential execution, used below ``WITNESS_DEVICE_MIN`` proofs and as
  the reference in tests.

Shape discipline: batch size snaps to the ``witness_verify`` buckets
registered with :func:`ops.aot.register_shape_bucket` (warmed by
``node/warmup.py``); slots / rounds / ops-per-round snap to pow2 or
multiple-of-8 tiers, so the closed signature set stays tiny and a live
request can never trace a fresh program mid-serve.
"""

from __future__ import annotations

import time

import numpy as np

from ..ops.aot import register_shape_bucket, shape_buckets
from ..ssz.hash import hashlib_level
from ..telemetry import inc, span
from ..utils.env import env_flag
from .multiproof import (
    ProofPlan,
    WitnessError,
    WitnessProof,
    plan_for,
    verify_host,
    witness_fields,
)

__all__ = [
    "DEFAULT_BATCH_BUCKETS",
    "verify_batch",
    "warm_witness_programs",
]

#: Registered on first plane use (and by the node warmer): flush-sized
#: light-client batches snap up to one of these proof counts.
DEFAULT_BATCH_BUCKETS = (64, 256)

_KERNEL = None  # lazily built aot_jit-wrapped verifier
_SHARDED_KERNELS: dict = {}  # mesh-device key -> aot_jit-wrapped program


def _device_min() -> int:
    import os

    try:
        return int(os.environ.get("WITNESS_DEVICE_MIN", "8"))
    except ValueError:
        return 8


def _use_device_plane() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _shard_enabled() -> bool:
    """Route the device plane through the mesh-sharded program?  Same
    polarity discipline as the crypto/Merkle planes: ``WITNESS_NO_SHARD``
    wins, ``WITNESS_SHARD=1`` forces (the virtual CPU mesh in tests),
    default on only for a live multi-device TPU backend."""
    if env_flag("WITNESS_NO_SHARD"):
        return False
    if env_flag("WITNESS_SHARD"):
        return True
    from ..ops.mesh import _multi_device_tpu

    return _multi_device_tpu()


def _verify_rounds_body(nodes, lidx, ridx, oidx, root_idx, expected):
    """The pure round-runner: batch-major, per-proof-local slot indices —
    the SAME body serves the single-device jit and each mesh shard.

    ``nodes``: (B, S, 8) uint32; ``lidx``/``ridx``/``oidx``: (D, B, W)
    int32 LOCAL slots; ``root_idx``: (B,); ``expected``: (B, 8)."""
    import jax
    import jax.numpy as jnp

    from ..ops.sha256 import hash_blocks_jnp

    bidx = jnp.arange(nodes.shape[0])[:, None]

    def body(d, nd):
        left = jnp.take_along_axis(nd, lidx[d][..., None], axis=1)
        right = jnp.take_along_axis(nd, ridx[d][..., None], axis=1)
        dig = hash_blocks_jnp(jnp.concatenate([left, right], axis=-1))
        return nd.at[bidx, oidx[d]].set(dig)

    nd = jax.lax.fori_loop(0, lidx.shape[0], body, nodes)
    got = jnp.take_along_axis(nd, root_idx[:, None, None], axis=1)[:, 0]
    return jnp.all(got == expected, axis=-1)


def _get_kernel():
    """Build (once) the single-device jitted plane behind the AOT cache."""
    global _KERNEL
    if _KERNEL is not None:
        return _KERNEL
    import jax

    from ..ops.aot import aot_jit

    _KERNEL = aot_jit(jax.jit(_verify_rounds_body), "witness_verify")
    return _KERNEL


def _get_sharded_kernel(mesh):
    """The mesh-sharded plane: proofs dealt across ``dp`` (the batch axis
    is the only data-parallel axis, exactly like the sharded Merkle
    tree's leaf-block axis), each device running the identical round
    body on its shard — no collective at all until the (B,)-sharded
    verdict vector is read back."""
    key = tuple(d.id for d in mesh.devices.flat)
    fn = _SHARDED_KERNELS.get(key)
    if fn is not None:
        return fn
    import jax
    from jax.sharding import PartitionSpec as P

    from ..ops.aot import aot_jit

    sharded = jax.shard_map(
        _verify_rounds_body,
        mesh=mesh,
        in_specs=(
            P("dp", None, None),  # nodes (B, S, 8)
            P(None, "dp", None),  # lidx (D, B, W)
            P(None, "dp", None),  # ridx
            P(None, "dp", None),  # oidx
            P("dp"),              # root_idx (B,)
            P("dp", None),        # expected (B, 8)
        ),
        out_specs=P("dp"),
        check_vma=False,
    )
    fn = aot_jit(jax.jit(sharded), "witness_verify_sharded")
    _SHARDED_KERNELS[key] = fn
    return fn


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _snap_batch(n: int) -> int:
    buckets = shape_buckets("witness_verify")
    if not buckets:
        for b in DEFAULT_BATCH_BUCKETS:
            register_shape_bucket("witness_verify", b)
        buckets = shape_buckets("witness_verify")
    for b in buckets:
        if n <= b:
            return b
    return _pow2(n)


def verify_batch(proofs, expected_roots, device: bool | None = None) -> list:
    """Verify B independent multiproofs; returns one bool per proof.

    ``expected_roots`` is a single 32-byte root (broadcast) or one per
    proof.  Proofs whose SHAPE is malformed (empty/duplicated/truncated
    index sets — anything :func:`..multiproof.plan_for` rejects) are
    verdict ``False`` without touching any plane; value corruption is
    caught by the root comparison inside the plane.  ``device`` forces
    the jitted plane on (True) or off (False); ``None`` routes TPU
    backends through it and everything else through the vectorized host
    plane (``WITNESS_NO_DEVICE=1`` also forces host) — all bit-exact."""
    n = len(proofs)
    if n == 0:
        return []
    if isinstance(expected_roots, (bytes, bytearray)):
        expected_roots = [bytes(expected_roots)] * n
    if len(expected_roots) != n:
        raise WitnessError(f"{len(expected_roots)} roots for {n} proofs")
    verdicts: list[bool | None] = [None] * n
    plans: list[ProofPlan | None] = [None] * n
    for i, proof in enumerate(proofs):
        if not isinstance(proof, WitnessProof):
            verdicts[i] = False
            continue
        try:
            plans[i] = plan_for(proof)
        except WitnessError:
            verdicts[i] = False
    live = [i for i in range(n) if verdicts[i] is None]
    if device is None:
        device = (
            len(live) >= _device_min()
            and not env_flag("WITNESS_NO_DEVICE")
            and _use_device_plane()
        )

    # the device plane only ever dispatches REGISTERED batch shapes: a
    # request past the largest warmed bucket is split into largest-bucket
    # chunks instead of snapping to an unregistered pow2 (which would
    # trace a fresh program mid-serve — the exact failure the bucket
    # discipline exists to prevent); the host plane has no signature set
    # and takes the whole batch at once
    max_bucket = max(shape_buckets("witness_verify") or DEFAULT_BATCH_BUCKETS)
    # padded-plane footprint guard: the batch pads every proof to the
    # LARGEST plan's pow2 slot count, so one adversarially wide proof
    # (thousands of leaves) would multiply across the whole bucket —
    # past ~2M slots (64 MB of nodes) the per-proof oracle is both
    # smaller and faster, and verdict-identical by construction
    plane_ok = live and (
        _snap_batch(min(len(live), max_bucket))
        * _pow2(max(plans[i].n_slots for i in live))
        <= (1 << 21)
    )

    with span("witness_verify"):
        if not live:
            pass
        elif not plane_ok or (len(live) < _device_min() and not device):
            for i in live:
                verdicts[i] = verify_host(proofs[i], expected_roots[i])
        elif device:
            for at in range(0, len(live), max_bucket):
                chunk = live[at : at + max_bucket]
                results = _verify_plane_device(_assemble(
                    [proofs[i] for i in chunk],
                    [expected_roots[i] for i in chunk],
                    [plans[i] for i in chunk],
                ))
                for i, ok in zip(chunk, results):
                    verdicts[i] = bool(ok)
        else:
            results = _verify_plane_host(_assemble(
                [proofs[i] for i in live],
                [expected_roots[i] for i in live],
                [plans[i] for i in live],
            ))
            for i, ok in zip(live, results):
                verdicts[i] = bool(ok)
    ok_count = sum(1 for v in verdicts if v)
    if ok_count:
        inc("witness_verified_total", ok_count, result="ok")
    if n - ok_count:
        inc("witness_verified_total", n - ok_count, result="invalid")
    return [bool(v) for v in verdicts]


# ------------------------------------------------------------ assembly

# per-plan index templates: (lidx, ridx, oidx, mask) as (D_p, W_p) int32 /
# bool arrays in LOCAL slot numbers (scratch = 0), so batch assembly is a
# vectorized slice-assign per proof instead of a per-op Python loop
_TPL_CACHE: dict[tuple, tuple] = {}


def _plan_template(plan: ProofPlan) -> tuple:
    tpl = _TPL_CACHE.get(plan.leaf_gindices)
    if tpl is not None:
        return tpl
    d_p = len(plan.rounds)
    w_p = plan.max_round_ops
    lidx = np.zeros((d_p, w_p), np.int32)
    ridx = np.zeros((d_p, w_p), np.int32)
    oidx = np.zeros((d_p, w_p), np.int32)
    mask = np.zeros((d_p, w_p), bool)
    for d, ops in enumerate(plan.rounds):
        for w, (left, right, out) in enumerate(ops):
            lidx[d, w] = left
            ridx[d, w] = right
            oidx[d, w] = out
            mask[d, w] = True
    tpl = (lidx, ridx, oidx, mask)
    if len(_TPL_CACHE) > 256:
        _TPL_CACHE.clear()  # tiny arrays; plans repeat heavily in practice
    _TPL_CACHE[plan.leaf_gindices] = tpl
    return tpl


def _assemble(proofs, roots, plans) -> dict:
    """Pad B proofs to the witness_verify shape buckets: one batch-major
    (B, S, 32) node buffer + (D, B, W) local index arrays shared by the
    device and host planes."""
    n = len(proofs)
    batch = _snap_batch(n)
    # slots / rounds / per-round width snapped so the device signature
    # set stays closed: pow2 slots, multiple-of-8 rounds, pow2 width
    slots = _pow2(max(max(p.n_slots for p in plans), 32))
    rounds = max(8, -(-max(len(p.rounds) for p in plans) // 8) * 8)
    width = _pow2(max(max(p.max_round_ops for p in plans), 1))

    # all indices are LOCAL slots (scratch = 0): the device plane is
    # batch-major ((B, S, 8) nodes), so the same arrays serve the
    # single-device jit and every shard of the mesh-sharded program;
    # the host plane flattens with per-proof bases below
    nodes = np.zeros((batch, slots, 32), np.uint8)
    lidx = np.zeros((rounds, batch, width), np.int32)
    ridx = np.zeros((rounds, batch, width), np.int32)
    oidx = np.zeros((rounds, batch, width), np.int32)
    mask = np.zeros((rounds, batch, width), bool)
    root_idx = np.zeros((batch,), np.int32)
    expected = np.zeros((batch, 32), np.uint8)
    for b, (proof, root, plan) in enumerate(zip(proofs, roots, plans)):
        blob = b"".join(
            [bytes(c) for _g, c in proof.leaves]
            + [bytes(s) for s in proof.siblings]
        )
        vals = np.frombuffer(blob, np.uint8).reshape(-1, 32)
        nodes[b, 1 : 1 + vals.shape[0]] = vals
        tl, tr, to, tm = _plan_template(plan)
        d_p, w_p = tl.shape
        lidx[:d_p, b, :w_p] = tl
        ridx[:d_p, b, :w_p] = tr
        oidx[:d_p, b, :w_p] = to
        mask[:d_p, b, :w_p] = tm
        root_idx[b] = plan.root_slot
        expected[b] = np.frombuffer(bytes(root), np.uint8)
    return {
        "n": n,
        "slots": slots,
        "nodes": nodes,
        "lidx": lidx,
        "ridx": ridx,
        "oidx": oidx,
        "mask": mask,
        "root_idx": root_idx,
        "expected": expected,
    }


def _verify_plane_host(packed: dict) -> np.ndarray:
    """The CPU fallback plane: the shared plan arrays driven through
    numpy gathers + ``hashlib_level`` — each round hashes the whole
    batch's live ops as one level, no per-proof Python loop."""
    batch, slots = packed["nodes"].shape[:2]
    nodes = packed["nodes"].reshape(batch * slots, 32)
    rounds = packed["mask"].shape[0]
    bases = (np.arange(batch, dtype=np.int32) * slots)[None, :, None]
    flat = {
        k: (packed[k] + bases).reshape(rounds, -1)
        for k in ("lidx", "ridx", "oidx")
    }
    fmask = packed["mask"].reshape(rounds, -1)
    for d in range(rounds):
        m = fmask[d]
        if not m.any():
            continue
        left = flat["lidx"][d][m]
        right = flat["ridx"][d][m]
        blocks = np.concatenate([nodes[left], nodes[right]], axis=1)
        nodes[flat["oidx"][d][m]] = hashlib_level(blocks)
    got = nodes[packed["root_idx"] + bases[0, :, 0]]
    return (got == packed["expected"]).all(axis=1)[: packed["n"]]


def _verify_plane_device(packed: dict) -> np.ndarray:
    """The jitted plane: node words resident, rounds under fori_loop —
    dealt across the ``dp`` mesh when the sharded route is on and the
    bucket divides the device count (results bit-identical either way,
    like the sharded Merkle tree: the batch axis is purely data-parallel)."""
    import jax.numpy as jnp

    words = (
        np.ascontiguousarray(packed["nodes"]).view(">u4").astype(np.uint32)
    )
    expected = (
        np.ascontiguousarray(packed["expected"]).view(">u4").astype(np.uint32)
    )
    kernel = None
    if _shard_enabled():
        from ..ops.mesh import default_mesh

        mesh = default_mesh()
        if words.shape[0] % int(mesh.devices.size) == 0:
            kernel = _get_sharded_kernel(mesh)
    if kernel is None:
        kernel = _get_kernel()
    out = kernel(
        jnp.asarray(words),
        jnp.asarray(packed["lidx"]),
        jnp.asarray(packed["ridx"]),
        jnp.asarray(packed["oidx"]),
        jnp.asarray(packed["root_idx"]),
        jnp.asarray(expected),
    )
    return np.asarray(out)[: packed["n"]]


def warm_witness_programs(batch: int | None = None) -> float:
    """Register the ``witness_verify`` buckets and compile/load the plane
    at the canonical single-index serving shape — the node warmer calls
    this so the first real light-client batch finds the program resident.
    Values are garbage; program identity is keyed by shape, which is all
    warming needs.

    Deliberately drives the plane INTERNALS, not :func:`verify_batch`:
    the serving wrapper records ``witness_verify_seconds`` and
    ``witness_verified_total``, and a planned warmup compile landing in
    that histogram would read as a phantom ``witness_verify_p95``
    violation on every boot (same discipline as
    ``warm_transition_programs``).  Only the plane the serving path will
    actually dispatch is compiled: the jitted (possibly mesh-sharded)
    program on a device backend, the template-only host plane elsewhere."""
    from ..ops.aot import compile_context

    t0 = time.perf_counter()
    for b in DEFAULT_BATCH_BUCKETS:
        register_shape_bucket("witness_verify", b)
    b = int(batch) if batch else DEFAULT_BATCH_BUCKETS[0]
    proof = _dummy_proof()
    plan = plan_for(proof)
    packed = _assemble([proof] * b, [b"\x00" * 32] * b, [plan] * b)
    with compile_context("warmup:witness"):
        if _use_device_plane():
            _verify_plane_device(packed)
        else:
            _verify_plane_host(packed)
    return time.perf_counter() - t0


def _dummy_proof() -> WitnessProof:
    """A shape-correct single-index proof (balances[0]) with zero values:
    enough to key the canonical program identity without any state."""
    from ..types.beacon import BeaconState
    from .multiproof import _top_depth, helper_gindices, leaf_gindex

    meta = witness_fields()["balances"]
    g = leaf_gindex(meta, 0, _top_depth(BeaconState))
    helpers = helper_gindices([g])
    zero = b"\x00" * 32
    return WitnessProof(
        state_root=zero,
        indices=(("balances", 0),),
        leaves=((g, zero),),
        siblings=tuple(zero for _ in helpers),
    )
