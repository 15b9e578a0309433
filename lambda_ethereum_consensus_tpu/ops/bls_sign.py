"""Batched BLS signing plane (G2): the mirror image of the verify plane.

A signature is ``sk * hash_to_G2(message)`` — the verify plane's RLC
ladders run the same double-and-add over G2, so signing N messages for a
10^4-10^5-key operator is the exact workload shape the device already
serves, with the scalar now secret instead of random (arXiv:2302.00418
benchmarks precisely this signer-side cost).  Three execution paths, all
bit-exact against the host ``bls.sign`` oracle (affine coordinates are
unique, so equal group math means equal compressed bytes — for valid and
tampered-but-in-range keys alike):

- **device plane** (``_sign_points_device``): the plane-layout G2 ladder
  (:mod:`.ladder` over the fused Fq2 tower from :mod:`.bls_fq12`),
  AOT-cached behind ``aot_jit("duty_sign")`` with the batch snapped to
  the registered ``duty_sign`` shape buckets (warmed by
  ``node/warmup.start_warmer`` under ``compile_context("warmup:duties")``)
  — a live duty flush can never trace a fresh program mid-slot.  Batches
  past the largest bucket run in largest-bucket chunks, exactly like the
  witness plane.  Messages hash on host: one ``hash_to_g2`` per DISTINCT
  message, and every member of a committee shares its committee's point.
- **host comb** (``_sign_points_host``): shared-base fixed-window tables
  per distinct message point — the committee-duty shape means one table
  amortizes across every signer of that message (~4x the plain ladder on
  this CPU); small groups fall through to the plain ``multiply``.
- **host oracle**: per-item ``C.g2.multiply`` — what ``bls.sign`` runs,
  and what every guard below falls back to.

Every guard (key range/length, device routing, a raising dispatch)
precedes any output and degrades to the host path, so this plane can
never make a signature wrong — only a cold start slower.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Sequence

import numpy as np

from ..crypto.bls import curve as C
from ..crypto.bls.api import BlsError
from ..crypto.bls.fields import P, R
from ..crypto.bls.hash_to_curve import DST_POP, hash_to_g2_many
from ..telemetry import device_fault, inc, span
from ..utils.env import env_flag
from .aot import aot_jit, compile_context, register_shape_bucket, shape_buckets
from .bls_g1 import SCALAR_BITS, _ints_batch, _scalar_bits_batch, batch_inv_mod
from .bls_g2 import fq2_limbs_batch, g2_plane_field
from .profile import register_entry_plane

# round-18 HBM accounting: the duty-sign ladders' compiled programs (the
# retained device footprint of this plane — bases and scalars are
# per-dispatch transients) report as their own plane instead of folding
# into the shared aot_executables plane (both are non-live planes:
# program bytes never appear in the jax.live_arrays() total)
register_entry_plane("duty_sign_ladders", "duty_sign")

__all__ = [
    "DEFAULT_SIGN_BUCKETS",
    "sign_batch",
    "warm_sign_programs",
]

log = logging.getLogger("bls_sign")

#: Registered on first plane use (and by the node warmer): duty flushes
#: snap up to one of these signature counts before the ladder dispatch.
DEFAULT_SIGN_BUCKETS = (256, 1024)

# fixed-window width for the host comb; 4 balances table cost (~36 ms per
# message on this CPU) against per-signature adds (~64) for the 10-300
# member committees an operator signs for
_COMB_W = 4
#: groups smaller than this skip the table (plain multiply is cheaper)
_COMB_MIN = 3

_KERNELS: dict = {}  # (nbits, interpret) -> packed ladder callable


def _device_min() -> int:
    try:
        return int(os.environ.get("DUTY_SIGN_MIN", "8"))
    except ValueError:
        return 8


def _use_device_plane() -> bool:
    """Default device routing: TPU backends only (the CPU ladder staging
    cost is the round-1 giant-compile failure mode; the comb is faster
    anyway).  ``DUTY_NO_DEVICE`` wins, ``DUTY_SIGN_DEVICE=1`` forces —
    the crypto-plane polarity discipline."""
    if env_flag("DUTY_NO_DEVICE"):
        return False
    if env_flag("DUTY_SIGN_DEVICE"):
        return True
    import jax

    return jax.default_backend() == "tpu"


def _interpret_mode() -> bool:
    """Eager per-op dispatch instead of one staged ladder program — the
    CPU-test mode (mirrors ``bls_batch._use_planes`` polarity: staging
    the 256-step scan on the CPU backend compiles for minutes)."""
    import jax

    return jax.default_backend() != "tpu"


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _snap_batch(n: int) -> int:
    buckets = shape_buckets("duty_sign")
    if not buckets:
        for b in DEFAULT_SIGN_BUCKETS:
            register_shape_bucket("duty_sign", b)
        buckets = shape_buckets("duty_sign")
    for b in buckets:
        if n <= b:
            return b
    return _pow2(n)


def _sk_scalar(secret_key: bytes) -> int:
    """The host oracle's key guard, verbatim semantics (``bls.api``):
    32 bytes, value in (0, R) — identical rejects on every path."""
    if len(secret_key) != 32:
        raise BlsError("private key must be 32 bytes")
    sk = int.from_bytes(secret_key, "big")
    if sk == 0 or sk >= R:
        raise BlsError("private key out of range")
    return sk


# ------------------------------------------------------------ device plane


def _get_sign_kernel(nbits: int, interpret: bool):
    """The packed plane ladder: affine G2 bases as ``(32, 2, B)`` limb
    planes + MSB-first ``(nbits, B)`` scalar bit rows -> one flat
    ``(6*32+1, B)`` Jacobian result array.  Jitted + AOT-cached on a
    device backend; eager per-op dispatch in interpret mode."""
    key = (nbits, interpret)
    fn = _KERNELS.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    from . import bigint as BI
    from .ladder import make_ladder

    ladder = make_ladder(g2_plane_field(interpret), eager=interpret)

    def packed(bx, by, kbits):
        X, Y, Z, inf = ladder((bx, by), kbits)
        return jnp.concatenate(
            [
                X.reshape(2 * BI.NLIMBS, -1),
                Y.reshape(2 * BI.NLIMBS, -1),
                Z.reshape(2 * BI.NLIMBS, -1),
                inf[None].astype(jnp.int32),
            ],
            axis=0,
        )

    fn = packed if interpret else aot_jit(jax.jit(packed), "duty_sign")
    _KERNELS[key] = fn
    return fn


def _sign_points_device(
    points: list, scalars: list, nbits: int = SCALAR_BITS
) -> list:
    """``[k_i * Q_i]`` through the bucket-snapped plane ladder; affine
    int-pair tuples out (None never occurs for real signatures: a
    subgroup point times k in (0, R) is never infinity, and padded lanes
    are dropped before conversion)."""
    import jax.numpy as jnp

    from . import bigint as BI

    n = len(points)
    out: list = [None] * n
    interpret = _interpret_mode()
    kernel = _get_sign_kernel(nbits, interpret)
    # dispatch REGISTERED shapes only: past the largest warmed bucket the
    # batch runs in largest-bucket chunks (witness-plane discipline — an
    # unregistered pow2 would trace a fresh program mid-slot)
    max_bucket = max(shape_buckets("duty_sign") or DEFAULT_SIGN_BUCKETS)
    for at in range(0, n, max_bucket):
        chunk = list(range(at, min(at + max_bucket, n)))
        # every dispatch snaps to a registered bucket: on the staged
        # path that keeps the program-signature set closed (no mid-slot
        # retrace); interpret-mode tests register tiny buckets so the
        # identical pad-and-drop logic is exercised without eager-mode
        # padded lanes costing real per-op work
        batch = _snap_batch(len(chunk))
        pad = batch - len(chunk)
        pts = [points[i] for i in chunk] + [C.G2_GENERATOR] * pad
        ks = [scalars[i] for i in chunk] + [1] * pad
        bx = fq2_limbs_batch([pt[0] for pt in pts])
        by = fq2_limbs_batch([pt[1] for pt in pts])
        kbits = _scalar_bits_batch(ks, nbits)
        flat = np.asarray(
            kernel(
                jnp.asarray(np.ascontiguousarray(bx.transpose(2, 1, 0))),
                jnp.asarray(np.ascontiguousarray(by.transpose(2, 1, 0))),
                jnp.asarray(kbits.T),
            )
        )
        nl = 2 * BI.NLIMBS
        X = flat[:nl].reshape(BI.NLIMBS, 2, -1).transpose(2, 1, 0)
        Y = flat[nl : 2 * nl].reshape(BI.NLIMBS, 2, -1).transpose(2, 1, 0)
        Z = flat[2 * nl : 3 * nl].reshape(BI.NLIMBS, 2, -1).transpose(2, 1, 0)
        inf = flat[3 * nl].astype(bool)
        xs_c = (_ints_batch(X[:, 0]), _ints_batch(X[:, 1]))
        ys_c = (_ints_batch(Y[:, 0]), _ints_batch(Y[:, 1]))
        zs_c = (_ints_batch(Z[:, 0]), _ints_batch(Z[:, 1]))
        live = [j for j in range(len(chunk)) if not bool(inf[j])]
        # Fq2 inverse via conjugate over the Fp norm, all norms through
        # ONE modexp (the Montgomery prefix trick batch_g2_mul uses)
        zinvs: dict[int, tuple] = {}
        if live:
            norms = [
                (zs_c[0][j] * zs_c[0][j] + zs_c[1][j] * zs_c[1][j]) % P
                for j in live
            ]
            for j, ninv in zip(live, batch_inv_mod(norms, P)):
                zinvs[j] = (
                    zs_c[0][j] * ninv % P,
                    (P - zs_c[1][j]) * ninv % P,
                )
        from ..crypto.bls import fields as F

        for j in live:
            zinv2 = F.fq2_sq(zinvs[j])
            zinv3 = F.fq2_mul(zinv2, zinvs[j])
            out[chunk[j]] = (
                F.fq2_mul((xs_c[0][j], xs_c[1][j]), zinv2),
                F.fq2_mul((ys_c[0][j], ys_c[1][j]), zinv3),
            )
    return out


# -------------------------------------------------------------- host comb


def _comb_tables(pt) -> list:
    """Fixed-base window tables ``T[i][d] = (d << (w*i)) * pt`` in
    Jacobian form — built once per DISTINCT message point and shared by
    every signer of that message (the committee-duty shape)."""
    nwin = (SCALAR_BITS + _COMB_W - 1) // _COMB_W
    tables = []
    base = C.g2.to_jacobian(pt)
    for _ in range(nwin):
        row: list = [None] * (1 << _COMB_W)
        row[1] = base
        for d in range(2, 1 << _COMB_W):
            row[d] = C.g2.jac_add(row[d - 1], base)
        tables.append(row)
        for _ in range(_COMB_W):
            base = C.g2.jac_double(base)
    return tables


def _comb_mul(tables: list, k: int):
    acc = (C.g2.one, C.g2.one, C.g2.zero)
    i = 0
    while k:
        d = k & ((1 << _COMB_W) - 1)
        if d:
            acc = C.g2.jac_add(acc, tables[i][d])
        k >>= _COMB_W
        i += 1
    return C.g2.from_jacobian(acc)


def _sign_points_host(points: list, scalars: list) -> list:
    """The CPU path: group entries by base point, amortize one comb
    table across each group; sub-``_COMB_MIN`` groups run the plain
    (possibly native) ``multiply_raw`` — all the same group math."""
    by_pt: dict = {}
    for i, pt in enumerate(points):
        by_pt.setdefault(pt, []).append(i)
    out: list = [None] * len(points)
    for pt, members in by_pt.items():
        if len(members) >= _COMB_MIN and C.g2.native_mul is None:
            tables = _comb_tables(pt)
            for i in members:
                out[i] = _comb_mul(tables, scalars[i])
        else:
            for i in members:
                out[i] = C.g2.multiply_raw(pt, scalars[i])
    return out


# ---------------------------------------------------------------- surface


def sign_batch(
    secret_keys: Sequence[bytes],
    messages: Sequence[bytes],
    dst: bytes = DST_POP,
    device: bool | None = None,
    nbits: int = SCALAR_BITS,
) -> list[bytes]:
    """Sign ``messages[i]`` with ``secret_keys[i]``; compressed 96-byte
    signatures out, bit-exact with ``bls.sign`` per item.

    Distinct messages hash once (committee members share their point).
    ``device`` forces the plane on (True) or off (False); ``None``
    routes TPU backends with >= ``DUTY_SIGN_MIN`` entries through it.
    ``nbits`` narrows the ladder's bit rows for reduced-width test
    scalars (every real key uses the full 255-bit default)."""
    if len(secret_keys) != len(messages):
        raise BlsError(
            f"{len(secret_keys)} keys for {len(messages)} messages"
        )
    if not secret_keys:
        return []
    if nbits % 8:
        # _scalar_bits_batch byte-packs: a non-multiple-of-8 width would
        # raise deep inside the device dispatch and read as a device
        # fault (silent host fallback) instead of the caller error it is
        raise BlsError(f"ladder width must be a multiple of 8, got {nbits}")
    scalars = [_sk_scalar(sk) for sk in secret_keys]
    if any(k >> nbits for k in scalars):
        raise BlsError(f"secret scalar wider than the {nbits}-bit ladder")
    distinct: dict[bytes, int] = {}
    for msg in messages:
        distinct.setdefault(bytes(msg), len(distinct))
    hashed = hash_to_g2_many(list(distinct), dst)
    points = [hashed[distinct[bytes(msg)]] for msg in messages]
    n = len(points)
    if device is None:
        device = n >= _device_min() and _use_device_plane()
    with span("duty_sign"):
        if device:
            try:
                out = _sign_points_device(points, scalars, nbits)
                inc("duty_signatures_total", n, path="device")
            except Exception:
                # a device fault mid-slot must cost latency, not
                # correctness or the duty: host math is the oracle.
                # LOUD: a permanently broken plane degrading every slot
                # to the comb must not hide behind a counter — the
                # round-20 latch keeps it visible at /debug/slo
                log.exception(
                    "device signing plane failed for %d entries; "
                    "host fallback", n,
                )
                device_fault("duty_sign")
                inc("duty_signatures_total", n, path="host_fallback")
                out = _sign_points_host(points, scalars)
        else:
            inc("duty_signatures_total", n, path="host")
            out = _sign_points_host(points, scalars)
    return [C.g2_to_bytes(pt) for pt in out]


def warm_sign_programs(batch: int | None = None) -> float:
    """Register the ``duty_sign`` buckets and, on a device backend,
    compile/load the plane ladder at the first bucket — the node warmer
    calls this so a slot's first duty flush finds the program resident.
    Drives the plane INTERNALS, not :func:`sign_batch`: a planned warmup
    compile landing in ``duty_sign_seconds`` would read as a phantom
    ``duty_sign_p95`` violation on every boot (the witness-warmer
    discipline).  Values are garbage; program identity is keyed by
    shape, which is all warming needs."""
    t0 = time.perf_counter()
    for b in DEFAULT_SIGN_BUCKETS:
        register_shape_bucket("duty_sign", b)
    if _use_device_plane() and not _interpret_mode():
        b = int(batch) if batch else DEFAULT_SIGN_BUCKETS[0]
        with compile_context("warmup:duties"):
            _sign_points_device([C.G2_GENERATOR] * b, [1] * b)
    return time.perf_counter() - t0
