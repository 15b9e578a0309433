"""Batched G1 scalar multiplication on device (JAX over limb arithmetic).

The first stage of the device BLS path: the random-linear-combination batch
verification (crypto/bls/batch.py) spends its time on many independent
~128-bit scalar multiplications — exactly a data-parallel ladder.  This
module runs them as one ``lax.scan`` ladder ``vmap``-ed over the batch, on
top of :mod:`.bigint`'s Barrett limb arithmetic (plain canonical residues).

Branch-free completeness: the addition step computes both the generic
addition and the doubling result and selects by the (canonical-form) limb
equality masks, and point-at-infinity flags thread through ``where`` — no
data-dependent Python control flow, so the whole ladder jits.

Host boundary: affine integer points in, affine integer points out
(Jacobian -> affine inversion happens on host, one inversion per result).
"""

from __future__ import annotations

import numpy as np

from ..crypto.bls.fields import P
from . import bigint as BI

SCALAR_BITS = 256


def _scalar_bits_batch(ks: list, nbits: int = SCALAR_BITS) -> np.ndarray:
    """ints -> (N, nbits) int32 bits, MSB first (vectorized)."""
    raw = b"".join(int(k).to_bytes(nbits // 8, "big") for k in ks)
    bits = np.unpackbits(np.frombuffer(raw, np.uint8))
    return bits.reshape(len(ks), nbits).astype(np.int32)


def _ints_batch(limbs: np.ndarray) -> list:
    """(N, 32) int32 little-endian 12-bit limbs -> list of N ints.

    Vectorized inverse of :func:`_limbs_batch` — the per-element
    ``BI.from_limbs`` loop costs ~25us/element in Python, which dominated
    the whole device ladder at batch 4096."""
    n = len(limbs)
    # big-endian bitstream: most-significant limb first, bits MSB-first
    bits = ((limbs[:, ::-1, None] >> np.arange(BI.LIMB_BITS - 1, -1, -1)) & 1)
    packed = np.packbits(bits.astype(np.uint8).reshape(n, -1), axis=1)
    return [int.from_bytes(row.tobytes(), "big") for row in packed]


def _limbs_batch(xs: list) -> np.ndarray:
    """ints -> (N, NLIMBS) int32 12-bit limbs (vectorized)."""
    raw = b"".join(int(x).to_bytes(BI.NLIMBS * BI.LIMB_BITS // 8, "big") for x in xs)
    bits = np.unpackbits(np.frombuffer(raw, np.uint8)).reshape(
        len(xs), BI.NLIMBS, BI.LIMB_BITS
    )
    weights = 1 << np.arange(BI.LIMB_BITS - 1, -1, -1, dtype=np.int32)
    limbs_be = bits.astype(np.int32) @ weights  # (N, NLIMBS) most-significant first
    return limbs_be[:, ::-1].copy()  # little-endian limb order


def make_g1_ops(nbits: int = SCALAR_BITS):
    import jax
    import jax.numpy as jnp

    from .ladder import make_ladder

    ops = BI.get_ops()
    field = {
        "mul": ops["mul_mod"],
        "add": ops["add_mod"],
        "sub": ops["sub_mod"],
        "one": jnp.asarray(BI.to_limbs(1)),
        "zero": jnp.zeros(BI.NLIMBS, jnp.int32),
        "eq": lambda a, b: jnp.all(a == b, axis=-1),
        "felt_ndim": 1,
    }
    ladder = make_ladder(field, nbits)
    ladder_batched = jax.jit(jax.vmap(ladder, in_axes=((0, 0), 0)))
    return {"ladder_batched": ladder_batched}


# one compiled ladder per scalar width (256 generic, 128 for RLC coefficients)
_G1_OPS: dict = {}


def _get_g1_ops(nbits: int):
    if nbits not in _G1_OPS:
        _G1_OPS[nbits] = make_g1_ops(nbits)
    return _G1_OPS[nbits]


def g1_plane_field(interpret: bool = False) -> dict:
    """The plane-layout Fq field dict (elements ``(32, ...B)``, batch
    trailing) consumed by :mod:`.ladder` — shared by the standalone plane
    ladder below and the chained batch-verify pipeline (:mod:`.bls_batch`)."""
    import jax.numpy as jnp

    from .bigint_pallas import make_plane_ops

    ops = make_plane_ops(interpret=interpret)
    return {
        "mul": ops["mul_mod"],
        "add": ops["add_mod"],
        "sub": ops["sub_mod"],
        "one": jnp.asarray(BI.to_limbs(1)[:, None]),
        "zero": jnp.zeros((BI.NLIMBS, 1), jnp.int32),
        "eq": lambda a, b: jnp.all(a == b, axis=0),
        "felt_ndim": 0,
        "flags": lambda bx: jnp.zeros(bx.shape[1:], jnp.bool_),
    }


def make_g1_plane_ops(nbits: int = SCALAR_BITS, interpret: bool = False):
    """Plane-layout ladder: elements are ``(32, B)`` limb planes, batch
    last, multiplication through the fused Pallas kernel
    (:mod:`.bigint_pallas`) — no vmap; the batch IS the trailing axis."""
    import jax
    import jax.numpy as jnp

    from .ladder import make_ladder

    ladder = make_ladder(g1_plane_field(interpret), nbits, eager=interpret)

    def packed(base_xy, bits):
        # one output array -> one device->host pull
        X, Y, Z, inf = ladder(base_xy, bits)
        return jnp.concatenate(
            [X, Y, Z, inf[None].astype(jnp.int32)], axis=0
        )

    # "eager" skips jit: interpret-mode CI runs would otherwise inline
    # every kernel into one giant XLA CPU program
    return {"ladder_packed": packed if interpret else jax.jit(packed)}


_G1_PLANE_OPS: dict = {}


def _get_g1_plane_ops(nbits: int, interpret: bool = False):
    key = (nbits, interpret)
    if key not in _G1_PLANE_OPS:
        _G1_PLANE_OPS[key] = make_g1_plane_ops(nbits, interpret)
    return _G1_PLANE_OPS[key]


_PLANE_QUANTUM = 1024  # sublanes x lanes: the Pallas tile batch quantum


def _use_planes() -> bool:
    import jax

    from ..utils.env import env_flag

    if env_flag("BIGINT_NO_PALLAS"):
        return False
    return jax.default_backend() == "tpu"


def batch_inv_mod(values: list, modulus: int) -> list:
    """Montgomery prefix-product batch inversion: one modexp for any
    number of nonzero residues (shared by the G1/G2 affine conversions)."""
    assert all(v % modulus != 0 for v in values)
    prefix = []
    acc = 1
    for v in values:
        acc = acc * v % modulus
        prefix.append(acc)
    inv_all = pow(acc, modulus - 2, modulus)
    out = [0] * len(values)
    for idx in range(len(values) - 1, -1, -1):
        before = prefix[idx - 1] if idx > 0 else 1
        out[idx] = inv_all * before % modulus
        inv_all = inv_all * values[idx] % modulus
    return out


def batch_g1_mul(
    points: list,
    scalars: list,
    bits: int = SCALAR_BITS,
    planes: bool | None = None,
    interpret: bool = False,
) -> list:
    """Batched scalar multiplication: ``[k_i * P_i]`` on device.

    ``points``: affine ``(x, y)`` int pairs (no Nones); ``scalars``: ints in
    [0, 2^bits) — callers with short scalars (the 128-bit RLC coefficients)
    pass the width so the ladder runs half the steps.  Returns affine int
    pairs or ``None`` for infinity results.

    ``planes``: force the Pallas plane path on/off (default: on when the
    backend is TPU).
    """
    assert len(points) == len(scalars)
    if not points:
        return []
    n = len(points)
    bx = _limbs_batch([x for x, _ in points])
    by = _limbs_batch([y for _, y in points])
    if planes is None:
        planes = _use_planes()
    if planes:
        import jax.numpy as jnp

        pad = -n % _PLANE_QUANTUM
        if pad:
            gx, gy = _limbs_batch([1]), _limbs_batch([2])  # any x,y: masked out
            bx = np.concatenate([bx, np.repeat(gx, pad, 0)])
            by = np.concatenate([by, np.repeat(gy, pad, 0)])
        kbits = _scalar_bits_batch(list(scalars) + [1] * pad, bits)
        ops = _get_g1_plane_ops(bits, interpret)
        packed = np.asarray(
            ops["ladder_packed"](
                (jnp.asarray(bx.T), jnp.asarray(by.T)), jnp.asarray(kbits.T)
            )
        )
        nl = BI.NLIMBS
        X, Y, Z = packed[:nl].T, packed[nl : 2 * nl].T, packed[2 * nl : 3 * nl].T
        inf = packed[3 * nl].astype(bool)
    else:
        ops = _get_g1_ops(bits)
        kbits = _scalar_bits_batch(scalars, bits)
        X, Y, Z, inf = ops["ladder_batched"]((bx, by), kbits)
        # bulk device->host transfer once, not per element
        X, Y, Z, inf = (
            np.asarray(X),
            np.asarray(Y),
            np.asarray(Z),
            np.asarray(inf),
        )
    live = [i for i in range(n) if not bool(inf[i])]
    xs_l, ys_l, zs_l = _ints_batch(X[:n]), _ints_batch(Y[:n]), _ints_batch(Z[:n])
    xs = {i: xs_l[i] for i in live}
    ys = {i: ys_l[i] for i in live}
    zs = {i: zs_l[i] for i in live}
    # the ladder's infinity flag guarantees nonzero z for live entries;
    # batch_inv_mod asserts it rather than poisoning the shared product
    zinvs = dict(zip(live, batch_inv_mod([zs[i] for i in live], P))) if live else {}
    out = []
    for i in range(len(points)):
        if i not in zinvs:
            out.append(None)
            continue
        zinv = zinvs[i]
        zinv2 = zinv * zinv % P
        out.append((xs[i] * zinv2 % P, ys[i] * zinv2 % P * zinv % P))
    return out
