"""Pallas TPU kernel for batched 384-bit modular multiplication.

The fused replacement for :mod:`.bigint`'s einsum path.  The einsum
formulation contracts through a dense one-hot tensor — 32x32x63 ~= 64k
MACs per element where the schoolbook convolution needs 1024 — and
round-trips every intermediate through XLA buffers.  This kernel does the
direct convolution with all intermediates in vector registers.

Layout (mirrors ops/sha256.py): limb-plane major ``(32, R, 128) int32``
— limb index outermost, batch across (sublane-rows x 128 lanes).  Each
grid step owns a ``(32, 8, 128)`` tile; every statement below is one
(8, 128) VPU op.

In-kernel arithmetic notes:

- Limbs are 12-bit in int32 (canonical inputs); convolution partial sums
  are bounded by 33 * 2^24 < 2^30 — exact, 2x headroom (same bound as
  bigint.py; re-derive before changing limb width or count).
- Carry/borrow propagation is a single *serial sweep* over the limb
  planes: per-plane statements make a 64-deep dependency chain of (8,128)
  ops — negligible — where the array-at-once einsum path needed the
  log-depth carry-lookahead machinery.
- Barrett reduction (HAC 14.42) identical to the host/einsum path, with
  the modulus and mu as per-limb Python int scalars (free broadcasts).
"""

from __future__ import annotations

import numpy as np

from ..crypto.bls.fields import P
from . import bigint as BI

LANES = 128
SUBLANES = 8

_LIMB_BITS = BI.LIMB_BITS
_MASK = BI.LIMB_MASK
_N = BI.NLIMBS  # 32
_P_LIMBS = [int(v) for v in BI.to_limbs(P)]
_MU_LIMBS = [int(v) for v in BI.to_limbs(BI.MU, _N + 1)]


def _conv(a: list, b: list) -> list:
    """Schoolbook limb convolution of plane lists (len n1 x n2)."""
    out = [None] * (len(a) + len(b) - 1)
    for i in range(len(a)):
        for j in range(len(b)):
            t = a[i] * b[j]
            k = i + j
            out[k] = t if out[k] is None else out[k] + t
    return out


def _conv_const(a: list, c: list) -> list:
    """Convolution with a constant limb vector (Python int scalars)."""
    out = [None] * (len(a) + len(c) - 1)
    for i in range(len(a)):
        for j, cj in enumerate(c):
            if cj == 0:
                continue
            t = a[i] * cj
            k = i + j
            out[k] = t if out[k] is None else out[k] + t
    import jax.numpy as jnp

    zero = jnp.zeros_like(a[0])
    return [zero if v is None else v for v in out]


def _carry_sweep(v: list, width: int) -> list:
    """Non-negative planes -> canonical limbs over ``width`` planes.
    One serial low-to-high sweep; the value must fit the width."""
    import jax.numpy as jnp

    zero = jnp.zeros_like(v[0])
    out = list(v) + [zero] * (width - len(v))
    for i in range(width - 1):
        carry = out[i] >> _LIMB_BITS
        out[i] = out[i] & _MASK
        out[i + 1] = out[i + 1] + carry
    return out


def _sub_sweep(v: list, m: list) -> tuple[list, "object"]:
    """(v - m) mod b^len with serial borrow sweep; also returns the final
    borrow (1 where v < m).  Operands canonical, same length."""
    out = []
    borrow = 0
    for i in range(len(v)):
        d = v[i] - m[i] - borrow
        neg = (d < 0).astype(d.dtype)
        out.append(d + (neg << _LIMB_BITS))
        borrow = neg
    return out, borrow


def _sub_const_if_ge(v: list, c: list) -> list:
    """v - c where v >= c else v (c: Python int limbs padded to len(v))."""
    import jax.numpy as jnp

    cp = [jnp.full_like(v[0], ci) for ci in c]
    diff, borrow = _sub_sweep(v, cp)
    keep = borrow.astype(bool)
    return [jnp.where(keep, vi, di) for vi, di in zip(v, diff)]


def _add_mod_kernel(a_ref, b_ref, out_ref):
    v = [a_ref[i] + b_ref[i] for i in range(_N)]
    v = _carry_sweep(v, _N + 1)
    v = _sub_const_if_ge(v, _P_LIMBS + [0])
    for i in range(_N):
        out_ref[i] = v[i]


def _sub_mod_kernel(a_ref, b_ref, out_ref):
    # a - b + p; per-limb negatives flow through the serial sweep because
    # arithmetic >> floors, so carries are in {-1, 0, 1} and & MASK
    # re-canonicalizes each limb
    v = [a_ref[i] - b_ref[i] + _P_LIMBS[i] for i in range(_N)]
    v = _carry_sweep(v, _N + 1)
    v = _sub_const_if_ge(v, _P_LIMBS + [0])
    for i in range(_N):
        out_ref[i] = v[i]


def _mul_mod_kernel(a_ref, b_ref, out_ref):
    a = [a_ref[i] for i in range(_N)]
    b = [b_ref[i] for i in range(_N)]
    x = _carry_sweep(_conv(a, b), 2 * _N)  # canonical 64-limb product
    # Barrett: q1 = x >> b^(k-1); q2 = q1*mu; q3 = q2 >> b^(k+1); r = x - q3*p
    q1 = x[_N - 1 :]  # 33 limbs
    q2 = _carry_sweep(_conv_const(q1, _MU_LIMBS), 2 * _N + 2)
    q3 = q2[_N + 1 : 2 * _N + 2]  # 33 limbs
    qp = _carry_sweep(_conv_const(q3, _P_LIMBS), 2 * _N + 1)
    width = _N + 2  # r = (x - q3*p) mod b^34; true r in [0, 3p)
    r, _ = _sub_sweep(x[:width], qp[:width])
    pc = _P_LIMBS + [0] * (width - _N)
    r = _sub_const_if_ge(r, pc)
    r = _sub_const_if_ge(r, pc)
    for i in range(_N):
        out_ref[i] = r[i]


def mul_mod_planes(a, b, interpret: bool = False):
    """Batched ``(a * b) mod p`` in limb-plane layout: ``(32, R, 128)``
    int32 canonical, ``R % 8 == 0``; returns the same shape, canonical."""
    return _plane_call(_mul_mod_kernel, a, b, interpret)


# ----------------------------------------------------- plane-layout field ops
#
# Element layout for the plane-based device stack: ``(32, comps..., B)`` —
# limb planes outermost, tower-component axes in the middle, batch last.
# Batch-last means per-element masks (B,) broadcast against any element
# without expansion, tower components slice as ``a[:, i]``, and the whole
# component block flattens into the kernel's batch axis with a free
# reshape (no transpose).


_TILE_CALLS: dict = {}


def _tile_call(kernel, interpret: bool):
    """The kernel over whole tiles: ``(32, rows, 128)`` x2 -> same shape.

    Compiled mode puts exactly this behind ONE ``jax.jit`` per kernel: a
    staged program (ladder, Miller loop) reaches these kernels at hundreds
    of call sites, and without it every site re-traces the unrolled kernel
    and lowers its own Mosaic module — minutes of host-side lowering per
    program.  Behind the jit the trace and the lowering are cached per tile
    count (operands of every shape flatten and pad to it first), and each
    site becomes a call the compiler inlines: the same compiled program,
    lowered in seconds."""
    key = (kernel, interpret)
    fn = _TILE_CALLS.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        spec = pl.BlockSpec(
            (_N, SUBLANES, LANES), lambda i: (0, i, 0), memory_space=pltpu.VMEM
        )

        def run(a3, b3):
            rows = a3.shape[1]
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((_N, rows, LANES), jnp.int32),
                grid=(rows // SUBLANES,),
                in_specs=[spec, spec],
                out_specs=spec,
                interpret=interpret,
            )(a3, b3)

        fn = _TILE_CALLS[key] = run if interpret else jax.jit(run)
    return fn


def _plane_call(kernel, a, b, interpret: bool):
    """Broadcast two plane operands, flatten component axes into the
    batch, pad to the tile quantum, run the kernel tile-wise, restore the
    shape."""
    import jax.numpy as jnp

    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    m = int(np.prod(shape[1:]))
    quantum = SUBLANES * LANES
    mp = -(-m // quantum) * quantum
    a = a.reshape(_N, m)
    b = b.reshape(_N, m)
    if mp != m:
        a = jnp.pad(a, ((0, 0), (0, mp - m)))
        b = jnp.pad(b, ((0, 0), (0, mp - m)))
    rows = mp // LANES
    out = _tile_call(kernel, interpret)(
        a.reshape(_N, rows, LANES), b.reshape(_N, rows, LANES)
    )
    return out.reshape(_N, mp)[:, :m].reshape(shape)


def make_plane_ops(interpret: bool = False, pallas_interpret: bool = False):
    """mul/add/sub over ``(32, ..., B)`` plane-layout operands.

    Default: fused Pallas kernels, ``prod(.., B) % 1024 == 0`` after
    broadcasting (tile quantum handled internally).

    ``interpret=True`` is the CPU-testable mode: plane semantics served by
    the jitted einsum/Barrett path (:mod:`.bigint`) through layout
    transposes — fast enough to drive the full plane ladder/pairing/chain
    stacks in CI.  The Pallas kernel *statements* get their own CPU
    coverage via ``pallas_interpret=True`` (true Pallas interpret mode,
    per-tile Python execution — kernel unit tests only; far too slow for
    the composite stacks).
    """
    if interpret and not pallas_interpret:
        import jax
        import jax.numpy as jnp

        eins = BI.get_ops()

        def _lift(op):
            # One jitted program per op/shape: the moveaxis/broadcast
            # wrappers would otherwise multiply eager-dispatch overhead
            # ~6x across the hundreds of thousands of field ops a chained
            # verify issues.
            @jax.jit
            def f(a, b):
                shape = jnp.broadcast_shapes(a.shape, b.shape)
                a2 = jnp.moveaxis(jnp.broadcast_to(a, shape), 0, -1)
                b2 = jnp.moveaxis(jnp.broadcast_to(b, shape), 0, -1)
                return jnp.moveaxis(op(a2, b2), -1, 0)

            return f

        return {
            "mul_mod": _lift(eins["mul_mod"]),
            "add_mod": _lift(eins["add_mod"]),
            "sub_mod": _lift(eins["sub_mod"]),
        }

    run_interpret = pallas_interpret

    def _mul(a, b):
        return _plane_call(_mul_mod_kernel, a, b, run_interpret)

    def _add(a, b):
        return _plane_call(_add_mod_kernel, a, b, run_interpret)

    def _sub(a, b):
        return _plane_call(_sub_mod_kernel, a, b, run_interpret)

    return {"mul_mod": _mul, "add_mod": _add, "sub_mod": _sub}


# ------------------------------------------------------- host marshalling


def to_planes(xs: list, rows: int) -> np.ndarray:
    """ints -> (32, rows, 128) plane layout (zero padded)."""
    from .bls_g1 import _limbs_batch

    limbs = _limbs_batch(xs)  # (N, 32)
    out = np.zeros((rows * LANES, _N), np.int32)
    out[: len(xs)] = limbs
    return np.ascontiguousarray(out.T).reshape(_N, rows, LANES)


def from_planes(planes: np.ndarray, n: int) -> list:
    """(32, rows, 128) planes -> list of n ints."""
    flat = np.asarray(planes).reshape(_N, -1).T[:n]  # (n, 32)
    return [BI.from_limbs(row) for row in flat]
