"""Batched BLS12-381 optimal-ate pairing on device (JAX over limb towers).

The device counterpart of ``crypto/bls/pairing.py`` and the lockstep C++
Miller loop in ``native/bls381/bls381.cpp`` (SURVEY.md §7 hard-part #1:
"batched pairing under vmap ... Miller loops + shared final
exponentiation").  Same line-slot convention as the native backend — the
line through the running twist point r evaluated at P = (px, py), scaled
by xi, lives at tower slots w^0 / w^3 / w^5:

    l = (py*xi) * w^0 + (lambda*x_r - y_r) * w^3 + (-lambda*px) * w^5

— but where the native path stays affine and shares one Montgomery batch
inversion per step (a serial host trick), the device loop clears
denominators into homogeneous projective coordinates (X, Y, Z): scaling a
line by any Fq2 factor is legal because subfield factors die in the final
exponentiation's p^6-1 part, so each step is inversion-free and the whole
batch advances in lockstep under one ``lax.scan``.

Exceptional cases (vertical lines, doubling-as-addition) cannot occur for
the inputs this module accepts: subgroup-checked points of prime order R
with the loop scalar |x| << R, infinities filtered by the caller — so the
step formulas are used unconditionally and the kernel stays branch-free.

Final exponentiation mirrors the host addition chain (cubed hard part,
``crypto/bls/pairing.py``) with ``a^|x|`` as a scan over the static
parameter bits; inversion is the batched Fermat powmod from
:mod:`.bls_fq12`.

Two instantiations (same code, different layout adapters — see
:mod:`.bls_fq12`): the batch-leading einsum stack (CPU backend, oracle
tests) and the limb-plane Pallas stack (TPU fast path).
"""

from __future__ import annotations

import os

import numpy as np

from ..crypto.bls.fields import BLS_X, BLS_X_IS_NEG
from . import bls_fq12 as FQ
from .bls_g1 import _limbs_batch, _use_planes

__all__ = [
    "make_pairing_ops",
    "miller_loop_batch",
    "pairing_product_is_one",
    "pairing_products_are_one",
]

# MSB-first bits of |x| after the leading 1 (63 entries), shared by the
# Miller loop and a^x — identical to the host/native loop order.
_X_BITS = np.array([int(b) for b in bin(BLS_X)[3:]], np.int32)

# The device Miller loop and pow_x conjugate UNCONDITIONALLY for the
# negative BLS parameter (the host path branches on the flag) — make the
# assumption loud if the curve constants ever change (ADVICE r1).
assert BLS_X_IS_NEG, "device pairing assumes the negative BLS12-381 parameter"

# w-power -> (c1?, v-power) tower slot, per w^2 = v, v^3 = xi.
_W_SLOTS = [(0, 0), (1, 0), (0, 1), (1, 1), (0, 2), (1, 2)]

_WARNED_TAILS: set = set()


def _warn_tail_fallback(mode: str) -> None:
    """A broken fast tail silently reinstating the ~10 s/drain composed
    path is a 50x latency regression — say so, once per mode."""
    if mode not in _WARNED_TAILS:
        _WARNED_TAILS.add(mode)
        import logging

        logging.getLogger("ops.pairing").exception(
            "%s tail failed; falling back to the composed device tail "
            "(expect much higher per-drain latency)", mode
        )


def make_pairing_ops(
    plane: bool = False, interpret: bool = False, eager: bool | None = None
):
    """``interpret`` picks the base ops (Pallas vs einsum delegation);
    ``eager`` picks the loop style (host loops vs lax.scan/cond) and
    defaults to ``interpret``.  The sharded pipeline uses
    ``interpret=True, eager=False`` — stageable bodies over the
    CPU-portable base."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if eager is None:
        eager = interpret
    ops = (
        FQ.get_fq12_plane_ops(interpret, eager) if plane else FQ.get_fq12_ops()
    )
    lay = ops["layout"]
    f2m, f2s = ops["fq2_mul"], ops["fq2_sq"]
    f2a, f2sub = ops["fq2_add"], ops["fq2_sub"]
    f2neg, f2xi = ops["fq2_neg"], ops["fq2_mul_by_xi"]
    f2fp = ops["fq2_scale_fp"]
    f12m, f12sq = ops["fq12_mul"], ops["fq12_sq"]
    f12conj, f12inv = ops["fq12_conj"], ops["fq12_inv"]
    f12frob = ops["fq12_frobenius"]

    bits = jnp.asarray(_X_BITS)

    def _slots(f):
        """Fq12 -> list of 6 Fq2 slots in w-power order."""
        return [
            lay.part(6, lay.part(12, f, i), j) for (i, j) in _W_SLOTS
        ]

    def _from_slots(s):
        c0 = lay.stack(6, [s[0], s[2], s[4]])
        c1 = lay.stack(6, [s[1], s[3], s[5]])
        return lay.stack(12, [c0, c1])

    def mul_sparse035(f, l0, l3, l5):
        """f *= l0 + l3 w^3 + l5 w^5 — 18 fq2 muls, mirrors the native
        fq12_mul_sparse slot convolution with w^6 = xi wrap."""
        fs = _slots(f)
        out = [None] * 6
        for i in range(6):
            for pw, c in ((0, l0), (3, l3), (5, l5)):
                k = i + pw
                prod = f2m(fs[i], c)
                if k >= 6:
                    k -= 6
                    prod = f2xi(prod)
                out[k] = prod if out[k] is None else f2a(out[k], prod)
        return _from_slots(out)

    def dbl_step(f, X, Y, Z, px, py):
        """Projective doubling + line (EFD dbl-2007-bl, a = 0; line terms
        share w3/s/Rr with the point update)."""
        XX = f2s(X)
        w3 = f2a(f2a(XX, XX), XX)
        t = f2m(Y, Z)
        s = f2a(t, t)
        ss = f2s(s)
        sss = f2m(s, ss)
        Rr = f2m(Y, s)
        RR = f2s(Rr)
        B = f2m(X, Rr)
        B = f2a(B, B)
        h = f2sub(f2s(w3), f2a(B, B))
        Xn = f2m(h, s)
        Yn = f2sub(f2m(w3, f2sub(B, h)), f2a(RR, RR))
        Zn = sss
        # line at the pre-update point, scaled by (2y) * Z^3
        l0 = f2fp(f2xi(f2m(s, Z)), py)
        l3 = f2sub(f2m(X, w3), Rr)
        l5 = f2neg(f2fp(f2m(w3, Z), px))
        return mul_sparse035(f, l0, l3, l5), Xn, Yn, Zn

    def add_step(f, X, Y, Z, qx, qy, px, py):
        """Mixed addition of the affine base Q + line (EFD madd-1998-cmo),
        line scaled by (qx - x_r) * Z."""
        u = f2sub(f2m(qy, Z), Y)
        v = f2sub(f2m(qx, Z), X)
        uu = f2s(u)
        vv = f2s(v)
        vvv = f2m(v, vv)
        Rm = f2m(vv, X)
        A = f2sub(f2sub(f2m(uu, Z), vvv), f2a(Rm, Rm))
        Xn = f2m(v, A)
        Yn = f2sub(f2m(u, f2sub(Rm, A)), f2m(vvv, Y))
        Zn = f2m(vvv, Z)
        l0 = f2fp(f2xi(f2m(v, Z)), py)
        l3 = f2sub(f2m(u, X), f2m(v, Y))
        l5 = f2neg(f2fp(f2m(u, Z), px))
        return mul_sparse035(f, l0, l3, l5), Xn, Yn, Zn

    def miller(px, py, qx, qy):
        """Batched Miller loop.  Fp operands px/py and Fq2 twist
        coordinates qx/qy in the instantiation's layout; returns f."""
        f = ops["fq12_one"](lay.fq_batch_shape(px))
        X, Y = qx, qy
        Z = lay.fq2_like((1, 0), qx)

        if eager:
            # CPU-test mode: the loop bits are STATIC — unroll as host
            # Python (no lax.cond/scan staging, no giant CPU compile;
            # the tower ops dispatch small fq2-level jits), skipping the
            # add step on zero bits entirely.
            for bit in _X_BITS.tolist():
                f = f12sq(f)
                f, X, Y, Z = dbl_step(f, X, Y, Z, px, py)
                if bit:
                    f, X, Y, Z = add_step(f, X, Y, Z, qx, qy, px, py)
            return f12conj(f)

        def body(carry, bit):
            f, X, Y, Z = carry
            f = f12sq(f)
            f, X, Y, Z = dbl_step(f, X, Y, Z, px, py)

            def with_add(op):
                return add_step(op[0], op[1], op[2], op[3], qx, qy, px, py)

            f, X, Y, Z = lax.cond(
                bit != 0, with_add, lambda op: op, (f, X, Y, Z)
            )
            return (f, X, Y, Z), None

        (f, _, _, _), _ = lax.scan(body, (f, X, Y, Z), bits)
        return f12conj(f)  # negative BLS parameter

    def pow_x_abs(a):
        """a^|x| by square-and-multiply over the static parameter bits.
        (Callers conjugate for the negative sign — on the cyclotomic
        subgroup, where every use of this lives.)"""
        if eager:
            acc = a
            for bit in _X_BITS.tolist():
                acc = f12sq(acc)
                if bit:
                    acc = f12m(acc, a)
            return acc

        def body(acc, bit):
            acc = f12sq(acc)
            acc = lax.cond(bit != 0, lambda t: f12m(t, a), lambda t: t, acc)
            return acc, None

        acc, _ = lax.scan(body, a, bits)
        return acc

    def easy_part(f):
        """f^((p^6-1)(p^2+1))."""
        f = f12m(f12conj(f), f12inv(f))
        return f12m(f12frob(f12frob(f)), f)

    def masked_product(f, mask):
        """Fq12 batch with a K grouping axis innermost + live mask ->
        product over K; padded lanes become the identity.

        Staged path: a lax.scan of one f12_mul — the pairwise-halving
        tree makes a distinct program shape per level, each its own
        trace, lowering and compile.  Eager path keeps the halving tree
        (fewer host dispatches).
        """
        one = ops["fq12_one"](lay.batch_shape(f))
        f = jnp.where(lay.expand_mask(mask), f, one)
        if not eager:
            xs = lay.kleading(f)

            def body(acc, elem):
                return f12m(acc, elem), None

            acc, _ = lax.scan(body, xs[0], xs[1:])
            return acc
        k = lay.ksize(f)
        while k > 1:
            if k % 2:
                pad_shape = (*lay.batch_shape(f)[:-1], 1)
                f = lay.kconcat([f, ops["fq12_one"](pad_shape)])
                k += 1
            f = f12m(lay.kslice(f, slice(0, None, 2)), lay.kslice(f, slice(1, None, 2)))
            k //= 2
        return lay.kslice(f, 0)

    # The final exponentiation is composed on the host from these small
    # jitted pieces rather than jitted whole: the fully-unrolled chain is
    # a single XLA program big enough to exhaust compiler memory on the
    # CPU backend, while each piece here is at most one scan body deep.
    # In interpret mode (CPU tests) the LOOP-carrying pieces (miller,
    # pow_x_abs, easy_part via fp_inv, masked_product) stay host-composed
    # — staging their loops is exactly the giant-compile failure mode —
    # while the straight-line pieces still jit (one dispatch each).
    if eager:
        wrap = lambda f, name=None: f
    else:
        from .aot import aot_jit

        # compiled programs go through the cross-process AOT executable
        # cache (ops/aot.py)
        tag = "plane" if plane else "einsum"
        wrap = lambda f, name=None: aot_jit(
            jax.jit(f), f"pair_{tag}_{name or getattr(f, '__name__', 'fn')}"
        )
    jits = {
        "miller": wrap(miller, "miller"),
        # UNwrapped bodies for shard_map composition (ops/bls_shard.py):
        # the aot_jit wrapper cannot run under another trace (it calls
        # .lower()/compiled executables with tracers), so the sharded
        # pipeline builds ONE program from these and jits that whole
        # shard_map — same discipline as bls_batch's staged_reduce_*.
        "miller_raw": miller,
        "masked_product_raw": masked_product,
        "mul_raw": f12m,
        "pow_x_abs": wrap(pow_x_abs, "pow_x_abs"),
        # easy_part is host-composed from inv/conj/frob/mul below on the
        # staged path; the eager path keeps the direct composition
        "easy_part": easy_part if eager else None,
        "inv": wrap(f12inv, "inv"),
        "masked_product": wrap(masked_product, "masked_product"),
        "mul": wrap(f12m, "mul"),
        "sq": wrap(f12sq, "sq"),
        "conj": wrap(f12conj, "conj"),
        "frob": wrap(f12frob, "frob"),
        "is_one": wrap(ops["fq12_is_one"], "is_one"),
    }

    def pow_x(a):
        return jits["conj"](jits["pow_x_abs"](a))

    def final_exp(f):
        """Host-composed mirror of the host-side addition chain
        (crypto/bls/pairing.py): easy part, then the cubed hard part —
        every step a cached device dispatch."""
        mul, conj, frob, sq = (
            jits["mul"],
            jits["conj"],
            jits["frob"],
            jits["sq"],
        )
        if jits["easy_part"] is not None:  # eager path
            m = jits["easy_part"](f)
        else:
            # f^((p^6-1)(p^2+1)) from the small jitted pieces: the
            # inversion (a Fermat scan) is the only non-trivial program
            t = mul(conj(f), jits["inv"](f))
            m = mul(frob(frob(t)), t)
        a = mul(pow_x(m), conj(m))
        b = mul(pow_x(a), conj(a))
        c = mul(pow_x(b), frob(b))
        d = mul(mul(pow_x(pow_x(c)), frob(frob(c))), conj(c))
        return mul(d, mul(sq(m), m))

    def _tail_raw(f, mask):
        """The WHOLE tail — masked product, easy part, hard part,
        is-one — traced as ONE program.  The scans (pow_x_abs, inv,
        masked product) stay lax loops inside it, so the program is
        bounded; what fuses away is the composed path's ~29 separate
        dispatches (their cost on a local chip: not measured)."""
        m = masked_product(f, mask)
        t = f12m(f12conj(m), f12inv(m))
        e = f12m(f12frob(f12frob(t)), t)

        def pxr(a):
            return f12conj(pow_x_abs(a))

        a = f12m(pxr(e), f12conj(e))
        b = f12m(pxr(a), f12conj(a))
        c = f12m(pxr(b), f12frob(b))
        d = f12m(f12m(pxr(pxr(c)), f12frob(f12frob(c))), f12conj(c))
        return ops["fq12_is_one"](f12m(d, f12m(f12sq(e), e)))

    if not eager:
        jits["check_tail_fused"] = wrap(_tail_raw, "check_tail_fused")

    def _tail_hybrid(f, mask):
        """Device masked product (ONE dispatch) -> pull the O(checks)
        fq12 products -> C++ final exp + identity check.  The default
        TPU tail: the composed on-device final exp is ~29 dispatches,
        while the pulled remainder is 576 bytes and ~2 ms of native math
        per check.  (Chosen when every dispatch crossed a remote link;
        whether it still wins on a local chip is not measured.)"""
        from ..crypto.bls import native

        m = jits["masked_product"](f, mask)
        vals = FQ.fq12_batch_from_limbs(np.asarray(m), plane=plane)
        return np.asarray(native.final_exp_is_one(vals), dtype=bool)

    def check_tail(f, mask):
        """Miller outputs grouped (batch..., K) + live mask -> bools.

        Tail modes (BLS_TAIL overrides: fused | hybrid | composed):
        - TPU default: hybrid (device product, native host final exp);
        - BLS_TAIL=fused: the single-program on-device tail (first use
          pays its multi-minute compile; AOT-cached after);
        - composed: the per-piece device dispatches — always the
          fallback, and the only mode for CPU/staged (the multichip
          dryrun's virtual mesh), where one giant XLA CPU program is
          the compiler-memory failure mode the module docstring records.
        """
        mode = os.environ.get("BLS_TAIL", "")
        on_tpu = not eager and jax.default_backend() == "tpu"
        if mode == "fused" and "check_tail_fused" in jits:
            try:
                return jits["check_tail_fused"](f, mask)
            except Exception:
                _warn_tail_fallback("fused")
        if on_tpu and mode != "composed":
            from ..crypto.bls import native

            if native.final_exp_available():
                try:
                    return _tail_hybrid(f, mask)
                except Exception:
                    _warn_tail_fallback("hybrid")
        return jits["is_one"](final_exp(jits["masked_product"](f, mask)))

    jits["final_exp"] = final_exp
    jits["check_tail"] = check_tail
    jits["layout"] = lay
    return jits


_OPS: dict = {}


def _get_ops(plane: bool = False, interpret: bool = False, eager: bool | None = None):
    if eager is None:
        eager = interpret
    key = (plane, interpret, eager)
    if key not in _OPS:
        _OPS[key] = make_pairing_ops(plane, interpret, eager)
    return _OPS[key]


def _pow2_pad(n: int) -> int:
    k = 1
    while k < n:
        k *= 2
    return k


# A fixed valid pad pair (the generators); padded lanes are masked to the
# identity after the Miller loop, so their value never matters — they only
# keep shapes in a small set of power-of-two sizes.
def _pad_pairs(pairs, target):
    from ..crypto.bls.curve import G1_GENERATOR, G2_GENERATOR

    return list(pairs) + [(G1_GENERATOR, G2_GENERATOR)] * (target - len(pairs))


def _fq2_batch(values) -> np.ndarray:
    from .bls_g2 import fq2_limbs_batch

    return fq2_limbs_batch(values)


def _pack_pairs(pairs, plane: bool):
    """[(G1 affine, G2 affine)] -> (px, py, qx, qy) in the layout."""
    px = _limbs_batch([p[0] for p, _ in pairs])
    py = _limbs_batch([p[1] for p, _ in pairs])
    qx = _fq2_batch([q[0] for _, q in pairs])
    qy = _fq2_batch([q[1] for _, q in pairs])
    if plane:
        px, py = px.T.copy(), py.T.copy()
        qx = np.ascontiguousarray(qx.transpose(2, 1, 0))
        qy = np.ascontiguousarray(qy.transpose(2, 1, 0))
    return px, py, qx, qy


def _fq12_tuples_from_planes(f: np.ndarray, n: int) -> list:
    """(32, 2, 3, 2, B) plane Fq12 batch -> host tuples for the first n."""
    return FQ.fq12_batch_from_limbs(f[..., :n], plane=True)


def miller_loop_batch(pairs, plane: bool | None = None):
    """Batched Miller loops on device -> list of host Fq12 tuples.

    ``pairs``: affine, non-infinity, subgroup-checked (P in G1, Q in G2).
    """
    if not pairs:
        return []
    import jax.numpy as jnp

    if plane is None:
        plane = _use_planes()
    n = len(pairs)
    padded = _pad_pairs(pairs, _pow2_pad(n))
    f = _get_ops(plane)["miller"](
        *[jnp.asarray(x) for x in _pack_pairs(padded, plane)]
    )
    f = np.asarray(f)
    if plane:
        return _fq12_tuples_from_planes(f, n)
    return [FQ.fq12_from_limbs(f[i]) for i in range(n)]


def pairing_product_is_one(pairs) -> bool:
    """Single check: prod e(P_i, Q_i) == 1, fully on device."""
    return pairing_products_are_one([pairs])[0]


def pairing_products_are_one(checks, plane: bool | None = None) -> list[bool]:
    """Batched pairing-product checks (one bool per inner pair list)."""
    if not checks:
        return []
    if plane is None:
        plane = _use_planes()
    kmax = _pow2_pad(max(len(c) for c in checks))
    g = _pow2_pad(len(checks))
    flat = []
    mask = np.zeros((g, kmax), bool)
    for i in range(g):
        chk = checks[i] if i < len(checks) else []
        mask[i, : len(chk)] = True
        flat.extend(_pad_pairs(chk, kmax))
    import jax.numpy as jnp

    ops = _get_ops(plane)
    f = ops["miller"](*[jnp.asarray(x) for x in _pack_pairs(flat, plane)])
    if plane:
        f = f.reshape(*f.shape[:-1], g, kmax)
    else:
        f = f.reshape(g, kmax, *f.shape[1:])
    ok = ops["check_tail"](f, jnp.asarray(mask))
    return [bool(v) for v in np.asarray(ok)[: len(checks)]]
