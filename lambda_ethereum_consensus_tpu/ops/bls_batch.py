"""Chained device RLC batch verification (the whole check on device).

Round 1 ran each device stage through the host: ladder -> pull affine ints
-> host group adds -> repack -> Miller -> check.  Every pull is a
synchronization point, so the kernel speed never reached the API.  This
module chains every stage ON DEVICE — the host packs limb planes once and
pulls back C booleans:

    ladders (r_i * pk_i, r_i * sig_i)           [RLC-width plane ladders]
    -> gather into (check, group, slot) rectangles
    -> Jacobian tree reductions (group pk sums, per-check sig sum)
    -> batched Fermat normalization (Jacobian -> affine, no host inversion)
    -> Miller loop over (check, group+1) pairs    [ops/bls_pairing]
    -> masked per-check product, shared final exponentiation, == 1

Grouping by message mirrors ``crypto/bls/batch.py::verify_points`` (ref:
native/bls_nif/src/lib.rs:14-158 — the blst aggregate-verify API this
replaces): the pairing count per check is ``#distinct messages + 1``.

Infinity semantics: a group sum or signature sum that reduces to the point
at infinity contributes e(inf, Q) = 1, which the device path realizes by
masking that Miller slot to the Fq12 identity — the same value the true
pairing would take, so masking is semantics, not approximation.  Dead
(padding) slots use the same mask.

Shapes are padded to a small set (batch to the 1024-lane plane quantum,
slots/groups to powers of two) so jit caches stay warm across drains.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import numpy as np

from ..crypto.bls import curve as C
from ..crypto.bls.batch import _COEFF_BITS  # single soundness-width source
from ..telemetry import inc, span
from . import bigint as BI
from .bls_g1 import (
    _limbs_batch,
    _PLANE_QUANTUM as _QUANTUM,
    _scalar_bits_batch,
    _use_planes,
    g1_plane_field,
)
from .bls_g2 import fq2_limbs_batch, g2_plane_field
from .bls_pairing import _pow2_pad as _pow2

__all__ = [
    "chain_verify",
    "chain_verify_cached",
    "chain_verify_cached_planes",
    "chain_recheck",
    "LadderedPlanes",
    "CommitteeSide",
    "smaller_side",
    "aggregate_g1_chain",
    "DeviceCommitteeCache",
    "RegistryPlaneStore",
    "get_plane_store",
    "plane_store_stats",
]


def _g1_planes(points) -> tuple[np.ndarray, np.ndarray]:
    """[(x, y)] -> two (32, N) plane arrays."""
    bx = _limbs_batch([p[0] for p in points])
    by = _limbs_batch([p[1] for p in points])
    return np.ascontiguousarray(bx.T), np.ascontiguousarray(by.T)


def _g2_planes(points) -> tuple[np.ndarray, np.ndarray]:
    """[((x0,x1),(y0,y1))] -> two (32, 2, N) plane arrays."""
    bx = fq2_limbs_batch([p[0] for p in points])
    by = fq2_limbs_batch([p[1] for p in points])
    return (
        np.ascontiguousarray(bx.transpose(2, 1, 0)),
        np.ascontiguousarray(by.transpose(2, 1, 0)),
    )


def make_chain_ops(interpret: bool = False):
    """Build (and cache) the chained-stage functions for one backend mode."""
    import jax
    import jax.numpy as jnp

    from .bls_fq12 import get_fq12_plane_ops
    from .bls_pairing import _get_ops as get_pairing_ops
    from .ladder import make_jacobian_ops

    fq = get_fq12_plane_ops(interpret)
    g1f = g1_plane_field(interpret)
    g2f = g2_plane_field(interpret)
    g1j = make_jacobian_ops(g1f, eager=interpret)
    g2j = make_jacobian_ops(g2f, eager=interpret)
    pairing = get_pairing_ops(plane=True, interpret=interpret)
    if interpret:
        wrap = lambda f, name=None: f
    else:
        from .aot import aot_jit

        # every compiled program goes through the cross-process AOT
        # executable cache (ops/aot.py): a disk hit skips the tracing and
        # lowering that JAX's own persistent cache still pays
        wrap = lambda f, name=None: aot_jit(
            jax.jit(f), f"chain_{name or getattr(f, '__name__', 'fn')}"
        )

    def ladder_g1(bx, by, kbits, live):
        X, Y, Z, inf = g1j["ladder"]((bx, by), kbits)
        return X, Y, Z, inf | ~live

    def ladder_g2(bx, by, kbits, live):
        X, Y, Z, inf = g2j["ladder"]((bx, by), kbits)
        return X, Y, Z, inf | ~live

    def _norm_g1(X, Y, Z):
        """Jacobian -> affine via batched Fermat inversion (z=0 -> (0,0))."""
        zi = fq["fp_inv"](Z)
        zi2 = fq["mul"](zi, zi)
        return fq["mul"](X, zi2), fq["mul"](Y, fq["mul"](zi2, zi))

    def _norm_g2(X, Y, Z):
        zi = fq["fq2_inv"](Z)
        zi2 = fq["fq2_mul"](zi, zi)
        return fq["fq2_mul"](X, zi2), fq["fq2_mul"](Y, fq["fq2_mul"](zi2, zi))

    # -G1 generator, the fixed P of the signature-sum pair.
    _ng = C.g1.affine_neg(C.G1_GENERATOR)
    neg_g1_x = jnp.asarray(BI.to_limbs(_ng[0])[:, None, None])  # (32,1,1)
    neg_g1_y = jnp.asarray(BI.to_limbs(_ng[1])[:, None, None])

    # prep is HOST-COMPOSED from small jitted pieces rather than jitted
    # whole: its unrolled reduction levels + the Fermat scans in one XLA
    # program took >25 min to compile on the TPU backend, while each
    # piece below compiles in seconds and every intermediate stays on
    # device (no host pulls — the chain property that matters).
    jadd1 = wrap(g1j["jac_add"], "jadd1")
    jadd2 = wrap(g2j["jac_add"], "jadd2")
    norm_g1_j = wrap(_norm_g1, "norm_g1")
    norm_g2_j = wrap(_norm_g2, "norm_g2")

    def _tree_reduce_j(jadd, pt):
        X, Y, Z, inf = pt
        while X.shape[-1] > 1:
            a = (X[..., ::2], Y[..., ::2], Z[..., ::2], inf[..., ::2])
            b = (X[..., 1::2], Y[..., 1::2], Z[..., 1::2], inf[..., 1::2])
            X, Y, Z, inf = jadd(a, b)
        return X[..., 0], Y[..., 0], Z[..., 0], inf[..., 0]

    # Staged reductions for the compiled (TPU) path: every tree LEVEL is
    # a distinct program shape with its own trace and compile — a
    # lax.scan of one jac_add compiles once like the ladder.  Long axes
    # split sqrt-ways into two scans so the sequential step count stays
    # ~2*sqrt(S).
    def _scan_reduce(jac_add, pt):
        from jax import lax

        xs = tuple(jnp.moveaxis(v, -1, 0) for v in pt)
        init = tuple(v[0] for v in xs)
        rest = tuple(v[1:] for v in xs)

        def body(carry, elem):
            return jac_add(carry, elem), None

        carry, _ = lax.scan(body, init, rest)
        return carry

    def _staged_reduce_last(jac, pt):
        s = pt[0].shape[-1]
        if s == 1:
            return tuple(v[..., 0] for v in pt)
        s1 = 1
        while s1 * s1 < s:
            s1 *= 2
        if s1 * (s // s1) == s and s > 16:
            s2 = s // s1
            pt = tuple(
                v.reshape(*v.shape[:-1], s1, s2) for v in pt
            )
            pt = _scan_reduce(jac["jac_add"], pt)  # over s2 -> (..., s1)
        return _scan_reduce(jac["jac_add"], pt)

    reduce_g1_j = wrap(
        lambda X, Y, Z, inf: _staged_reduce_last(g1j, (X, Y, Z, inf)), "reduce_g1"
    )
    reduce_g2_j = wrap(
        lambda X, Y, Z, inf: _staged_reduce_last(g2j, (X, Y, Z, inf)), "reduce_g2"
    )

    def _reduce_last(which, pt):
        """interpret: eager pairwise tree (loops can't stage); compiled:
        one jitted scan-based program per operand shape."""
        if interpret:
            return _tree_reduce_j(jadd1 if which == 1 else jadd2, pt)
        return (reduce_g1_j if which == 1 else reduce_g2_j)(*pt)

    def _take_entries(jac, idx, axis):
        """Ladder outputs ``(X, Y, Z, inf)`` over the b lanes of the flat
        entry batch (lane axis ``axis`` of the coordinates), gathered
        into the rectangle of entry indices ``idx``.  An index past the
        last lane — the dead slot of :func:`_entry_budget` — reads the
        identity: ``inf`` True over zero coordinates.  Mode and fill are
        written out, not left to ``jnp.take``'s default: a clamp
        (``mode="clip"``, or ``x[:, idx]`` indexing) would read lane b - 1
        there, a live entry of a full batch, and add its key into every
        padded slot."""
        X, Y, Z, inf = jac
        flat = idx.reshape(-1)
        return (
            *(jnp.take(v, flat, axis=axis, mode="fill", fill_value=0)
              .reshape(*v.shape[:axis], *idx.shape) for v in (X, Y, Z)),
            jnp.take(inf, flat, axis=0, mode="fill", fill_value=True)
            .reshape(idx.shape),
        )

    def prep(jac1, jac2, idx_g1, idx_sig, h_x, h_y, static_live):
        """Gather + reduce + normalize + pack the Miller batch.

        jac1/jac2: ladder outputs over the b lanes of the flat entry batch.
        idx_g1: (c, m1, s) int32 entry indices per (check, group, slot);
        idx_sig: (c, e) indices per (check, slot); a dead slot holds an
        index past the last lane (b: the sentinel of ``_take_entries``,
        which reads the identity and costs the ladders no lane).
        h_x/h_y: (32, 2, c, m1) hashed message points; static_live: (c, m)
        host liveness (m = m1 + 1, slot m-1 is the signature pair).
        """
        return finish(
            # (32, c, m1, s) + (c, m1, s) -> (32, c, m1), (c, m1)
            _reduce_last(1, _take_entries(jac1, idx_g1, axis=1)),
            # (32, 2, c, e) + (c, e) -> (32, 2, c), (c,)
            _reduce_last(2, _take_entries(jac2, idx_sig, axis=2)),
            h_x, h_y, static_live,
        )

    def finish(group_jac, sig_jac, h_x, h_y, static_live):
        """Normalize reduced Jacobians and pack the (c, m) Miller batch:
        groups in slots 0..m1-1, the signature pair last.  Shared by the
        single-device prep and the sharded pipeline (which produces the
        reduced Jacobians via per-device partial sums + all_gather)."""
        gX, gY, gZ, ginf = group_jac
        sX, sY, sZ, sinf = sig_jac
        c = gX.shape[1]
        px_g, py_g = norm_g1_j(gX, gY, gZ)
        qx_s, qy_s = norm_g2_j(sX, sY, sZ)
        px = jnp.concatenate([px_g, jnp.broadcast_to(neg_g1_x, (32, c, 1))], -1)
        py = jnp.concatenate([py_g, jnp.broadcast_to(neg_g1_y, (32, c, 1))], -1)
        qx = jnp.concatenate([h_x, qx_s[..., None]], -1)
        qy = jnp.concatenate([h_y, qy_s[..., None]], -1)
        inf_all = jnp.concatenate([ginf, sinf[:, None]], -1)  # (c, m)
        mask = static_live & ~inf_all
        return px, py, qx, qy, mask

    one_plane = jnp.asarray(BI.to_limbs(1))  # (32,) limb planes of 1

    def _ones_like(bx):
        return jnp.broadcast_to(
            one_plane.reshape(32, *([1] * (bx.ndim - 1))), bx.shape
        )

    def _reduce_inline(jac, pt):
        """Reduce-last for use INSIDE a to-be-jitted body (compiled mode)
        or eagerly (interpret mode) — unlike ``_reduce_last`` this never
        routes through another aot_jit wrapper."""
        if interpret:
            return _tree_reduce_j(jac["jac_add"], pt)
        return _staged_reduce_last(jac, pt)

    def committee_sums(rx, ry, idx, inf):
        """Full-committee pubkey sums from the device registry.

        ``rx/ry``: (32, N) registry coordinate planes.  ``idx``: (C, kp)
        member indices (kp pow2-padded; padded slots carry ``inf`` True).
        Returns affine (32, C) sums — the once-per-epoch precompute that
        replaces the per-drain 8.3M-point gather (VERDICT r3 weak #1).
        """
        c, kp = idx.shape
        gx = jnp.take(rx, idx.reshape(-1), axis=1).reshape(-1, c, kp)
        gy = jnp.take(ry, idx.reshape(-1), axis=1).reshape(-1, c, kp)
        X, Y, Z, _ = _reduce_inline(g1j, (gx, gy, _ones_like(gx), inf))
        return _norm_g1(X, Y, Z)

    def agg_corrected(rx, ry, sum_x, sum_y, comm_ids, idx, idx_inf, attesting):
        """Per-entry aggregate pubkeys from the SMALLER side of each
        committee: ``full_sum - missing_members`` where ``attesting[e]``
        is False, ``identity + attesting_members`` where it is True.

        Committee membership is fixed per epoch, so each drain only pays a
        gather over the shorter of an entry's two index lists: ``idx``
        (E, w) registry indices (dead slots flagged in ``idx_inf``),
        ``comm_ids`` (E,) committee of each entry.  One body, one program
        per width ``w`` (:meth:`DeviceCommitteeCache.widths`).  Returns
        affine (32, E) points plus an (E,) infinity mask (an
        empty-participation entry reduces to infinity; callers must mark
        it dead).
        """
        e, w = idx.shape
        gx = jnp.take(rx, idx.reshape(-1), axis=1).reshape(-1, e, w)
        gy = jnp.take(ry, idx.reshape(-1), axis=1).reshape(-1, e, w)
        X, Y, Z, sinf = _reduce_inline(
            g1j, (gx, gy, _ones_like(gx), idx_inf)
        )
        fx = jnp.take(sum_x, comm_ids, axis=1)  # (32, E)
        fy = jnp.take(sum_y, comm_ids, axis=1)
        # the base point: the committee sum, or the identity (flagged
        # infinity) where the listed members are the participants
        base = (fx, fy, _ones_like(fx), attesting)
        # -missing: Jacobian negation is (X, -Y, Z)
        Ys = jnp.where(attesting[None, :], Y, fq["neg"](Y))
        X3, Y3, Z3, inf3 = g1j["jac_add"](base, (X, Ys, Z, sinf))
        ax, ay = _norm_g1(X3, Y3, Z3)
        return ax, ay, inf3

    def single_gather(rx, ry, idx):
        """Per-entry pubkeys of single-signer entries: column ``idx[e]``
        of the device registry planes, (32, E) affine.  No sum and no
        correction — an unaggregated vote's pubkey IS its attester's
        registry key, which already lies on the device."""
        return jnp.take(rx, idx, axis=1), jnp.take(ry, idx, axis=1)

    def single_merge(ax, ay, ainf, sx, sy, is_single):
        """A mixed drain's pubkey planes: the gathered key where the
        entry is a single signer, the corrected committee aggregate
        elsewhere (registry keys are never the identity)."""
        pick = is_single[None, :]
        return (
            jnp.where(pick, sx, ax),
            jnp.where(pick, sy, ay),
            ainf & ~is_single,
        )

    def aggregate_g1(bx, by, inf):
        # operands arrive pow2-padded along the reduce axis (host side:
        # aggregate_g1_chain) so the jit cache is keyed on padded shapes;
        # host-composed per level like prep (one giant jit of the
        # unrolled reduction is the >25-min-compile failure mode)
        bx, by, inf = jnp.asarray(bx), jnp.asarray(by), jnp.asarray(inf)
        z = jnp.broadcast_to(
            jnp.asarray(BI.to_limbs(1)).reshape(32, *([1] * (bx.ndim - 1))),
            bx.shape,
        )
        X, Y, Z, _ = _reduce_last(1, (bx, by, z, inf))
        return norm_g1_j(X, Y, Z)

    return {
        "ladder_g1": wrap(ladder_g1, "ladder_g1"),
        "ladder_g2": wrap(ladder_g2, "ladder_g2"),
        "committee_sums": wrap(committee_sums, "committee_sums"),
        "agg_corrected": wrap(agg_corrected, "agg_corrected"),
        "single_gather": wrap(single_gather, "single_gather"),
        "single_merge": wrap(single_merge, "single_merge"),
        # host-composed (see comment above prep) — pieces are jitted
        "prep": prep,
        "take_entries": _take_entries,
        "finish": finish,
        "jadd1": jadd1,
        "jadd2": jadd2,
        # the compiled path's prep pieces, by program name
        "reduce_g1": reduce_g1_j,
        "reduce_g2": reduce_g2_j,
        "norm_g1_jit": norm_g1_j,
        "norm_g2_jit": norm_g2_j,
        # unjitted scan-based reducers for shard_map bodies (compile as
        # one program per shape — see the compile-latency note above)
        "staged_reduce_g1": lambda pt: _staged_reduce_last(g1j, pt),
        "staged_reduce_g2": lambda pt: _staged_reduce_last(g2j, pt),
        "aggregate_g1": aggregate_g1,
        "miller": pairing["miller"],
        "check_tail": pairing["check_tail"],
        "tree_reduce": _tree_reduce_j,
        "norm_g1": _norm_g1,
        "g1j": g1j,
        "g2j": g2j,
        "wrap": wrap,
    }


_CHAIN_OPS: dict = {}


def _get_chain_ops(interpret: bool = False):
    if interpret not in _CHAIN_OPS:
        _CHAIN_OPS[interpret] = make_chain_ops(interpret)
    return _CHAIN_OPS[interpret]


def chain_verify(
    checks, interpret: bool | None = None, coeff_bits: int = _COEFF_BITS
) -> list[bool]:
    """Verify C independent RLC pairing-product checks in one device chain.

    Each check is ``(entries, h_points, group_ids)``:

    - ``entries``: list of ``(pk_xy, sig_xy, coeff)`` — G1 affine int pair,
      G2 affine Fq2 pair, RLC coefficient in [1, 2^coeff_bits).
      ``coeff_bits`` defaults to ``BLS_RLC_BITS`` (64 — ~2^-64 forgery
      slip per batch, the deployed batch-verification width; see
      crypto/bls/batch.py); tests shorten it to cut ladder steps.
    - ``h_points``: G2 affine int pairs, one per message group.
    - ``group_ids``: per-entry group index into ``h_points``.

    Returns one bool per check:  prod_g e(sum_{i in g} r_i pk_i, H_g)
    * e(-g1, sum_i r_i sig_i) == 1.  Points must be on-curve and
    subgroup-checked by the caller (decoders do this); entries with
    infinity points must be filtered by the caller.
    """
    import jax.numpy as jnp

    if interpret is None:
        # Pallas plane kernels need a real TPU (and honor the
        # BIGINT_NO_PALLAS kill-switch like every other plane router);
        # everywhere else the same chain runs through the CPU-testable
        # einsum delegation.
        interpret = not _use_planes()

    n_checks = len(checks)
    if n_checks == 0:
        return []

    with span("bls_host_pack"):  # host side up to the first dispatch
        flat_pk, flat_sig, flat_coeff = [], [], []
        for entries, _, _ in checks:
            for pk, sig, coeff in entries:
                flat_pk.append(pk)
                flat_sig.append(sig)
                flat_coeff.append(coeff)
        n = len(flat_pk)
        layout = _chain_layout(checks, interpret)
        b = layout.b
        _count_entries(b, points=n)

        # Flat entry planes, padded with the generator in the lanes past n
        # (``live`` False; none where the call fills its budget).
        pad = b - n
        pkx, pky = _g1_planes(flat_pk + [C.G1_GENERATOR] * pad)
        sgx, sgy = _g2_planes(flat_sig + [C.G2_GENERATOR] * pad)
        live = np.zeros(b, bool)
        live[:n] = True
        kbits = jnp.asarray(_scalar_bits_batch(flat_coeff + [1] * pad, coeff_bits).T)
        pkx, pky, sgx, sgy, live = (jnp.asarray(a) for a in (pkx, pky, sgx, sgy, live))

    # the chain's program calls and the layout packing between them: the
    # device works meanwhile (in interpret mode the math itself runs here)
    with span("bls_dispatch"):
        ops = _get_chain_ops(interpret)
        jac1 = ops["ladder_g1"](pkx, pky, kbits, live)
        jac2 = ops["ladder_g2"](sgx, sgy, kbits, live)
        ok = _dispatch_tail(ops, jac1, jac2, _tail_operands(checks, layout))
    return _fetch_flags(ok)


def _count_entries(b: int, **by_shape: int) -> None:
    """Book the entries entering a chained verify by the shape their
    pubkeys take (``bls_chain_entries_total{shape}``) and the ``b`` lanes
    they are dispatched at by what a lane holds
    (``bls_chain_lanes_total{use}``: ``live`` an entry, ``pad`` nothing —
    the share of the aggregation's and the ladders' per-lane cost that
    verifies no signature): once per call that ladders its entries —
    which path verified what, at what fill.  A re-check on a flush's
    laddered planes (:func:`chain_recheck`) books nothing here: no entry
    enters a ladder there."""
    for shape, n in by_shape.items():
        if n:
            inc("bls_chain_entries_total", value=n, shape=shape)
    live = sum(by_shape.values())
    for use, lanes in (("live", live), ("pad", b - live)):
        if lanes:
            inc("bls_chain_lanes_total", value=lanes, use=use)


def _entry_budget(n: int, interpret: bool) -> tuple[int, int]:
    """Padded flat-entry batch size and the canonical dead-slot index.

    ``b`` is the smallest multiple of the quantum that holds ``n`` (one
    quantum at least), so a full flush — n a multiple of the quantum —
    has no padding lane at all.  The dead slot is index ``b``, one past
    the last lane: ``prep``'s gathers read the identity there, and the
    ladders, which cost per lane, carry no lane for it.  The 1024-lane
    quantum only matters for the Pallas tiles; the CPU-testable mode
    keeps batches tiny.
    """
    q = _QUANTUM if not interpret else 8
    b = max(-(-n // q), 1) * q
    return b, b


class ChainLayout(NamedTuple):
    """The shape key of one chained verify: its six programs (aggregation
    or gather, two ladders, prep, Miller, masked product) are compiled per
    value of it, so every distinct layout is a set of programs to load —
    or, cold, to compile — inside the call that first meets it."""

    b: int  # padded flat-entry budget (aggregation, ladders)
    checks: int  # checks of the call (1 but for a bisection level)
    m1: int  # message groups per check (pow2 - 1; slot m1 is the sig pair)
    s: int  # entries per group (pow2)
    e: int  # entries per check (pow2)


# Layouts whose programs a warmer has dispatched (node/warmup.py: the
# drain's and the bisection ladder below it).  A call that fits inside one
# is padded up to it with dead entries, groups and slots, so a flush of ANY
# size below the warmed drain — a deadline flush, a slot's ragged tail —
# and every bisection level of a flush that holds one bad entry run the
# programs already resident instead of a set of their own.
_WARMED_LAYOUTS: set[ChainLayout] = set()


def register_chain_layout(layout: ChainLayout) -> None:
    """Advertise that the chain's programs at ``layout`` are warmed (or
    about to be: a warmer registers before its background dispatch, as it
    does its shape buckets)."""
    _WARMED_LAYOUTS.add(ChainLayout(*(int(v) for v in layout)))


def warmed_chain_layouts() -> tuple[ChainLayout, ...]:
    """The registered layouts, smallest first (``/debug/compile``)."""
    return tuple(sorted(_WARMED_LAYOUTS))


def _chain_layout(checks, interpret: bool, b: int | None = None) -> ChainLayout:
    """The layout ``checks`` are dispatched at: the smallest warmed layout that holds the call on every axis, else
    the call's own (each axis pow2-padded, the entry budget by
    :func:`_entry_budget`).  Padding is what every axis already carries
    up to its pow2 — dead entries (``live`` False: only where the call
    does not fill its last quantum, or is padded up to a warmed layout),
    empty groups (``static_live`` False), dead slots — so a padded call's
    verdicts are its own layout's.  The dead slots hold the layout's
    ``b``, the index one past its last lane (``b >= n``: a full call has
    no lane to spare), which ``prep`` reads as the identity.  A re-check
    (:func:`chain_recheck`) passes the ``b`` of the planes it reads: its
    layout keeps that budget, and only a warmed layout at that very ``b``
    holds it.  Books
    ``bls_chain_layouts_total{layout="warmed"|"own"}``: an ``own`` layout is
    a program set that no warmer loaded, compiled or loaded inside the call."""
    fixed_b = b
    if b is None:
        b, _dead = _entry_budget(sum(len(entries) for entries, _, _ in checks), interpret)
    max_groups = max(max((len(h) for _, h, _ in checks), default=1), 1)
    max_slot = 1
    for _, h_points, group_ids in checks:
        if h_points:
            counts = np.bincount(np.asarray(group_ids, np.int64), minlength=1)
            max_slot = max(max_slot, int(counts.max()))
    own = ChainLayout(
        b=b,
        checks=len(checks),
        m1=_pow2(max_groups + 1) - 1,
        s=_pow2(max_slot),
        e=_pow2(max((len(c[0]) for c in checks), default=1) or 1),
    )
    fits = [w for w in _WARMED_LAYOUTS
            if w.checks == own.checks and all(x >= y for x, y in zip(w, own))
            and fixed_b in (None, w.b)]
    inc("bls_chain_layouts_total", layout="warmed" if fits else "own")
    return min(fits, default=own)


def _tail_operands(checks, layout: ChainLayout, offsets=None):
    """The host planes of a chained verify's tail, uploaded: the (check,
    group, slot) and (check, slot) rectangles of lane indices, the hashed
    message points and the static liveness.

    ``checks`` supplies only the entry counts, h_points and group_ids
    here, ``layout`` (:func:`_chain_layout`) the padded rectangles, whose
    dead slots hold ``layout.b`` — past the last lane, the identity.
    Entry ``ei`` of check ``ci`` reads lane ``offsets[ci] + ei``: by
    default the running sum of the entry counts (the checks' entries
    laddered in order); a re-check passes each range's first index in
    the flush whose planes it reads."""
    import jax.numpy as jnp

    n_checks, m1, s, e = layout.checks, layout.m1, layout.s, layout.e
    dead = layout.b  # one past the last lane: prep reads the identity there
    if offsets is None:
        offsets, off = [], 0
        for entries, _, _ in checks:
            offsets.append(off)
            off += len(entries)

    idx_g1 = np.full((n_checks, m1, s), dead, np.int32)
    idx_sig = np.full((n_checks, e), dead, np.int32)
    static_live = np.zeros((n_checks, m1 + 1), bool)
    for ci, (entries, h_points, group_ids) in enumerate(checks):
        fill = [0] * len(h_points)
        for ei, g in enumerate(group_ids):
            idx_g1[ci, g, fill[g]] = offsets[ci] + ei
            fill[g] += 1
        for ei in range(len(entries)):
            idx_sig[ci, ei] = offsets[ci] + ei
        static_live[ci, : len(h_points)] = [c > 0 for c in fill]
        static_live[ci, m1] = len(entries) > 0

    # Pack the hashed message points as (32, 2, C, m1); dead slots reuse
    # the generator (masked out after the Miller loop).
    h_points_padded = []
    for ci, (_, h_points, _) in enumerate(checks):
        row = list(h_points) + [C.G2_GENERATOR] * (m1 - len(h_points))
        h_points_padded.extend(row)
    hx, hy = _g2_planes(h_points_padded)
    hx = hx.reshape(32, 2, n_checks, m1)
    hy = hy.reshape(32, 2, n_checks, m1)
    return tuple(jnp.asarray(a) for a in (idx_g1, idx_sig, hx, hy, static_live))


def _dispatch_tail(ops, jac1, jac2, operands):
    """The shared back half of every chained verify: ``prep`` gathers the
    laddered entries into (check, group, slot) rectangles and reduces
    them, then ``miller`` and ``check_tail`` — dispatched, one boolean per
    check still on the device (:func:`_fetch_flags` pulls them back).  The
    laddered planes arrive as ``jac1``/``jac2`` from host-packed points
    (:func:`chain_verify`) or the epoch committee cache
    (:func:`chain_verify_cached_planes`, whose planes
    :func:`chain_recheck` reads again); ``operands`` from
    :func:`_tail_operands`."""
    px, py, qx, qy, mask = ops["prep"](jac1, jac2, *operands)
    # miller preserves the (C, m) batch shape; the group axis is already
    # innermost, exactly what check_tail's masked product reduces.
    f = ops["miller"](px, py, qx, qy)
    return ops["check_tail"](f, mask)


def _fetch_flags(ok) -> list[bool]:
    """Everything is dispatched: the host blocks on the chip for the
    per-check booleans."""
    with span("bls_device_wait"):
        flags = np.asarray(ok)
    return [bool(v) for v in flags]


class CommitteeSide(NamedTuple):
    """Registry indices of ONE side of a committee aggregate and which
    side they are — the participants (``attesting`` True: summed from the
    identity) or the missing members (False: subtracted from the cached
    committee sum)."""

    indices: Sequence[int]
    attesting: bool


def smaller_side(attesting, missing) -> CommitteeSide:
    """The side of a committee aggregate the device sums over: whichever
    index list is shorter (ties: subtract the missing), so the gather is
    never wider than half the committee."""
    if len(attesting) < len(missing):
        return CommitteeSide(attesting, True)
    return CommitteeSide(missing, False)


def _pack_members(cache: "DeviceCommitteeCache", flat, b: int):
    """Index and mask planes of one cached call, at its width bucket.

    ``flat``: the call's entries; ``b``: the padded entry budget.  Returns
    ``(cid, is_single, idx, idx_inf, attesting)`` — ``cid`` the entry's
    committee or, for a single signer, its registry index; ``idx`` /
    ``idx_inf`` (b, w) the listed members with dead slots flagged
    (``None`` where every entry is a single signer); ``attesting`` (b,)
    which side each list is.  ``w`` is the smallest of ``cache.widths``
    that holds the call's longest list: read from the miss counts, never
    from a flag.  Books ``bls_agg_entries_total{width, side}`` (a re-check
    on the first check's planes packs no members and books none).
    """
    cid = np.zeros(b, np.int32)
    is_single = np.zeros(b, bool)
    attesting = np.zeros(b, bool)
    rows, lists = [], []
    for i, (comm_id, members, _, _) in enumerate(flat):
        cid[i] = comm_id
        if members is None:
            is_single[i] = True
            continue
        if isinstance(members, CommitteeSide):
            members, attesting[i] = members
        rows.append(i)
        lists.append(members)
    if not rows:
        return cid, is_single, None, None, attesting
    counts = np.fromiter((len(m) for m in lists), np.int64, len(lists))
    longest = int(counts.max())
    if longest > cache.wmax:
        i = rows[int(counts.argmax())]
        raise ValueError(
            f"entry {i}: {longest} missing members exceeds cache capacity "
            f"{cache.wmax}"
        )
    w = next(w for w in cache.widths if w >= longest)
    # one scatter for the whole call: row r's list lands in idx[r, :len]
    live = np.zeros((b, w), bool)
    live[rows] = np.arange(w)[None, :] < counts[:, None]
    idx = np.zeros((b, w), np.int32)
    if longest:
        idx[live] = np.concatenate([np.asarray(m, np.int32) for m in lists])
    idx_inf = ~live
    on_side = attesting[rows]
    for side, count in (("attesting", int(on_side.sum())),
                        ("missing", int((~on_side).sum()))):
        if count:
            inc("bls_agg_entries_total", value=count, width=str(w), side=side)
    return cid, is_single, idx, idx_inf, attesting


class LadderedPlanes(NamedTuple):
    """The laddered entry planes of one cached chained verify, still on the
    device: ``r_i * pk_i`` (``jac1``) and ``r_i * sig_i`` (``jac2``) as
    Jacobian coordinates plus infinity flags, lane ``i`` for the call's
    entry ``i`` over its ``b`` lanes (a dead lane reads the identity).  No
    chain program donates its inputs, so a tail may read them any number
    of times (:func:`chain_recheck`)."""

    jac1: tuple
    jac2: tuple
    b: int


def chain_verify_cached(
    cache: "DeviceCommitteeCache",
    checks,
    interpret: bool | None = None,
    coeff_bits: int = _COEFF_BITS,
) -> list[bool]:
    """:func:`chain_verify` with aggregate pubkeys taken from the epoch
    committee cache instead of host-packed points — the node-path drain
    (VERDICT r4 next #1: the production attestation path must run the
    machinery the headline measures).

    ``checks`` is an iterable (consumed once, under the ``bls_host_pack``
    span); each check is ``(entries, h_points, group_ids)`` where an
    entry is ``(comm_id, members, sig_xy, coeff)``:

    - ``comm_id``: the entry's committee index into the cache;
    - ``members``: a :class:`CommitteeSide` — registry indices of the
      SHORTER of the committee's two sides and which side they are
      (:func:`smaller_side`) — or a plain sequence, read as the
      NON-participating members.  A list longer than ``cache.wmax``
      (half the committee, pow2-padded) raises ``ValueError``: the
      caller broke the smaller-side contract;
    - ``sig_xy``/``coeff``: as in :func:`chain_verify`.

    A **single-signer** entry is ``(validator_index, None, sig_xy,
    coeff)``: its pubkey is column ``validator_index`` of the registry
    planes the cache already holds on the device (``single_gather``) —
    no committee sum, no correction table, no host point.  A call may mix
    both shapes; each entry is routed by its own ``members``.

    The aggregate pubkey never touches the host at ANY participation:
    ``full_sum[comm_id] - sum(missing)`` or ``sum(attesting)`` —
    whichever list is shorter — is computed on device and flows straight
    into the RLC ladder.  The gather width of the call is the smallest
    of ``cache.widths`` that holds its longest list, so a call of
    high-participation aggregates dispatches the ``mmax``-wide program
    and only a call that carries a sparser aggregate pays a wider one
    (a call that mixes both is padded to its widest entry: one program
    per call).  Callers must pre-reject empty-participation entries
    (their aggregate is the infinity point, invalid per the spec's
    fast-aggregate-verify preconditions).
    """
    return chain_verify_cached_planes(cache, checks, interpret, coeff_bits)[0]


def chain_verify_cached_planes(
    cache: "DeviceCommitteeCache",
    checks,
    interpret: bool | None = None,
    coeff_bits: int = _COEFF_BITS,
) -> tuple[list[bool], LadderedPlanes | None]:
    """:func:`chain_verify_cached`, handing back the call's laddered planes
    beside its flags (``None`` where the call holds no check): the first
    check of a flush that bisects keeps them, and every level after it
    re-checks its ranges on them (:func:`chain_recheck`)."""
    import jax.numpy as jnp

    # batch quantization and op set must match the ops the CACHE compiled
    # with — a caller-supplied flag that disagrees would feed wrongly
    # padded batches into the other backend's programs
    if interpret is None:
        interpret = cache._interpret
    elif interpret != cache._interpret:
        raise ValueError(
            f"interpret={interpret} conflicts with the cache's "
            f"interpret={cache._interpret}"
        )
    # host side up to the first dispatch, under one span.  ``checks`` may be
    # a generator: the caller's hash-to-G2 and entry packing then run here
    with span("bls_host_pack"):
        checks = list(checks)
        if not checks:
            return [], None

        flat = [entry for entries, _, _ in checks for entry in entries]
        n = len(flat)
        layout = _chain_layout(checks, interpret)
        b = layout.b
        pad = b - n

        with span("agg_index_pack"):  # the index and mask planes of the call
            cid, is_single, idx, idx_inf, attesting = _pack_members(cache, flat, b)
        n_single = int(is_single.sum())
        _count_entries(b, single=n_single, committee=n - n_single)

        sgx, sgy = _g2_planes([sig for _, _, sig, _ in flat] + [C.G2_GENERATOR] * pad)
        live = np.zeros(b, bool)
        live[:n] = True
        kbits = jnp.asarray(_scalar_bits_batch(
            [coeff for _, _, _, coeff in flat] + [1] * pad, coeff_bits
        ).T)
        sgx, sgy, live = jnp.asarray(sgx), jnp.asarray(sgy), jnp.asarray(live)

    with span("bls_dispatch"):
        ops = cache._ops
        if n_single == n:
            # a subnet drain: every pubkey is one registry column
            agg_x, agg_y = cache.gather_single(cid)
            g1_live = live  # a registry key is never the identity
        else:
            comm_ids = np.where(is_single, 0, cid) if n_single else cid
            agg_x, agg_y, agg_inf = cache.aggregate(comm_ids, idx, idx_inf, attesting)
            if n_single:  # a mixed drain: both programs at b, then a select
                sx, sy = cache.gather_single(np.where(is_single, cid, 0))
                agg_x, agg_y, agg_inf = ops["single_merge"](
                    agg_x, agg_y, agg_inf, sx, sy, jnp.asarray(is_single)
                )
            # aggregate()'s contract: infinity aggregates MUST be marked dead.
            # Killing only the G1 lane (the signature lane stays live) leaves
            # the check with a signature term and no matching pubkey term, so
            # it deterministically FAILS and bisection blames the entry — the
            # spec verdict for an infinity aggregate pubkey with a non-infinity
            # signature (empty participation is pre-rejected by callers; a
            # crafted identity-sum needs sks the depositor cannot prove).
            g1_live = live & ~agg_inf
        jac1 = ops["ladder_g1"](agg_x, agg_y, kbits, g1_live)
        jac2 = ops["ladder_g2"](sgx, sgy, kbits, live)
        # layout builder only reads len(entries)/h_points/group_ids — the
        # cached-entry tuples carry the same positional layout contract
        ok = _dispatch_tail(ops, jac1, jac2, _tail_operands(checks, layout))
    return _fetch_flags(ok), LadderedPlanes(jac1, jac2, b)


def chain_recheck(
    cache: "DeviceCommitteeCache", planes: LadderedPlanes, checks, offsets
) -> list[bool]:
    """Re-check ranges of a flush on the laddered planes of its first check:
    the chain's tail alone — ``prep`` -> ``miller`` -> ``check_tail``.

    ``planes``: the first check's (:func:`chain_verify_cached_planes`),
    whose one check held the whole flush, entry ``i`` in lane ``i``.
    ``checks``: an iterable of ``(entries, h_points, group_ids)``, of whose
    entries only the count is read; ``offsets``: each check's first index
    in the flush — a bisection range is a contiguous slice of it, so entry
    ``ei`` of check ``ci`` reads lane ``offsets[ci] + ei``.  No member
    index planes, signature limbs or scalar bits are packed, no coefficient
    is drawn, and neither aggregation nor ladder runs: the call is
    dispatched at the planes' ``b`` (:func:`_chain_layout`), a rung of the
    warmed bisection ladder where the warmer holds one.

    Soundness.  The coefficients ``r_i`` are drawn once a flush, by its
    first check: secret, uniform, odd and 64 bits wide (``BLS_RLC_BITS``),
    never revealed.  The check of a subset S is still the small-exponents
    batch test over S: ``prod_g e(sum_{i in S, g} r_i pk_i, H_g) *
    e(-g1, sum_{i in S} r_i sig_i) == 1``.  A subset of valid entries
    passes whatever the coefficients.  A false pass needs ``sum_{i in S
    and Bad} r_i d_i = 0 (mod q)``, ``d_i != 0`` entry i's error exponent:
    impossible with one bad entry in S (``r_i`` is odd and smaller than
    ``q``), probability at most 2^-63 over the draw for a fixed subset
    with more.  Which subsets bisection judges depends on earlier
    outcomes, and so on the same ``r_i``; but every one is a range of the
    flush's halving tree, a family fixed before the draw with at most
    ``2n`` members.  A union bound over that family bounds a false pass
    anywhere in the flush by ``2n * 2^-63``.  So the per-entry flags are
    what a fresh draw at every level gives, up to that bound; only the
    work differs.

    Books ``bls_recheck_planes_total{planes="reused"}`` (one a bisection
    level) and ``bls_chain_layouts_total`` as every chained call does, and
    nothing into ``bls_chain_entries_total``, ``bls_chain_lanes_total`` or
    ``bls_agg_entries_total``: no entry enters a ladder here."""
    with span("bls_host_pack"):  # hash-to-G2, the rectangles, the h planes
        checks = list(checks)
        if not checks:
            return []
        inc("bls_recheck_planes_total", planes="reused")
        layout = _chain_layout(checks, cache._interpret, b=planes.b)
        operands = _tail_operands(checks, layout, offsets)
    with span("bls_dispatch"):
        ok = _dispatch_tail(cache._ops, planes.jac1, planes.jac2, operands)
    return _fetch_flags(ok)


def aggregate_g1_chain(points_planes, interpret: bool | None = None):
    """Tree-reduce G1 points on device: (32, ..., K) -> affine (32, ...).

    The committee-aggregation stage (eth_fast_aggregate_verify's pubkey
    sum, ref lib/bls.ex:7-50): K affine points per lane reduce to one
    affine point with no host inversion.  Input planes must carry no
    infinities (callers validate pubkeys); output lanes that reduce to
    infinity come back as (0, 0).

    The reduce axis is pow2-padded HERE (host side, with infinity
    entries) so that all K in (kp/2, kp] share one compiled program —
    _tree_reduce's pairwise halving would silently double-count an odd
    split, and padding inside the jit would key the compile cache on
    every distinct raw K.
    """
    if interpret is None:
        interpret = not _use_planes()
    bx, by = points_planes
    k = bx.shape[-1]
    kp = _pow2(k)
    pad = [(0, 0)] * (bx.ndim - 1) + [(0, kp - k)]
    bx = np.pad(np.asarray(bx), pad)
    by = np.pad(np.asarray(by), pad)
    inf = np.zeros(bx.shape[1:], bool)
    inf[..., k:] = True
    ops = _get_chain_ops(interpret)
    return ops["aggregate_g1"](bx, by, inf)


class RegistryPlaneStore:
    """Per-chain shared device-resident registry pubkey planes.

    Every :class:`DeviceCommitteeCache` used to upload its own copy of the
    full registry planes (256 B/validator: 2 coords x 32 int32 limb
    planes), so the up-to-14 live epoch contexts pinned
    O(contexts x registry) duplicated immutable device memory — multiple
    GB at mainnet scale.  A validator's pubkey
    never changes once registered, so one chain needs exactly ONE device
    copy: this store owns it, every cache on the chain references the same
    buffer, and device memory for registry data is O(registry).

    Growth policy: capacity is padded to power-of-two column counts, so

    - a deposit that grows the registry within capacity writes only the new
      columns into the existing allocation (``dynamic_update_slice`` — the
      resident prefix never re-crosses the host/device link), and
    - a growth past capacity concatenates the on-device prefix with the new
      columns plus fresh zero padding (again only the delta is uploaded),
      doubling capacity so uploads amortize and the jitted gather programs
      keyed on the (32, capacity) operand shape stay warm across deposits.

    Invalidation: incoming host planes are compared against the retained
    host reference over the OVERLAPPING prefix (memcmp-fast numpy, O(n) at
    cache-build frequency — once per epoch context, never per drain).  An
    older state's shorter-but-consistent view of the same append-only
    registry — the common case when a previous-epoch target context builds
    after a deposit grew the registry — is served from the existing buffer
    as-is; only a genuine prefix mutation (synthetic/test registries) drops
    the buffer and bumps ``version``.  Caches built against a dropped
    buffer keep their (still internally consistent) reference until
    evicted.
    """

    def __init__(self, interpret: bool | None = None, min_capacity: int = 1024):
        if interpret is None:
            interpret = not _use_planes()
        self._interpret = interpret
        self._min_cap = max(1, int(min_capacity))
        self.count = 0  # live registry columns
        self.capacity = 0  # allocated columns (power of two)
        self.rx = None  # jnp (32, capacity) — THE shared buffer
        self.ry = None
        self.version = 0  # bumped on prefix invalidation
        self.uploaded_cols = 0  # telemetry: host->device columns shipped
        # host-side reference of what was uploaded (a live view the
        # per-chain planes cache holds anyway — no copy)
        self._host_rx = None
        self._host_ry = None
        # mesh-sharded placement (round 11): the registry column axis is
        # dealt over ``dp`` so an 8-chip mesh pins 1/8 of the planes per
        # chip and the committee gathers read mostly-local shards.
        # Decided once at construction — re-deciding per update() would
        # bounce the resident buffer between layouts.
        from .mesh import shard_plane_store_enabled

        self._sharded = shard_plane_store_enabled()

    def _place(self, name: str, arr):
        """Pin a (32, capacity) plane buffer in the layout the round-21
        partition-rule table legislates for ``name`` (``registry/rx`` /
        ``registry/ry`` — column-sharded over the mesh; capacity is pow2
        so it always divides the pow2 ``dp`` axis), resident-as-is when
        the store is unsharded."""
        if not self._sharded:
            return arr
        from . import shard_rules

        return shard_rules.place(name, arr)

    def shard_devices(self) -> int:
        """Live mesh-device spread of the resident planes (1 =
        replicated/unsharded) — read from the buffer's sharding, never
        the construction-time intent."""
        if self.rx is None:
            return 1
        try:
            return max(1, len(self.rx.sharding.device_set))
        except AttributeError:
            return 1

    @property
    def resident_bytes(self) -> int:
        """Device bytes pinned by the shared planes (both coordinates) —
        independent of how many caches reference them."""
        if self.rx is None:
            return 0
        return int(self.rx.nbytes) + int(self.ry.nbytes)

    def update(self, rx, ry):
        """Grow the device planes to cover the host planes ``(rx, ry)``
        (numpy, (32, n)); returns ``(rx_dev, ry_dev)`` — the full-capacity
        shared buffers.  Only columns beyond the cached count are uploaded;
        a shorter consistent view is served from the existing buffer, and
        a mutated prefix invalidates (version bump + full re-upload)."""
        import jax.numpy as jnp

        rx = np.asarray(rx)
        ry = np.asarray(ry)
        n = rx.shape[1]
        k = min(n, self.count)
        if k and not (
            np.array_equal(rx[:, :k], self._host_rx[:, :k])
            and np.array_equal(ry[:, :k], self._host_ry[:, :k])
        ):
            # the shared buffer is poisoned for every holder: drop it and
            # let live caches keep their old (consistent) reference
            self.rx = self.ry = None
            self.count = self.capacity = 0
            self._host_rx = self._host_ry = None
            self.version += 1
        if n <= self.count:
            # an older (or identical) consistent view of the registry:
            # the resident buffer already covers it
            return self.rx, self.ry
        new_x = jnp.asarray(np.ascontiguousarray(rx[:, self.count : n]))
        new_y = jnp.asarray(np.ascontiguousarray(ry[:, self.count : n]))
        if n <= self.capacity:
            from jax import lax

            self.rx = self._place(
                "registry/rx",
                lax.dynamic_update_slice(self.rx, new_x, (0, self.count)),
            )
            self.ry = self._place(
                "registry/ry",
                lax.dynamic_update_slice(self.ry, new_y, (0, self.count)),
            )
        else:
            cap = _pow2(max(n, self._min_cap))
            zx = jnp.zeros((32, cap - n), new_x.dtype)
            prefix_x = [self.rx[:, : self.count]] if self.count else []
            prefix_y = [self.ry[:, : self.count]] if self.count else []
            self.rx = self._place(
                "registry/rx", jnp.concatenate(prefix_x + [new_x, zx], axis=1)
            )
            self.ry = self._place(
                "registry/ry", jnp.concatenate(prefix_y + [new_y, zx], axis=1)
            )
            self.capacity = cap
        self.uploaded_cols += n - self.count
        self.count = n
        self._host_rx, self._host_ry = rx, ry
        return self.rx, self.ry


# one store per (chain, backend mode): genesis_validators_root is the
# chain identity the host-side planes cache already keys on
_PLANE_STORES: dict = {}


def get_plane_store(
    chain_key: bytes, interpret: bool | None = None
) -> RegistryPlaneStore:
    """The per-chain shared :class:`RegistryPlaneStore` (created on first
    use).  ``interpret`` selects the backend mode exactly like the caches
    that will reference the planes."""
    if interpret is None:
        interpret = not _use_planes()
    key = (bytes(chain_key), bool(interpret))
    store = _PLANE_STORES.get(key)
    if store is None:
        store = _PLANE_STORES[key] = RegistryPlaneStore(interpret=interpret)
    return store


def plane_store_stats() -> dict:
    """Aggregate telemetry over every live plane store (the node's
    per-tick gauges — a public accessor like ``aot_stats`` so callers
    never couple to this module's internals)."""
    stores = list(_PLANE_STORES.values())
    return {
        "stores": len(stores),
        "resident_bytes": sum(s.resident_bytes for s in stores),
        "uploaded_cols": sum(s.uploaded_cols for s in stores),
    }


# round-18 HBM accounting: the shared registry planes are the largest
# deliberate device residents, so they claim their bytes in the plane
# registry the node tick emits as device_plane_bytes{plane}
from .profile import register_plane as _register_plane  # noqa: E402

_register_plane(
    "registry_planes",
    lambda: plane_store_stats()["resident_bytes"],
    devices=lambda: max(
        (s.shard_devices() for s in _PLANE_STORES.values()), default=1
    ),
)


class DeviceCommitteeCache:
    """Epoch-scoped device-resident committee aggregate pubkeys.

    The round-3 drain re-gathered every entry's full committee (up to 8.3M
    registry points per drain) — the measured super-linear wall.  Committee
    membership is fixed per epoch (ref: the shuffling seed in
    lib/lambda_ethereum_consensus/state_transition/misc.ex feeding
    ``get_beacon_committee``), so this cache computes each committee's FULL
    pubkey sum once per epoch (chunked gather + Jacobian tree reduce on
    device) and each drain pays only a gather over the SHORTER side of
    each aggregate:

        agg_pk[entry] = full_sum[committee] - sum(non-participating members)
                     or sum(participating members), whichever list is shorter

    High-participation aggregates (the gossip norm) make the correction
    gather ~20x smaller than the full gather; below two thirds
    participation (a network that has lost finality) the gather is at
    most half the committee.  All shapes are padded to a small bucket set
    (``widths``) so the jitted programs cache across epochs.

    ``registry_planes`` is either a :class:`RegistryPlaneStore` — the
    production path: this cache holds a reference into the chain's ONE
    shared device buffer, so N live caches pin O(registry), not
    O(N x registry) — or a raw ``(rx, ry)`` plane tuple, which uploads a
    private copy (bench scripts and synthetic-registry tests).  Committee
    indices only ever address live columns, so the store's zero-padded
    capacity tail is never gathered.
    """

    def __init__(
        self,
        registry_planes,
        committees,
        interpret: bool | None = None,
        chunk: int = 256,
        lengths=None,
        mmax: int | None = None,
    ):
        import jax.numpy as jnp

        if isinstance(registry_planes, RegistryPlaneStore):
            store = registry_planes
            if interpret is None:
                interpret = store._interpret
            elif interpret != store._interpret:
                raise ValueError(
                    f"interpret={interpret} conflicts with the plane "
                    f"store's interpret={store._interpret}"
                )
            if store.rx is None:
                raise ValueError("plane store is empty; update() it first")
            self.plane_store = store
            self._plane_version = store.version
            # the SHARED buffers — no copy, no per-cache upload
            self.rx = store.rx
            self.ry = store.ry
        else:
            if interpret is None:
                interpret = not _use_planes()
            self.plane_store = None
            self._plane_version = None
            rx, ry = registry_planes
            self.rx = jnp.asarray(rx)
            self.ry = jnp.asarray(ry)
        self._interpret = interpret
        self._ops = _get_chain_ops(interpret)
        committees = np.asarray(committees, np.int32)
        n_comm, k = committees.shape
        kp = _pow2(k)
        self.n_comm = n_comm
        # gather widths of chain_verify_cached's aggregation program: the
        # narrowest holds 12.5% of the committee (high-participation
        # aggregates are the gossip norm), the widest half of it — the
        # longer an entry's shorter side can ever be (smaller_side) — and
        # powers of two between, one compiled program each
        self.widths = self.gather_widths(k, mmax)
        self.mmax, self.wmax = self.widths[0], self.widths[-1]
        # pad members to pow2 (dead slots flagged inf) and committees to a
        # chunk multiple so every chunk runs the same compiled program
        chunk = min(chunk, _pow2(n_comm))
        cpad = (n_comm + chunk - 1) // chunk * chunk
        idx = np.zeros((cpad, kp), np.int32)
        idx[:n_comm, :k] = committees
        inf = np.ones((cpad, kp), bool)
        if lengths is None:
            inf[:n_comm, :k] = False
        else:
            # ragged committees (the spec's floor-division split leaves
            # ±1-member rows): member slots beyond each row's length stay
            # flagged infinity so they never enter the sum
            lengths = np.asarray(lengths, np.int64)
            if lengths.shape != (n_comm,):
                raise ValueError("lengths must be (n_committees,)")
            inf[:n_comm, :k] = np.arange(k)[None, :] >= lengths[:, None]
        sums_x, sums_y = [], []
        for i in range(0, cpad, chunk):
            sx, sy = self._ops["committee_sums"](
                self.rx,
                self.ry,
                jnp.asarray(idx[i : i + chunk]),
                jnp.asarray(inf[i : i + chunk]),
            )
            sums_x.append(sx)
            sums_y.append(sy)
        self.sum_x = jnp.concatenate(sums_x, axis=1)[:, :n_comm]
        self.sum_y = jnp.concatenate(sums_y, axis=1)[:, :n_comm]

    @staticmethod
    def gather_widths(k: int, mmax: int | None = None) -> tuple[int, ...]:
        """Ascending gather widths for committees of ``k`` members:
        ``mmax`` (default an eighth of the committee, pow2), doubling up to
        half the committee (pow2)."""
        widths = [mmax if mmax is not None else _pow2(max(k // 8, 2))]
        while widths[-1] < _pow2(max(k // 2, 1)):
            widths.append(widths[-1] * 2)
        return tuple(widths)

    def _refresh_planes(self) -> None:
        """Adopt the shared store's CURRENT buffer when registry growth
        rebound it: append-only growth keeps this cache's prefix
        byte-identical, so switching is free — and dropping the pre-growth
        reference is what lets that allocation actually be released
        (otherwise every deposit-era cache pins its own full-registry
        snapshot again).  After an invalidation (``version`` bump) the
        snapshot we were built against stays: it is the buffer our
        committee sums are consistent with."""
        s = self.plane_store
        if (
            s is not None
            and s.rx is not None
            and s.version == self._plane_version
            and s.rx is not self.rx
        ):
            self.rx, self.ry = s.rx, s.ry

    def gather_single(self, indices):
        """Affine pubkey planes of single-signer entries: registry column
        ``indices[e]`` of the planes this cache was built against (the
        same snapshot rule as :meth:`aggregate`, so a key replaced in the
        store after this cache was built is never read through it)."""
        import jax.numpy as jnp

        self._refresh_planes()
        return self._ops["single_gather"](
            self.rx, self.ry, jnp.asarray(np.asarray(indices, np.int32))
        )

    def aggregate(self, comm_ids, idx, idx_inf, attesting=None):
        """Affine aggregate pubkey planes for one drain's entries.

        ``comm_ids``: (E,) committee per entry; ``idx``/``idx_inf``:
        (E, w) registry indices of each entry's listed members with dead
        slots flagged (w one of ``self.widths``, padded by the caller for
        shape stability); ``attesting``: (E,) True where the listed
        members are the entry's participants (summed from the identity),
        False — the default for every entry — where they are its
        NON-participating members (subtracted from the committee sum).
        Returns ``(x_planes, y_planes, inf_mask)`` — entries whose
        participation is empty come back flagged infinity and MUST be
        marked dead by the caller (an aggregate with no participants is
        invalid per the spec's fast-aggregate-verify preconditions).
        """
        import jax.numpy as jnp

        self._refresh_planes()
        if attesting is None:
            attesting = np.zeros(len(comm_ids), bool)
        return self._ops["agg_corrected"](
            self.rx,
            self.ry,
            self.sum_x,
            self.sum_y,
            jnp.asarray(np.asarray(comm_ids, np.int32)),
            jnp.asarray(np.asarray(idx, np.int32)),
            jnp.asarray(np.asarray(idx_inf, bool)),
            jnp.asarray(np.asarray(attesting, bool)),
        )
