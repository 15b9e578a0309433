"""Mesh-sharded RLC batch verification (SURVEY §5.8b's deliverable).

Scales the chained device verify (:mod:`.bls_batch`) across a
``jax.sharding.Mesh`` the way the reference scales across peers with its
network backend (ref: native/libp2p_port — plane (a)); this is plane (b):
XLA collectives over ICI/DCN.

Layout: entries are dealt round-robin onto the ``dp`` axis so every
device owns an equal contiguous block of the flat entry batch, with the
last local slot reserved dead (guaranteed-infinity gather target).  The
data-parallel bulk — the per-entry 128-bit ladders and the per-group
Jacobian partial sums — runs under ``shard_map`` with zero communication;
one ``all_gather`` of the tiny per-device partials (#groups points, not
#entries) crosses the ICI, and the tree over the device axis plus the
normalization finish replicated.  Communication volume is
O(checks x groups), independent of the entry count.

The Miller stage (round 11) is sharded too: the (check, pair) Miller
batch is dealt over the ``dp`` axis, each device reduces its local
pairs to ONE per-check Fq12 partial product, and the partials (C x 576
bytes — a psum-shaped combine, except the monoid is Fq12
multiplication, which XLA has no primitive reduction for) product
replicated.  Two bodies behind that contract — the compiled (TPU) path
is one shard_map program (staged Miller scan + local masked product +
``all_gather`` + replicated product, AOT-cached); interpret mode runs
the manual-shard eager Miller instead (per-device committed blocks,
small cached per-op compiles) because staging the einsum Miller body
under shard_map costs 25+ minutes of XLA CPU compile for the one
program.  Only the final exponentiation — O(checks), the cheap tail —
stays replicated, through the same ``check_tail`` modes as the
single-device chain (hybrid native tail on TPU, composed on CPU).
``sharded_chain_verify`` is therefore the WHOLE verify: no stage's cost
scales with the entry count on fewer than all devices.
"""

from __future__ import annotations

import time as _time

import numpy as np

from ..crypto.bls.batch import _COEFF_BITS
from . import bls_batch as BB
from .bls_g1 import g1_plane_field
from .bls_g2 import g2_plane_field
from .mesh import default_mesh as _default_mesh

__all__ = [
    "sharded_chain_verify",
    "sharded_group_sums",
    "sharded_miller_products",
    "make_shard_ops",
    "pad_to_devices",
]


_SHARD_OPS: dict = {}


def make_shard_ops(mesh, interpret: bool):
    """Build (and cache) the sharded stage functions for one mesh."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .ladder import make_jacobian_ops

    key = (tuple(d.id for d in mesh.devices.flat), interpret)
    if key in _SHARD_OPS:
        return _SHARD_OPS[key]

    # eager loops in interpret mode (stage 1 runs them on sharded
    # arrays); staged lax.scan on the compiled path
    g1j = make_jacobian_ops(g1_plane_field(interpret), eager=interpret)
    g2j = make_jacobian_ops(g2_plane_field(interpret), eager=interpret)
    chain = BB._get_chain_ops(interpret)

    def smap(fn, in_specs, out_specs, name=None):
        jitted = jax.jit(
            jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )
        )
        if name is None or jax.default_backend() != "tpu":
            # CPU: deserialized executables can crash at run time
            # ("Buffer Definition Event ... not found", measured round 4)
            # and jax's own persistent cache misses for these programs —
            # the CPU mesh path instead keeps every body scan-based so
            # the per-process compile stays small (see the reduce note)
            return jitted
        from .aot import aot_jit

        return aot_jit(jitted, f"shard_{name}")

    def _with_live(pt, live):
        X, Y, Z, inf = pt
        return X, Y, Z, inf | ~live

    # ---- stage 1: per-entry ladders, zero communication ----------------
    # BOTH modes run the staged lax.scan ladder under shard_map: the scan
    # body compiles once per shape, and the AOT executable cache (smap
    # name=) makes later processes load it in milliseconds.  (Round 4
    # retired the interpret-mode eager ladder here: its ~50 per-op XLA
    # CPU compiles cost minutes per fresh process and jax's persistent
    # cache missed them, dominating the driver's multichip dryrun.)
    g1j_staged = make_jacobian_ops(g1_plane_field(interpret), eager=False)
    g2j_staged = make_jacobian_ops(g2_plane_field(interpret), eager=False)
    ladder_g1 = smap(
        lambda bx, by, kb, lv: _with_live(g1j_staged["ladder"]((bx, by), kb), lv),
        (P(None, "dp"), P(None, "dp"), P(None, "dp"), P("dp")),
        (P(None, "dp"), P(None, "dp"), P(None, "dp"), P("dp")),
        name="ladder_g1",
    )
    ladder_g2 = smap(
        lambda bx, by, kb, lv: _with_live(g2j_staged["ladder"]((bx, by), kb), lv),
        (P(None, None, "dp"), P(None, None, "dp"), P(None, "dp"), P("dp")),
        (
            P(None, None, "dp"),
            P(None, None, "dp"),
            P(None, None, "dp"),
            P("dp"),
        ),
        name="ladder_g2",
    )

    # BOTH modes: scan-based staged reduces.  One jac_add body compiles
    # once per operand shape; round 4 measured the interpret-mode
    # pairwise tree (log2 levels UNROLLED inside one shard_map jit) at
    # 10+ minutes of XLA CPU compile per process, and neither cache
    # layer reliably amortizes it on CPU.
    _reduce_g1_local = chain["staged_reduce_g1"]
    _reduce_g2_local = chain["staged_reduce_g2"]

    # ---- stage 2: local partial sums + all_gather + device-axis tree ---
    def _reduce_g1_body(X, Y, Z, inf, idx):
        # idx: (1, c, m1, s) local -> squeeze the device axis
        idx = idx[0]
        c, m1, s = idx.shape
        g = (
            jnp.take(X, idx.reshape(-1), axis=1).reshape(-1, c, m1, s),
            jnp.take(Y, idx.reshape(-1), axis=1).reshape(-1, c, m1, s),
            jnp.take(Z, idx.reshape(-1), axis=1).reshape(-1, c, m1, s),
            jnp.take(inf, idx.reshape(-1), axis=0).reshape(c, m1, s),
        )
        pX, pY, pZ, pinf = _reduce_g1_local(g)
        # partials are tiny (c x m1 points): gather all devices' and
        # finish the sum replicated — O(groups) over the ICI
        ag = [
            jnp.moveaxis(lax.all_gather(v, "dp", axis=0), 0, -1)
            for v in (pX, pY, pZ, pinf)
        ]
        return _reduce_g1_local(tuple(ag))

    reduce_g1 = smap(
        _reduce_g1_body,
        (P(None, "dp"), P(None, "dp"), P(None, "dp"), P("dp"), P("dp")),
        (P(None, None, None), P(None, None, None), P(None, None, None), P(None, None)),
        name="reduce_g1",
    )

    def _reduce_g2_body(X, Y, Z, inf, idx):
        idx = idx[0]
        c, e = idx.shape
        s2 = (
            jnp.take(X, idx.reshape(-1), axis=2).reshape(-1, 2, c, e),
            jnp.take(Y, idx.reshape(-1), axis=2).reshape(-1, 2, c, e),
            jnp.take(Z, idx.reshape(-1), axis=2).reshape(-1, 2, c, e),
            jnp.take(inf, idx.reshape(-1), axis=0).reshape(c, e),
        )
        pX, pY, pZ, pinf = _reduce_g2_local(s2)
        ag = [
            jnp.moveaxis(lax.all_gather(v, "dp", axis=0), 0, -1)
            for v in (pX, pY, pZ, pinf)
        ]
        return _reduce_g2_local(tuple(ag))

    reduce_g2 = smap(
        _reduce_g2_body,
        (
            P(None, None, "dp"),
            P(None, None, "dp"),
            P(None, None, "dp"),
            P("dp"),
            P("dp"),
        ),
        (P(None, None, None), P(None, None, None), P(None, None, None), P(None,)),
        name="reduce_g2",
    )

    # ---- stage 3: sharded Miller loops + Fq12 partial-product combine --
    #
    # Two bodies behind one contract, same split as every other stage in
    # this tree (eager on the CPU-testable path, staged on TPU):
    #
    # - COMPILED (TPU): one shard_map program — staged Miller scan on the
    #   local pairs, local masked product, one all_gather of the C-sized
    #   Fq12 partials, replicated product.  Goes through aot_jit like the
    #   other stages.
    # - INTERPRET (CPU mesh): staging the einsum Miller body under
    #   shard_map is the round-1 compile blowup measured at 25+ min of
    #   XLA CPU compile for the ONE program — so interpret mode instead
    #   runs the manual-shard eager Miller (_miller_combine_eager below):
    #   each device's pair block is committed to that device and the
    #   eager per-op jits execute on it, giving the same data-parallel
    #   layout and the same combine shape with only small cached per-op
    #   compiles.  Results are bit-identical (Fq12 math is exact; only
    #   the product order differs, and that does not change the value).
    from .bls_pairing import _get_ops as _get_pairing_ops

    miller_combine = None
    if not interpret:
        pairing_staged = _get_pairing_ops(
            plane=True, interpret=interpret, eager=False
        )
        _miller_raw = pairing_staged["miller_raw"]
        _mprod_raw = pairing_staged["masked_product_raw"]

        def _miller_combine_body(px, py, qx, qy, mask):
            # local shapes: px/py (32, c, ml), qx/qy (32, 2, c, ml),
            # mask (c, ml) — ml = padded pairs / n_devices
            f = _miller_raw(px, py, qx, qy)  # (32, 2, 3, 2, c, ml)
            part = _mprod_raw(f, mask)  # (32, 2, 3, 2, c) local partial
            # the combine: one all_gather of C Fq12 partials per device —
            # O(checks) over the ICI, independent of the pair/entry count
            ag = jnp.moveaxis(lax.all_gather(part, "dp", axis=0), 0, -1)
            live = jnp.ones(ag.shape[-2:], bool)  # (c, d): all live
            return _mprod_raw(ag, live)  # (32, 2, 3, 2, c) replicated

        miller_combine = smap(
            _miller_combine_body,
            (
                P(None, None, "dp"),
                P(None, None, "dp"),
                P(None, None, None, "dp"),
                P(None, None, None, "dp"),
                P(None, "dp"),
            ),
            P(),
            name="miller_combine",
        )

    ops = {
        "mesh": mesh,
        "sharding": lambda spec: NamedSharding(mesh, spec),
        "P": P,
        "ladder_g1": ladder_g1,
        "ladder_g2": ladder_g2,
        "reduce_g1": reduce_g1,
        "reduce_g2": reduce_g2,
        "miller_combine": miller_combine,
        "chain": chain,
    }
    _SHARD_OPS[key] = ops
    return ops


# G1/G2 generator limb planes — the canonical dead-pair padding values
# (same discipline as bls_batch's host packing: padded Miller slots carry
# the generators and are masked to the Fq12 identity after the loop).
_PAD_PLANES: dict = {}


def _pad_planes():
    if not _PAD_PLANES:
        import jax.numpy as jnp

        from ..crypto.bls import curve as C

        g1x, g1y = BB._g1_planes([C.G1_GENERATOR])  # (32, 1)
        g2x, g2y = BB._g2_planes([C.G2_GENERATOR])  # (32, 2, 1)
        _PAD_PLANES["g1x"] = jnp.asarray(g1x[:, :, None])  # (32, 1, 1)
        _PAD_PLANES["g1y"] = jnp.asarray(g1y[:, :, None])
        _PAD_PLANES["g2x"] = jnp.asarray(g2x[:, :, :, None])  # (32, 2, 1, 1)
        _PAD_PLANES["g2y"] = jnp.asarray(g2y[:, :, :, None])
    return _PAD_PLANES


def pad_to_devices(m: int, d: int) -> int:
    """Smallest multiple of ``d`` >= ``m`` — the pair-axis pad target of
    the sharded Miller stage.  Both operands are powers of two on every
    caller (m = m1 + 1 with m1 a pow2-minus-1 group count; d asserted
    pow2), so the result is ``max(m, d)`` and the padded shape stays in
    the same snapped bucket set as the single-device chain (no fresh
    trace per drain — the graftlint retrace discipline)."""
    if d <= 0:
        raise ValueError(f"device count must be positive, got {d}")
    return -(-m // d) * d


def _record_shard_stats(stats: dict, combine_s: float) -> None:
    """The ``ops_shard_*`` device-telemetry contract (round 11): mesh
    width, per-shard batch size and the wall time of the dispatch that
    carries the collective — all from the verify hot path, so the
    Grafana shard panel shows live drains, not a bench artifact."""
    from ..telemetry import get_metrics

    m = get_metrics()
    if not m.enabled:
        return
    m.set_gauge("ops_shard_devices", float(stats["devices"]))
    m.set_gauge("ops_shard_batch_per_device", float(stats["batch_per_device"]))
    m.observe("ops_shard_combine_seconds", combine_s)


def _miller_combine_eager(mesh, px, py, qx, qy, mask):
    """Interpret-mode sharded Miller: deal the pair blocks over the mesh
    devices by explicit placement and run the EAGER plane Miller on each
    — every op executes on the device its operands are committed to, so
    the eight blocks advance data-parallel while the host enqueues — then
    pull the eight C-sized Fq12 partials onto device 0 and product them
    pairwise (the collective-free CPU stand-in for the compiled path's
    all_gather; the partials are C x 576 bytes, placement cost is noise).
    """
    import jax
    import jax.numpy as jnp

    from .bls_pairing import _get_ops as _get_pairing_ops

    pair = _get_pairing_ops(plane=True, interpret=True, eager=True)
    devs = list(mesh.devices.flat)
    d = len(devs)
    mp = mask.shape[-1]
    ml = mp // d
    px, py, qx, qy, mask = (np.asarray(v) for v in (px, py, qx, qy, mask))
    partials = []
    for i, dev in enumerate(devs):
        sl = slice(i * ml, (i + 1) * ml)
        put = lambda a: jax.device_put(jnp.asarray(a[..., sl]), dev)
        f = pair["miller"](put(px), put(py), put(qx), put(qy))
        partials.append(pair["masked_product"](f, put(mask)))
    acc = jax.device_put(partials[0], devs[0])
    for p in partials[1:]:
        acc = pair["mul"](acc, jax.device_put(p, devs[0]))
    return acc


def _sharded_fq12_products(checks, mesh, interpret, coeff_bits):
    """Everything up to (and including) the sharded Miller loops and the
    Fq12 partial-product combine.  Returns ``(ops, prod)`` with ``prod``
    the replicated ``(32, 2, 3, 2, C)`` per-check pairing products, or
    ``None`` for an empty check list."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    reduced = _sharded_reduced(checks, mesh, interpret, coeff_bits)
    if reduced is None:
        return None
    ops, group_jac, sig_jac, hx, hy, static_live, stats = reduced

    # The reduced sums come back REPLICATED over the mesh, and the pieces
    # that finish them (Fermat normalization here, the tail below) are the
    # single-device chain's programs: Pallas kernels, which the compiler
    # cannot partition over a mesh-committed operand ("Mosaic kernels
    # cannot be automatically partitioned").  They are O(checks x groups)
    # points — pin them to one device, as the single-device chain has them.
    one = ops["mesh"].devices.flat[0]
    group_jac, sig_jac = jax.device_put((group_jac, sig_jac), one)
    chain = ops["chain"]
    px, py, qx, qy, mask = chain["finish"](
        group_jac, sig_jac, jnp.asarray(hx), jnp.asarray(hy),
        jnp.asarray(static_live),
    )
    # Deal the (C, m) Miller pairs over the mesh: pad the pair axis to a
    # device multiple with generator pairs (masked to the identity after
    # the loop, like every dead slot).  m is already a power of two
    # (m1 + 1) and d is asserted pow2, so mp = max(m, d) — the pad shapes
    # stay in the same snapped bucket set as the single-device chain.
    d = stats["devices"]
    c, m = mask.shape
    mp = pad_to_devices(m, d)
    pad = mp - m
    if pad:
        pp = _pad_planes()
        px = jnp.concatenate([px, jnp.broadcast_to(pp["g1x"], (32, c, pad))], -1)
        py = jnp.concatenate([py, jnp.broadcast_to(pp["g1y"], (32, c, pad))], -1)
        qx = jnp.concatenate(
            [qx, jnp.broadcast_to(pp["g2x"], (32, 2, c, pad))], -1
        )
        qy = jnp.concatenate(
            [qy, jnp.broadcast_to(pp["g2y"], (32, 2, c, pad))], -1
        )
        mask = jnp.concatenate([mask, jnp.zeros((c, pad), bool)], -1)
    t0 = _time.perf_counter()
    if ops["miller_combine"] is None:  # interpret: manual-shard eager
        prod = _miller_combine_eager(ops["mesh"], px, py, qx, qy, mask)
    else:
        put = lambda arr, spec: jax.device_put(arr, ops["sharding"](spec))
        prod = ops["miller_combine"](
            put(px, P(None, None, "dp")),
            put(py, P(None, None, "dp")),
            put(qx, P(None, None, None, "dp")),
            put(qy, P(None, None, None, "dp")),
            put(mask, P(None, "dp")),
        )
    prod.block_until_ready()
    _record_shard_stats(stats, _time.perf_counter() - t0)
    return ops, jax.device_put(prod, one)  # replicated -> one device (above)


def sharded_chain_verify(
    checks,
    mesh=None,
    interpret: bool | None = None,
    coeff_bits: int = _COEFF_BITS,
) -> list[bool]:
    """:func:`..bls_batch.chain_verify` distributed over a device mesh —
    the WHOLE verify: RLC ladders, group sums, Miller loops and the
    partial-product combine all run sharded over ``dp``; only the cheap
    O(checks) final exponentiation is replicated (via the same
    ``check_tail`` modes as the single-device chain).

    Same inputs/outputs and infinity semantics as ``chain_verify``, and
    bit-exact against it: group/sig sums are normalized to canonical
    affine coordinates before the Miller loop, and Fq12 multiplication
    is exact and associative, so the device partition changes only the
    product ORDER, never the value.
    """
    res = _sharded_fq12_products(checks, mesh, interpret, coeff_bits)
    if res is None:
        return []
    ops, prod = res
    chain = ops["chain"]
    c = prod.shape[-1]
    # the combine already applied the live mask: check_tail sees one
    # pre-multiplied product per check (K = 1, all live)
    ok = chain["check_tail"](prod[..., None], np.ones((c, 1), bool))
    return [bool(v) for v in np.asarray(ok)]


def sharded_miller_products(
    checks,
    mesh=None,
    interpret: bool | None = None,
    coeff_bits: int = _COEFF_BITS,
) -> list:
    """Host Fq12 tuples of each check's combined pairing product (the
    value entering the final exponentiation) — the oracle surface: the
    dryrun and the mesh tests compare these bit-exactly against the
    single-device chain, and (after final exp) against the pure-host
    pairing oracle."""
    res = _sharded_fq12_products(checks, mesh, interpret, coeff_bits)
    if res is None:
        return []
    from . import bls_fq12 as FQ

    _, prod = res
    return FQ.fq12_batch_from_limbs(np.asarray(prod), plane=True)


def sharded_group_sums(
    checks,
    mesh=None,
    interpret: bool | None = None,
    coeff_bits: int = _COEFF_BITS,
):
    """Run ONLY the sharded stages (ladders, per-device partial sums, the
    ``all_gather``) and return host affine integers:

        ([per-check list of per-group sum points], [per-check sig sum])

    with ``None`` for a sum that reduced to infinity.  This is the
    distributed portion of the verify — everything after it (Miller,
    final exp) runs replicated and is covered by the single-device chain
    tests — so the multi-chip dryrun can check the collective path
    against a host EC oracle without paying the replicated pairing's
    tracing cost on a virtual CPU mesh.
    """
    reduced = _sharded_reduced(checks, mesh, interpret, coeff_bits)
    if reduced is None:
        return [], []
    _, group_jac, sig_jac, _, _, static_live, _ = reduced
    import numpy as np

    from .bls_g1 import _ints_batch
    from ..crypto.bls.fields import P as FIELD_P

    def _to_affine(X, Y, Z, inf, fq2: bool):
        # host Jacobian -> affine over the pulled (tiny) partials
        shape = np.asarray(inf).shape
        flat = int(np.prod(shape)) if shape else 1
        lead = (32, 2) if fq2 else (32,)
        Xs = np.asarray(X).reshape(*lead, flat)
        Ys = np.asarray(Y).reshape(*lead, flat)
        Zs = np.asarray(Z).reshape(*lead, flat)
        infs = np.asarray(inf).reshape(flat)
        out = []
        for i in range(flat):
            if infs[i]:
                out.append(None)
                continue
            if fq2:
                xi = [_ints_batch(Xs[:, c, i].T.reshape(1, 32).astype(np.int32))[0]
                      for c in range(2)]
                yi = [_ints_batch(Ys[:, c, i].T.reshape(1, 32).astype(np.int32))[0]
                      for c in range(2)]
                zi = [_ints_batch(Zs[:, c, i].T.reshape(1, 32).astype(np.int32))[0]
                      for c in range(2)]
                from ..crypto.bls import fields as F

                z2 = F.fq2_mul(tuple(zi), tuple(zi))
                z3 = F.fq2_mul(z2, tuple(zi))
                x = F.fq2_mul(tuple(xi), F.fq2_inv(z2))
                y = F.fq2_mul(tuple(yi), F.fq2_inv(z3))
                out.append((x, y))
            else:
                xi = _ints_batch(Xs[:, i].T.reshape(1, 32).astype(np.int32))[0]
                yi = _ints_batch(Ys[:, i].T.reshape(1, 32).astype(np.int32))[0]
                zi = _ints_batch(Zs[:, i].T.reshape(1, 32).astype(np.int32))[0]
                z2 = pow(zi, 2, FIELD_P)
                z3 = (z2 * zi) % FIELD_P
                x = (xi * pow(z2, -1, FIELD_P)) % FIELD_P
                y = (yi * pow(z3, -1, FIELD_P)) % FIELD_P
                out.append((x, y))
        return out, shape

    gX, gY, gZ, ginf = group_jac
    flat_groups, gshape = _to_affine(gX, gY, gZ, ginf, fq2=False)  # (c, m1)
    sX, sY, sZ, sinf = sig_jac
    sig_sums, _ = _to_affine(sX, sY, sZ, sinf, fq2=True)  # (c,)
    c, m1 = gshape
    live = np.asarray(static_live)
    groups_out = []
    for ci in range(c):
        row = [
            flat_groups[ci * m1 + g] if live[ci, g] else None
            for g in range(m1)
        ]
        groups_out.append(row)
    return groups_out, sig_sums


def _sharded_reduced(checks, mesh, interpret, coeff_bits):
    """Shared front half: pack, shard, ladder, reduce.  Returns ``None``
    for an empty check list, else ``(ops, group_jac, sig_jac, hx, hy,
    static_live, stats)`` with the reduced Jacobians living on device
    and ``stats`` the shard-telemetry facts (mesh width, per-device
    padded batch)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..crypto.bls import curve as C

    if interpret is None:
        from .bls_g1 import _use_planes

        interpret = not _use_planes()
    if mesh is None:
        mesh = _default_mesh()
    d = mesh.devices.size
    assert d & (d - 1) == 0, "dp axis size must be a power of two"
    ops = make_shard_ops(mesh, interpret)

    n_checks = len(checks)
    if n_checks == 0:
        return None

    flat_pk, flat_sig, flat_coeff = [], [], []
    for ci, (entries, _, _) in enumerate(checks):
        for pk, sig, coeff in entries:
            flat_pk.append(pk)
            flat_sig.append(sig)
            flat_coeff.append(coeff)
    n = len(flat_pk)

    # Round-robin deal onto devices; each device keeps >= 1 dead tail
    # slot (the busiest device gets ceil(n/d) live entries, so bl must
    # exceed THAT, not n//d — off-by-one here corrupts every padding
    # gather on a full device).
    q = BB._QUANTUM if not interpret else 8
    nl = -(-n // d)  # live entries on the busiest device
    bl = (nl // q + 1) * q
    b = d * bl
    # flat entry e lives at global column (e % d) * bl + e // d
    col = np.arange(n)
    cols = (col % d) * bl + col // d

    order = np.full(b, -1, np.int64)
    order[cols] = np.arange(n)
    pk_list, sig_list, kf = [], [], []
    for slot in range(b):
        e = order[slot]
        if e >= 0:
            pk_list.append(flat_pk[e])
            sig_list.append(flat_sig[e])
            kf.append(flat_coeff[e])
        else:
            pk_list.append(C.G1_GENERATOR)
            sig_list.append(C.G2_GENERATOR)
            kf.append(1)
    pkx, pky = BB._g1_planes(pk_list)
    sgx, sgy = BB._g2_planes(sig_list)
    kbits = BB._scalar_bits_batch(kf, coeff_bits).T
    live = order >= 0

    # Shapes shared with chain_verify's packing.
    max_groups = max(max((len(h) for _, h, _ in checks), default=1), 1)
    m1 = BB._pow2(max_groups + 1) - 1
    # per-device group slots / sig slots (local indices, dead = bl - 1)
    counts = np.zeros((d, n_checks, m1), np.int64)
    sig_counts = np.zeros((d, n_checks), np.int64)
    flat_e = 0
    for ci, (entries, h_points, group_ids) in enumerate(checks):
        for ei, g in enumerate(group_ids):
            counts[flat_e % d, ci, g] += 1
            sig_counts[flat_e % d, ci] += 1
            flat_e += 1
    s = BB._pow2(int(counts.max()) or 1)
    e_max = BB._pow2(int(sig_counts.max()) or 1)

    idx_g1 = np.full((d, n_checks, m1, s), bl - 1, np.int32)
    idx_sig = np.full((d, n_checks, e_max), bl - 1, np.int32)
    static_live = np.zeros((n_checks, m1 + 1), bool)
    fill = np.zeros((d, n_checks, m1), np.int64)
    sig_fill = np.zeros((d, n_checks), np.int64)
    flat_e = 0
    for ci, (entries, h_points, group_ids) in enumerate(checks):
        for ei, g in enumerate(group_ids):
            dev = flat_e % d
            local = flat_e // d
            idx_g1[dev, ci, g, fill[dev, ci, g]] = local
            fill[dev, ci, g] += 1
            idx_sig[dev, ci, sig_fill[dev, ci]] = local
            sig_fill[dev, ci] += 1
            flat_e += 1
        # occupancy was already counted across devices — O(groups), not
        # a per-group membership scan over every entry
        static_live[ci, : len(h_points)] = (
            counts[:, ci, : len(h_points)].sum(axis=0) > 0
        )
        static_live[ci, m1] = len(entries) > 0

    h_points_padded = []
    for _, h_points, _ in checks:
        h_points_padded.extend(
            list(h_points) + [C.G2_GENERATOR] * (m1 - len(h_points))
        )
    hx, hy = BB._g2_planes(h_points_padded)
    hx = hx.reshape(32, 2, n_checks, m1)
    hy = hy.reshape(32, 2, n_checks, m1)

    put = lambda arr, spec: jax.device_put(jnp.asarray(arr), ops["sharding"](spec))
    pkx_d = put(pkx, P(None, "dp"))
    pky_d = put(pky, P(None, "dp"))
    sgx_d = put(sgx, P(None, None, "dp"))
    sgy_d = put(sgy, P(None, None, "dp"))
    kb_d = put(kbits, P(None, "dp"))
    lv_d = put(live, P("dp"))

    jac1 = ops["ladder_g1"](pkx_d, pky_d, kb_d, lv_d)
    jac2 = ops["ladder_g2"](sgx_d, sgy_d, kb_d, lv_d)
    group_jac = ops["reduce_g1"](*jac1, put(idx_g1, P("dp")))
    sig_jac = ops["reduce_g2"](*jac2, put(idx_sig, P("dp")))
    stats = {"devices": d, "batch_per_device": bl}
    return ops, group_jac, sig_jac, hx, hy, static_live, stats
