"""Background device-program warmer for node boot.

The first dispatch of each drain program pays its load (or, cold, its
trace, lowering and compile) in front of the first verified drain.  A
booting node has plenty of concurrent host work (anchor-state load,
registry-planes packing, sidecar spawn, range-sync negotiation), so the
fix is overlap: dispatch one full DUMMY drain at the expected production
shapes on a thread the moment the process starts, and by the time real
gossip arrives every program is resident.

The dummy drain runs the REAL op chain (committee sums, corrected
aggregates, RLC ladders, prep, Miller, final-exp tail) on zero planes —
the values are garbage, but program identity is keyed by shape, which is
all warming needs.
"""

from __future__ import annotations

import threading
import time

from ..ops.aot import compile_context
from ..telemetry import observe

__all__ = [
    "DrainShapes",
    "warm_drain_programs",
    "warm_duties",
    "warm_kzg",
    "warm_sharded_programs",
    "warm_transition",
    "start_transition_warmer",
    "warm_witness",
    "start_warmer",
]


class DrainShapes:
    """The shape key of one drain program set (see ops/bls_batch.py)."""

    def __init__(
        self,
        n_validators: int,
        n_committees: int,
        committee: int,
        entries: int,
        groups: int,
        checks: int = 1,
        coeff_bits: int | None = None,
    ):
        self.n_validators = n_validators
        self.n_committees = n_committees
        self.committee = committee
        self.entries = entries
        self.groups = groups
        self.checks = checks
        if coeff_bits is None:
            from ..crypto.bls.batch import _COEFF_BITS

            coeff_bits = _COEFF_BITS
        self.coeff_bits = coeff_bits

    def chain_layout(self, interpret: bool):
        """The :class:`...ops.bls_batch.ChainLayout` of one drain at these
        shapes: what :func:`warm_drain_programs` dispatches, and what a
        smaller flush is padded up to once it is registered."""
        from ..ops import bls_batch as BB

        b, _dead = BB._entry_budget(self.entries, interpret)
        per_check = (self.entries + self.checks - 1) // self.checks
        return BB.ChainLayout(
            b=b,
            checks=self.checks,
            m1=BB._pow2(self.groups + 1) - 1,
            s=BB._pow2(max(per_check // max(self.groups // self.checks, 1), 1)),
            e=BB._pow2(per_check),
        )

    def bisection_layouts(self, interpret: bool):
        """The ladder of :class:`...ops.bls_batch.ChainLayout` rungs below
        one drain at these shapes: level ``k`` of a failed flush re-checks
        two ranges of at most ``r = ceil(entries / 2^k)`` entries, which
        hold at most ``min(groups, r)`` messages and, where the flush fits
        the drain's layout, at most ``min(s, r)`` entries of one message.
        Each rung is that bound, so every level of a flush with one bad
        entry lands on a rung whatever the arrival order and wherever the
        bad entry sits.  Every rung keeps the drain's ``b``: a level
        re-checks its ranges on the laddered planes of the flush's first
        check (``ops/bls_batch.chain_recheck``), whose lanes are the
        drain's.  A flush with bad entries in both halves of a range
        re-checks more than two ranges a level: those levels keep layouts
        of their own."""
        from ..ops import bls_batch as BB

        drain = self.chain_layout(interpret)
        rungs, r = [], self.entries
        while r > 1:
            r = -(-r // 2)  # the larger half of a range
            rungs.append(BB.ChainLayout(
                b=drain.b,
                checks=2,
                m1=BB._pow2(min(self.groups, r) + 1) - 1,
                s=min(drain.s, BB._pow2(r)),
                e=BB._pow2(r),
            ))
        return rungs


def warm_sharded_programs(shapes: DrainShapes) -> float:
    """Dispatch one dummy SHARDED verify at ``shapes`` — the mesh
    analogue of :func:`warm_drain_programs`: loads/compiles the
    shard_map ladder, reduce and Miller-combine executables (plus the
    replicated tail) at the exact padded shapes the scheduler's
    deadline flushes snap to, so the first real sharded drain finds
    every program resident.  Values are generators (garbage); program
    identity is keyed by shape, which is all warming needs."""
    from ..crypto.bls import curve as C
    from ..ops.bls_shard import sharded_chain_verify

    t0 = time.perf_counter()
    checks = []
    per_check = max(1, shapes.entries // max(shapes.checks, 1))
    groups = max(1, min(shapes.groups, per_check))
    h_points = [C.G2_GENERATOR] * groups
    for _ in range(max(shapes.checks, 1)):
        entries = [(C.G1_GENERATOR, C.G2_GENERATOR, 1)] * per_check
        gids = [i % groups for i in range(per_check)]
        checks.append((entries, h_points, gids))
    # compile_context tags every lower/compile this dummy verify causes,
    # so /debug/compile attributes them to the planned warmup rather
    # than to a mid-drain retrace
    with compile_context("warmup:sharded"):
        ok = sharded_chain_verify(checks, coeff_bits=shapes.coeff_bits)
    assert len(ok) == len(checks)
    dt = time.perf_counter() - t0
    observe("warmup_phase_seconds", dt, phase="sharded")
    return dt


def warm_drain_programs(shapes: DrainShapes) -> float:
    """Dispatch one dummy drain at ``shapes``, then one dummy call at each
    rung of its bisection ladder (:meth:`DrainShapes.bisection_layouts`);
    blocks until every program ran on device.  Returns seconds spent
    (load/compile time).  On a multi-device mesh with the sharded plane
    selected, the SHARDED executables are warmed first — they are what the
    scheduler's flushes will actually dispatch — and the single-device
    programs after (the fallback, and the committee-cache drain's op
    set)."""
    import jax.numpy as jnp
    import numpy as np

    from ..crypto.bls.batch import shard_active
    from ..ops import bls_batch as BB

    t0 = time.perf_counter()
    if shard_active():
        warm_sharded_programs(shapes)
    interpret = not BB._use_planes()
    ops = BB._get_chain_ops(interpret)
    t_single = time.perf_counter()

    with compile_context("warmup:drain"):
        kp = BB._pow2(shapes.committee)
        mmax = BB._pow2(max(shapes.committee // 8, 2))

        zreg = jnp.zeros((32, shapes.n_validators), jnp.int32)
        chunk = min(256, max(1, shapes.n_committees))
        ops["committee_sums"](
            zreg, zreg,
            jnp.zeros((chunk, kp), jnp.int32),
            jnp.zeros((chunk, kp), bool),
        )
        sx = jnp.zeros((32, shapes.n_committees), jnp.int32)
        laddered = {}  # entry budget b -> its two laddered planes

        def dispatch(b, checks, m1, s, e):
            if b not in laddered:
                ax, ay, _ = ops["agg_corrected"](
                    zreg, zreg, sx, sx,
                    jnp.zeros((b,), jnp.int32),
                    jnp.zeros((b, mmax), jnp.int32),
                    jnp.ones((b, mmax), bool),
                    jnp.zeros((b,), bool),
                )
                kb = jnp.zeros((shapes.coeff_bits, b), jnp.int32)
                lv = jnp.zeros((b,), bool)
                laddered[b] = (ops["ladder_g1"](ax, ay, kb, lv), ops["ladder_g2"](
                    jnp.zeros((32, 2, b), jnp.int32), jnp.zeros((32, 2, b), jnp.int32),
                    kb, lv,
                ))
            px, py, qx, qy, mask = ops["prep"](
                *laddered[b],
                jnp.zeros((checks, m1, s), jnp.int32),
                jnp.zeros((checks, e), jnp.int32),
                jnp.zeros((32, 2, checks, m1), jnp.int32),
                jnp.zeros((32, 2, checks, m1), jnp.int32),
                jnp.zeros((checks, m1 + 1), bool),
            )
            f = ops["miller"](px, py, qx, qy)
            np.asarray(ops["check_tail"](f, mask))  # pull: blocks until loaded

        dispatch(*shapes.chain_layout(interpret))
        t_ladder = time.perf_counter()
        observe("warmup_phase_seconds", t_ladder - t_single, phase="drain")
        for rung in shapes.bisection_layouts(interpret):
            dispatch(*rung)
    observe(
        "warmup_phase_seconds", time.perf_counter() - t_ladder, phase="bisection"
    )
    return time.perf_counter() - t0


def warm_transition(n_validators: int) -> float:
    """Load/compile the resident-transition kernel set at the registry's
    padded shape (state_transition/resident.py) so a cold process's first
    epoch boundary — and the replay drivers' first block — dispatch
    resident programs instead of tracing mid-transition.  No-op seconds
    when the resident path is size/env-disabled for this registry."""
    from ..state_transition.resident import resident_enabled, warm_transition_programs

    if not resident_enabled(n_validators):
        return 0.0
    return warm_transition_programs(n_validators)


def warm_duties() -> float:
    """Register the ``duty_sign`` shape buckets and compile/load the
    batched signing plane at its first bucket (ops/bls_sign.py) under
    ``compile_context("warmup:duties")`` — so ``/debug/compile``
    attributes the planned duty compiles to the warmup phase and a
    slot's first duty flush never traces mid-slot.  Host backends only
    register the buckets (the comb path has no program to warm)."""
    from ..ops.bls_sign import warm_sign_programs

    dt = warm_sign_programs()
    observe("warmup_phase_seconds", dt, phase="duties")
    return dt


def warm_kzg() -> float:
    """Register the ``kzg_msm`` shape buckets and, on device backends,
    compile/load the packed MSM ladder at its first bucket (da/kzg.py)
    so a slot's first blob-sidecar flush dispatches a resident program
    instead of tracing mid-slot."""
    from ..da import warm_kzg_programs

    dt = warm_kzg_programs()
    observe("warmup_phase_seconds", dt, phase="kzg")
    return dt


def warm_witness() -> float:
    """Load/compile the batched witness-verification plane at its
    canonical serving shape (witness/verify.py) so the first real
    light-client batch dispatches a resident program.  Registers the
    ``witness_verify`` shape buckets as a side effect — the API's verify
    route snaps batch sizes onto them."""
    from ..witness.verify import warm_witness_programs

    dt = warm_witness_programs()
    observe("warmup_phase_seconds", dt, phase="witness")
    return dt


def start_transition_warmer(
    n_validators: int, stats: dict | None = None
) -> threading.Thread | None:
    """:func:`warm_transition` alone on a daemon thread, for a node started
    without drain shapes (a node that catches up: no gossip drain to warm).
    It crosses an epoch boundary every 32 slots all the same, and the
    plane's delta scatters are first dispatched at the second boundary
    that finds a block behind it: unwarmed, those donated programs (no
    disk tier) are lowered and compiled inside that block's import.
    ``None`` where the resident path is off for this registry size."""
    from ..state_transition.resident import resident_enabled

    if not resident_enabled(n_validators):
        return None
    stats = stats if stats is not None else {}

    def run():
        try:
            stats["transition_s"] = round(warm_transition(n_validators), 1)
        except Exception as e:  # visible, never fatal to boot
            stats["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=run, daemon=True, name="transition-warmer")
    t.start()
    return t


def start_warmer(
    shapes: DrainShapes, stats: dict | None = None,
    n_validators: int | None = None,
) -> threading.Thread:
    """Run :func:`warm_drain_programs` (and, when the resident transition
    is enabled for this registry size, :func:`warm_transition`, plus the
    witness-verification plane) on a daemon thread; failures land in
    ``stats['error']`` (a silent cold start would corrupt the boot
    timeline's meaning)."""
    stats = stats if stats is not None else {}
    # advertise the warmed batch shapes BEFORE the dispatch: the ingest
    # scheduler starts snapping flush sizes to this bucket immediately,
    # so the first real drain lands on the program the warmer is loading
    # rather than tracing a near-miss shape of its own; same contract for
    # the witness plane's verify-batch buckets
    from ..ops.aot import register_shape_bucket
    from ..ops.bls_sign import DEFAULT_SIGN_BUCKETS
    from ..witness.verify import DEFAULT_BATCH_BUCKETS

    register_shape_bucket("attestation_entries", shapes.entries)
    # ... and the chain pads a flush BELOW the warmed drain (a deadline
    # flush, a slot's ragged tail) up to this layout, and each bisection
    # level of a flush with one bad entry up to its rung of the ladder: each
    # layout is a set of programs, and only these are loaded before traffic
    from ..ops import bls_batch as BB

    interpret = not BB._use_planes()
    for layout in (shapes.chain_layout(interpret), *shapes.bisection_layouts(interpret)):
        BB.register_chain_layout(layout)
    for bucket in DEFAULT_BATCH_BUCKETS:
        register_shape_bucket("witness_verify", bucket)
    for bucket in DEFAULT_SIGN_BUCKETS:
        register_shape_bucket("duty_sign", bucket)

    def run():
        try:
            stats["overlap_s"] = round(warm_drain_programs(shapes), 1)
            stats["transition_s"] = round(
                warm_transition(
                    shapes.n_validators if n_validators is None else n_validators
                ),
                1,
            )
            stats["witness_s"] = round(warm_witness(), 1)
            stats["duties_s"] = round(warm_duties(), 1)
            stats["kzg_s"] = round(warm_kzg(), 1)
        except Exception as e:  # visible, never fatal to boot
            stats["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=run, daemon=True, name="drain-warmer")
    t.start()
    return t
