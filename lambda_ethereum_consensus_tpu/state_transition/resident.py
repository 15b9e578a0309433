"""Device-resident, delta-driven epoch processing.

The epoch boundary is the transition's O(n_validators) wall: rewards and
penalties, inactivity scores, slashing penalties and effective-balance
hysteresis all sweep the full registry.  The host path (epoch.py) runs
them as numpy expressions; this module moves the sweeps onto jitted
device kernels that consume *persistent device columns* — the hot
``BeaconState`` columns (balances, inactivity scores, participation)
live on device across blocks, synced by the per-epoch *delta* against a
host mirror instead of a full re-upload, and updated in place via
``donate_argnums`` so XLA aliases the output buffers onto the inputs.

Numerics: TPUs have no native 64-bit integers, so balances are held as
two uint32 limbs (lo/hi) and every kernel does exact limb arithmetic —
carry-propagated adds, borrow-propagated saturating subtracts, and the
inactivity penalty's 57-bit product in 16-bit partial products (the same
bit-plane discipline as ops/bigint.py, scaled down to one value).  The
per-flag reward/penalty amounts are pure functions of a validator's
effective-balance *increment count* (0..32), so the host precomputes
them as exact-python-int lookup tables and the kernel just gathers.

Representability is guarded, not assumed: :meth:`ResidentEpochPlane.sync`
refuses (and the caller falls back to the bit-exact host path) whenever
a balance, score, effective balance or lookup value strays outside the
limb bounds.  tests/unit/test_resident_transition.py pins the resident
path's state roots against the host oracle block-by-block across epoch
boundaries with slashings and registry churn.

Program identity is keyed by the padded column shape: every kernel is
``aot_jit``-wrapped (persistent executable cache), pads to pow2 via
:func:`_pad_pow2`, registers its shape buckets with
``ops/aot.register_shape_bucket`` and warms under the
``warmup:transition`` compile context (node/warmup.py), so a cold
process replays at warm speed instead of tracing mid-replay.
"""

from __future__ import annotations

import math
import os
import threading
import weakref

import numpy as np

from ..config import ChainSpec, constants, get_chain_spec
from ..ops import shard_rules
from ..ops.aot import aot_jit, compile_context, register_shape_bucket
from ..ops.mesh import state_shard_enabled
from ..ops.profile import register_plane
from ..telemetry import observe, set_gauge, span
from .math import integer_squareroot

__all__ = [
    "ResidentEpochPlane",
    "ensure_plane",
    "process_epoch_resident",
    "resident_enabled",
    "warm_transition_programs",
]

# Auto-attach threshold: below this registry size a device dispatch costs
# more than the whole host sweep (same crossover logic as the SSZ
# _DEVICE_CHUNKS floor).  GRAFT_RESIDENT_EPOCH=1/0 forces either way.
_MIN_VALIDATORS = int(os.environ.get("GRAFT_RESIDENT_MIN_VALIDATORS", str(1 << 14)))

# Limb bounds the kernels rely on (see module docstring): balances below
# 2^63 (hi limb < 2^31), scores below 2^30 (headroom for the bias add),
# per-validator reward/penalty table entries below 2^31 (single limb),
# and the inactivity-penalty multiplicand below 2^26 (16-bit partials).
_MAX_BAL = 1 << 63
_MAX_SCORE = 1 << 30
_MAX_LUT = 1 << 31
_MAX_MULT = 1 << 26

_KERNEL_LOCK = threading.Lock()
_KERNELS: dict | None = None


def resident_enabled(n_validators: int) -> bool:
    """Routing polarity for the resident epoch path."""
    raw = os.environ.get("GRAFT_RESIDENT_EPOCH", "").strip().lower()
    if raw in ("0", "false", "no", "off"):
        return False
    if raw in ("", "auto"):
        return n_validators >= _MIN_VALIDATORS
    return True


def _pad_pow2(n: int) -> int:
    """Snap a column length to the warmed pow2 shape bucket."""
    return 1 << max(int(n - 1).bit_length(), 5)


def _scatter_buckets(capacity: int) -> tuple[int, ...]:
    """The DELIBERATELY tiny scatter-index bucket set: one small bucket
    (most boundaries touch few indices) and one at the delta/full-upload
    crossover (sync() never scatters more than n/4 elements).  Padding
    to a coarse bucket costs only duplicate writes of identical values;
    a per-pow2 ladder would cost a live compile per new size — the
    donated scatter kernels have no disk tier, so every bucket here is
    a program the warmer must actually compile."""
    return tuple(sorted({
        min(1024, capacity),
        max(32, _pad_pow2(max(capacity // 4, 1))),
    }))


# --------------------------------------------------------------- kernels


def _kernel_bodies() -> dict:
    """The pure kernel bodies, element-wise over the validator axis.

    Shared VERBATIM by the single-device jit wrappers
    (:func:`_build_kernels`) and the round-21 ``shard_map`` wrappers
    (:func:`_build_sharded_kernels`): the sweep and hysteresis bodies
    are collective-free by construction (no cross-validator data flow),
    so sharding them is purely a placement decision — only the epoch
    sums need one ``psum`` to finish.
    """
    import jax.numpy as jnp

    u32 = jnp.uint32
    i32 = jnp.int32

    def _sums(efb_incr, part_prev, part_cur, active_prev, active_cur, slashed):
        unsl_prev = active_prev & ~slashed
        unsl_cur = active_cur & ~slashed

        def msum(mask):
            return jnp.sum(jnp.where(mask, efb_incr, 0), dtype=i32)

        return jnp.stack([
            msum(active_cur),
            msum(unsl_prev & ((part_prev & 1) != 0)),
            msum(unsl_prev & ((part_prev & 2) != 0)),
            msum(unsl_prev & ((part_prev & 4) != 0)),
            msum(unsl_cur & ((part_cur & 2) != 0)),
        ])

    def _sweep(bal_lo, bal_hi, scores, efb_incr, part_prev, eligible,
               active_prev, slashed, params, luts):
        # params i32[7]: [in_leak, do_inactivity, do_rewards, bias,
        #                 recovery, inactivity_mult, inactivity_shift]
        in_leak, do_inact, do_rew = params[0], params[1], params[2]
        bias, recovery = params[3], params[4]
        mult, shift = params[5].astype(u32), params[6].astype(u32)

        unsl = active_prev & ~slashed
        part_t = unsl & ((part_prev & 2) != 0)

        # inactivity updates (spec order: before rewards, whose
        # inactivity penalty reads the UPDATED scores)
        s = scores
        s = jnp.where(eligible & part_t, s - jnp.minimum(1, s), s)
        s = jnp.where(eligible & ~part_t, s + bias, s)
        s = jnp.where((in_leak == 0) & eligible, s - jnp.minimum(recovery, s), s)
        new_scores = jnp.where(do_inact != 0, s, scores)

        lo, hi = bal_lo, bal_hi
        for f in range(3):
            part_f = unsl & ((part_prev & (1 << f)) != 0)
            reward = jnp.where(
                eligible & part_f, jnp.take(luts[f], efb_incr), 0
            ).astype(u32)
            lo2 = lo + reward
            hi = hi + (lo2 < reward).astype(u32)
            lo = lo2
            if f != constants.TIMELY_HEAD_FLAG_INDEX:
                pen = jnp.where(
                    eligible & ~part_f, jnp.take(luts[3 + f], efb_incr), 0
                ).astype(u32)
                borrow = lo < pen
                nl = lo - pen
                nh = hi - borrow.astype(u32)
                under = borrow & (hi == 0)
                lo = jnp.where(under, 0, nl)
                hi = jnp.where(under, 0, nh)

        # inactivity penalty: (efb_incr * mult * score) >> shift, exact
        # 57-bit product in 16-bit partial products (plane idiom)
        a = (efb_incr.astype(u32)) * mult
        su = new_scores.astype(u32)
        a_l, a_h = a & 0xFFFF, a >> 16
        s_l, s_h = su & 0xFFFF, su >> 16
        p0 = a_l * s_l
        p1 = a_l * s_h + a_h * s_l
        p2 = a_h * s_h
        c0 = p0 >> 16
        w1 = c0 + (p1 & 0xFFFF)
        w2 = (w1 >> 16) + (p1 >> 16) + (p2 & 0xFFFF)
        w3 = (w2 >> 16) + (p2 >> 16)
        prod_lo = (p0 & 0xFFFF) | ((w1 & 0xFFFF) << 16)
        prod_hi = (w2 & 0xFFFF) | (w3 << 16)
        pen_lo = (prod_lo >> shift) | ((prod_hi << (32 - shift)).astype(u32))
        pen_hi = prod_hi >> shift
        apply_pen = (do_rew != 0) & eligible & ~part_t
        pen_lo = jnp.where(apply_pen, pen_lo, 0)
        pen_hi = jnp.where(apply_pen, pen_hi, 0)
        borrow = (lo < pen_lo).astype(u32)
        need = pen_hi + borrow
        under = hi < need
        nl = lo - pen_lo
        nh = hi - need
        lo = jnp.where(under, 0, nl)
        hi = jnp.where(under, 0, nh)

        out_lo = jnp.where(do_rew != 0, lo, bal_lo)
        out_hi = jnp.where(do_rew != 0, hi, bal_hi)
        return out_lo, out_hi, new_scores

    def _hysteresis(bal_lo, bal_hi, efb_incr, hparams):
        # hparams u32[4]: [downward, upward, incr_lo16, incr_hi16] — the
        # increment split so efb = efb_incr * increment stays in partials
        down, up = hparams[0], hparams[1]
        e = efb_incr.astype(u32)
        e_p0 = e * hparams[2]
        e_p1 = e * hparams[3]
        m = (e_p0 >> 16) + e_p1
        e_lo = (e_p0 & 0xFFFF) | ((m & 0xFFFF) << 16)
        e_hi = m >> 16

        def lt(alo, ahi, blo, bhi):
            return (ahi < bhi) | ((ahi == bhi) & (alo < blo))

        bd_lo = bal_lo + down
        bd_hi = bal_hi + (bd_lo < down).astype(u32)
        eu_lo = e_lo + up
        eu_hi = e_hi + (eu_lo < up).astype(u32)
        return lt(bd_lo, bd_hi, e_lo, e_hi) | lt(eu_lo, eu_hi, bal_lo, bal_hi)

    return {"sums": _sums, "sweep": _sweep, "hysteresis": _hysteresis}


def _build_kernels() -> dict:
    """The jitted kernel set — shape-polymorphic wrappers whose compiled
    programs are AOT-cached per padded column shape (aot_jit keys on the
    actual argument signature).

    Donation map: the sweep updates (bal_lo, bal_hi, scores) in place;
    the scatter kernels update their target column in place.  Callers
    MUST rebind their references to the outputs — graftlint's
    retrace-hazard donated-buffer check enforces exactly that.
    """
    import jax

    bodies = _kernel_bodies()

    def _scatter2(lo, hi, idx, v_lo, v_hi):
        return lo.at[idx].set(v_lo), hi.at[idx].set(v_hi)

    def _scatter1(buf, idx, vals):
        return buf.at[idx].set(vals)

    def _gather2(lo, hi, idx):
        return lo[idx], hi[idx]

    # donated programs must NOT hit the serialized-executable disk tier:
    # a deserialized executable's input-output aliasing reads garbage
    # intermittently (see aot_jit's docstring) — they stay in-memory
    # cached and the warmer compiles them off the boot critical path
    return {
        "sums": aot_jit(jax.jit(bodies["sums"]), "transition_sums"),
        "sweep": aot_jit(
            jax.jit(bodies["sweep"], donate_argnums=(0, 1, 2)),
            "transition_sweep", disk=False,
        ),
        "hysteresis": aot_jit(
            jax.jit(bodies["hysteresis"]), "transition_hysteresis"
        ),
        "scatter2": aot_jit(
            jax.jit(_scatter2, donate_argnums=(0, 1)),
            "transition_scatter2", disk=False,
        ),
        "scatter1": aot_jit(
            jax.jit(_scatter1, donate_argnums=(0,)),
            "transition_scatter1", disk=False,
        ),
        "gather2": aot_jit(jax.jit(_gather2), "transition_gather2"),
    }


def _kernels() -> dict:
    global _KERNELS
    with _KERNEL_LOCK:
        if _KERNELS is None:
            _KERNELS = _build_kernels()
        return _KERNELS


_SHARD_KERNELS: dict = {}


def _build_sharded_kernels(mesh) -> dict:
    """The round-21 mesh-sharded kernel set, cached per mesh identity.

    The sweep and hysteresis bodies run UNCHANGED under ``shard_map`` —
    element-wise over the validator axis, every column dealt ``P("dp")``,
    zero communication.  The epoch sums reduce each device's local
    partial through ONE ``psum``.  The scatter/gather kernels take
    per-shard index/value ROWS (``(n_shards, bucket)``, dealt
    ``P("dp", None)``): each device writes only its own row into its
    local column block, so the delta scatter is collective-free too —
    the host routes every touched index to its owning shard
    (:meth:`ResidentEpochPlane._shard_rows`).  ``disk=False``
    throughout: the donated programs must never hit the serialized
    executable tier, and shard_map programs deserialized on the CPU
    mesh are the measured round-4 crash mode.
    """
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    key = tuple(d.id for d in mesh.devices.flat)
    with _KERNEL_LOCK:
        hit = _SHARD_KERNELS.get(key)
        if hit is not None:
            return hit

    bodies = _kernel_bodies()
    col = P("dp")
    row = P("dp", None)
    rep = P()

    def _smap(fn, in_specs, out_specs, name, donate=()):
        kwargs = {"donate_argnums": donate} if donate else {}
        jitted = jax.jit(
            jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            ),
            **kwargs,
        )
        return aot_jit(jitted, name, disk=False)

    def _sums_psum(*args):
        return lax.psum(bodies["sums"](*args), "dp")

    # scatter/gather rows arrive (1, bucket) per device after shard_map
    # splits the leading shard axis: each device applies only ITS row to
    # its local column block — pre-routed by the host
    # (ResidentEpochPlane._shard_rows), so no collective is needed.  The
    # ``own`` mask keeps padded slots as identity read-back writes: a
    # shard with no touched indices cannot know a fresh value to repeat
    # (mid-epoch the host mirrors are stale), so it rewrites what the
    # buffer already holds.
    def _scatter2_rows(lo, hi, idx, v_lo, v_hi, own):
        import jax.numpy as jnp

        i = idx[0]
        new_lo = jnp.where(own[0], v_lo[0], lo[i])
        new_hi = jnp.where(own[0], v_hi[0], hi[i])
        return lo.at[i].set(new_lo), hi.at[i].set(new_hi)

    def _scatter1_rows(buf, idx, vals, own):
        import jax.numpy as jnp

        i = idx[0]
        return buf.at[i].set(jnp.where(own[0], vals[0], buf[i]))

    def _gather2_rows(lo, hi, idx, own):
        import jax.numpy as jnp

        g_lo = jnp.where(own[0], lo[idx[0]], 0)
        g_hi = jnp.where(own[0], hi[idx[0]], 0)
        # each bucket slot is owned by exactly one shard (others
        # contribute zeros), so the sum IS the gather
        return lax.psum(g_lo, "dp"), lax.psum(g_hi, "dp")

    kernels = {
        "sums": _smap(
            _sums_psum, (col,) * 6, rep, "transition_shard_sums"
        ),
        "sweep": _smap(
            bodies["sweep"],
            (col,) * 8 + (rep, rep),
            (col, col, col),
            "transition_shard_sweep",
            donate=(0, 1, 2),
        ),
        "hysteresis": _smap(
            bodies["hysteresis"], (col, col, col, rep), col,
            "transition_shard_hysteresis",
        ),
        "scatter2": _smap(
            _scatter2_rows, (col, col, row, row, row, row), (col, col),
            "transition_shard_scatter2", donate=(0, 1),
        ),
        "scatter1": _smap(
            _scatter1_rows, (col, row, row, row), col,
            "transition_shard_scatter1", donate=(0,),
        ),
        "gather2": _smap(
            _gather2_rows, (col, col, row, row), (rep, rep),
            "transition_shard_gather2",
        ),
    }
    with _KERNEL_LOCK:
        return _SHARD_KERNELS.setdefault(key, kernels)


# ----------------------------------------------------------------- plane


# live planes for the round-18 HBM accounting: weak — a plane's device
# columns free with its state lineage, and accounting must not pin them
_LIVE_PLANES: "weakref.WeakSet[ResidentEpochPlane]" = weakref.WeakSet()

register_plane(
    "resident_epoch",
    lambda: sum(p.device_bytes for p in list(_LIVE_PLANES)),
    devices=lambda: max(
        (p.shard_devices() for p in list(_LIVE_PLANES)), default=1
    ),
)


class ResidentEpochPlane:
    """Persistent device residency for the hot BeaconState columns.

    One plane rides one state lineage (``state._resident_plane``, carried
    across freeze/thaw exactly like the incremental root engine).  Host
    lists stay the source of truth between epoch boundaries; at each
    boundary :meth:`sync` ships only the indices blocks actually touched
    (diffed against the host mirror) and the kernels update the resident
    buffers in place via donation.
    """

    def __init__(self, n_validators: int):
        self.capacity = _pad_pow2(n_validators)
        self.n = 0
        # host mirrors (what the device columns currently hold)
        self.mirror_bal = np.zeros(0, np.uint64)
        self.mirror_scores = np.zeros(0, np.int64)
        self.mirror_part_prev = np.zeros(0, np.uint8)
        self.mirror_part_cur = np.zeros(0, np.uint8)
        # device columns (filled on first sync)
        self.bal_lo = None
        self.bal_hi = None
        self.scores = None
        self.part_prev = None
        self.part_cur = None
        self.stats = {"syncs": 0, "sweeps": 0, "scatter_elems": 0, "fallbacks": 0}
        # delta-chain stamps: field -> (TrackedList instance, gen) the
        # mirrors matched last, so sync can narrow its mirror compare to
        # the indices mutated since (mutable.dirty_superset)
        self._stamps: dict = {}
        # mesh-sharded residency (round 21): decided ONCE at construction
        # — re-deciding per sync would bounce every column between
        # layouts.  Capacity is pow2 and the dp axis is pow2, so the
        # block split is always even.
        self.sharded = False
        self._mesh = None
        self.n_shards = 1
        if state_shard_enabled():
            from ..ops.mesh import default_mesh, mesh_devices

            self._mesh = default_mesh()
            self.n_shards = mesh_devices(self._mesh)
            self.sharded = self.n_shards > 1 and (
                self.capacity % self.n_shards == 0
            )
            if not self.sharded:
                self._mesh, self.n_shards = None, 1
        register_shape_bucket("transition_validators", self.capacity)
        for b in _scatter_buckets(self.capacity):
            register_shape_bucket("transition_scatter", b)
        _LIVE_PLANES.add(self)

    @property
    def device_bytes(self) -> int:
        """Bytes pinned by the resident columns (0 before first sync) —
        the round-18 plane-registry accounting source.  Logical total
        across the mesh; divide by :meth:`shard_devices` for the
        per-device footprint the watermark gauge reports."""
        return sum(
            int(col.nbytes)
            for col in (
                self.bal_lo, self.bal_hi, self.scores,
                self.part_prev, self.part_cur,
            )
            if col is not None
        )

    def shard_devices(self) -> int:
        """How many devices the resident columns are actually spread
        over (1 = replicated/unsharded) — read from the live buffer's
        sharding, not the construction-time intent, so the accounting
        never claims a split that placement fell back from."""
        if self.bal_lo is None:
            return 1
        try:
            return max(1, len(self.bal_lo.sharding.device_set))
        except AttributeError:
            return 1

    # ------------------------------------------------------------- sync

    def _pad_col(self, arr: np.ndarray, dtype) -> np.ndarray:
        out = np.zeros(self.capacity, dtype)
        out[: arr.shape[0]] = arr
        return out

    def _put(self, name: str, arr: np.ndarray):
        """THE column placement path: through the partition-rule table
        when this plane is sharded, plain device residency otherwise."""
        import jax

        if self.sharded:
            return shard_rules.place(name, arr, self._mesh)
        return jax.device_put(arr)

    def _kset(self) -> dict:
        return (
            _build_sharded_kernels(self._mesh) if self.sharded else _kernels()
        )

    def _upload_full(self, balances: np.ndarray, scores: np.ndarray,
                     part_prev: np.ndarray, part_cur: np.ndarray) -> None:
        lo = (balances & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (balances >> np.uint64(32)).astype(np.uint32)
        self.bal_lo = self._put("resident/bal_lo", self._pad_col(lo, np.uint32))
        self.bal_hi = self._put("resident/bal_hi", self._pad_col(hi, np.uint32))
        self.scores = self._put("resident/scores", self._pad_col(scores, np.int32))
        self.part_prev = self._put(
            "resident/part_prev", self._pad_col(part_prev, np.int32)
        )
        self.part_cur = self._put(
            "resident/part_cur", self._pad_col(part_cur, np.int32)
        )

    def _scatter_idx(self, idx: np.ndarray) -> np.ndarray:
        """Pad a scatter index vector to the smallest warmed bucket by
        repeating the first index (duplicate writes of the same value
        are a no-op), so every scatter dispatch lands on a program the
        warmer already compiled.  Oversized vectors (mass slashings via
        slash_fixup) fall back to their own pow2 — rare enough to eat
        one live compile."""
        k = len(idx)
        bucket = next(
            (b for b in _scatter_buckets(self.capacity) if b >= k),
            _pad_pow2(k),
        )
        out = np.full(bucket, idx[0], np.int32)
        out[:k] = idx
        return out

    # ------------------------------------------- sharded delta routing

    def _shard_rows(self, idx: np.ndarray, vals: list) -> tuple:
        """Route a global scatter (``idx`` global indices, ``vals``
        arrays aligned with them) to per-shard rows for the sharded
        scatter kernels: every index lands on its OWNING shard's row
        (owner = global // local_block under the block split), local-
        indexed.  Ragged tails pad by repeating the shard's first entry
        (duplicate identical writes are no-ops); a shard with no touched
        indices pads with ``own=False`` slots the kernel turns into
        identity read-back writes.  Row width snaps to the warmed
        ``transition_scatter`` buckets."""
        d = self.n_shards
        local = self.capacity // d
        owner = idx // local
        li = (idx % local).astype(np.int32)
        counts = np.bincount(owner, minlength=d)
        kmax = int(counts.max())
        bucket = next(
            (b for b in _scatter_buckets(self.capacity) if b >= kmax),
            _pad_pow2(kmax),
        )
        idx_rows = np.zeros((d, bucket), np.int32)
        own_rows = np.zeros((d, bucket), np.bool_)
        val_rows = [np.zeros((d, bucket), v.dtype) for v in vals]
        for s in range(d):
            sel = np.nonzero(owner == s)[0]
            c = sel.size
            if not c:
                continue
            idx_rows[s, :c] = li[sel]
            idx_rows[s, c:] = li[sel][0]
            own_rows[s] = True
            for vr, v in zip(val_rows, vals):
                vr[s, :c] = v[sel]
                vr[s, c:] = v[sel][0]
        return idx_rows, val_rows, own_rows

    def _gather_rows(self, idx: np.ndarray) -> tuple:
        """Per-shard rows for the psum gather: bucket slot ``j`` carries
        global index ``idx[j]`` on its owning shard's row ONLY (every
        other shard contributes a masked zero), so the psum reassembles
        the gathered vector replicated on every device."""
        k = idx.size
        local = self.capacity // self.n_shards
        owner = idx // local
        li = (idx % local).astype(np.int32)
        bucket = next(
            (b for b in _scatter_buckets(self.capacity) if b >= k),
            _pad_pow2(k),
        )
        idx_rows = np.zeros((self.n_shards, bucket), np.int32)
        own_rows = np.zeros((self.n_shards, bucket), np.bool_)
        idx_rows[owner, np.arange(k)] = li
        own_rows[owner, np.arange(k)] = True
        return idx_rows, own_rows

    _STAMP_FIELDS = (
        "balances", "inactivity_scores",
        "previous_epoch_participation", "current_epoch_participation",
    )

    def _stamp_deltas(self, state) -> None:
        """Record the exact TrackedList instances the mirrors now match
        (and their generations): the next sync narrows its mirror
        compare to the indices mutated since, instead of diffing the
        full column — the shard-aware delta-routing feed.  A list that
        is not a TrackedList (or was replaced wholesale) stamps None
        and the next compare is full, which is always exact."""
        for field in self._STAMP_FIELDS:
            lst = getattr(state, field, None)
            gen = getattr(lst, "gen", None)
            self._stamps[field] = None if gen is None else (lst, gen)

    def _changed_idx(self, field: str, state, mirror: np.ndarray,
                     new: np.ndarray) -> np.ndarray:
        """Indices where the device column is stale.  The delta-chain
        stamp narrows the compare to a provable superset of the touched
        indices (mutable.dirty_superset); candidates are still value-
        compared against the mirror, so the result is exact either way."""
        hint = None
        st = self._stamps.get(field)
        lst = getattr(state, field, None)
        if st is not None and lst is not None and mirror.shape[0] == new.shape[0]:
            from .mutable import dirty_superset

            hint = dirty_superset(lst, st[0], st[1])
        if hint is None:
            return np.nonzero(mirror != new)[0]
        n = new.shape[0]
        cand = np.fromiter((i for i in hint if 0 <= i < n), np.int64)
        if cand.size == 0:
            return cand
        cand.sort()
        return cand[mirror[cand] != new[cand]]

    def _scatter1_col(self, col2: str, changed: np.ndarray,
                      new: np.ndarray) -> None:
        """One int32 column delta scatter, routed per-shard when the
        plane is sharded, through the warmed flat buckets otherwise."""
        k = self._kset()
        buf = getattr(self, col2)
        if self.sharded:
            idx_rows, (vals,), own = self._shard_rows(
                changed, [new[changed].astype(np.int32)]
            )
            setattr(self, col2, k["scatter1"](buf, idx_rows, vals, own))
        else:
            idx = self._scatter_idx(changed.astype(np.int32))
            setattr(self, col2, k["scatter1"](buf, idx, new[idx].astype(np.int32)))

    def sync(self, state, spec: ChainSpec) -> bool:
        """Bring the device columns up to date with ``state``; False when
        the state is outside the kernels' representable range (caller
        falls back to the host path)."""
        n = len(state.validators)
        balances = state.balances_array()
        scores = np.asarray(state.inactivity_scores, np.int64)
        part_prev = state.participation_array("previous")
        part_cur = state.participation_array("current")
        if n == 0 or int(balances.max(initial=0)) >= _MAX_BAL:
            return False
        if scores.size and (int(scores.max()) >= _MAX_SCORE or int(scores.min()) < 0):
            return False
        if n > self.capacity:
            self.capacity = _pad_pow2(n)
            register_shape_bucket("transition_validators", self.capacity)
            for b in _scatter_buckets(self.capacity):
                register_shape_bucket("transition_scatter", b)
            self.n = 0  # force the full re-upload below

        self.stats["syncs"] += 1
        if self.bal_lo is None or self.n != n:
            self._upload_full(balances, scores, part_prev, part_cur)
        else:
            k = self._kset()
            for field, mirror, new, col2 in (
                ("previous_epoch_participation",
                 self.mirror_part_prev, part_prev, "part_prev"),
                ("current_epoch_participation",
                 self.mirror_part_cur, part_cur, "part_cur"),
            ):
                changed = self._changed_idx(field, state, mirror, new)
                if changed.size == 0:
                    continue
                if changed.size > n // 4:
                    setattr(
                        self, col2,
                        self._put(
                            f"resident/{col2}", self._pad_col(new, np.int32)
                        ),
                    )
                else:
                    self._scatter1_col(col2, changed, new)
                    self.stats["scatter_elems"] += int(changed.size)
            changed = self._changed_idx(
                "balances", state, self.mirror_bal, balances
            )
            if changed.size:
                if changed.size > n // 4:
                    lo = (balances & np.uint64(0xFFFFFFFF)).astype(np.uint32)
                    hi = (balances >> np.uint64(32)).astype(np.uint32)
                    self.bal_lo = self._put(
                        "resident/bal_lo", self._pad_col(lo, np.uint32)
                    )
                    self.bal_hi = self._put(
                        "resident/bal_hi", self._pad_col(hi, np.uint32)
                    )
                elif self.sharded:
                    v = balances[changed]
                    idx_rows, (vlo, vhi), own = self._shard_rows(
                        changed,
                        [
                            (v & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                            (v >> np.uint64(32)).astype(np.uint32),
                        ],
                    )
                    self.bal_lo, self.bal_hi = k["scatter2"](
                        self.bal_lo, self.bal_hi, idx_rows, vlo, vhi, own
                    )
                    self.stats["scatter_elems"] += int(changed.size)
                else:
                    idx = self._scatter_idx(changed.astype(np.int32))
                    v = balances[idx]
                    self.bal_lo, self.bal_hi = k["scatter2"](
                        self.bal_lo, self.bal_hi, idx,
                        (v & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                        (v >> np.uint64(32)).astype(np.uint32),
                    )
                    self.stats["scatter_elems"] += int(changed.size)
            changed = self._changed_idx(
                "inactivity_scores", state, self.mirror_scores, scores
            )
            if changed.size:
                if changed.size > n // 4:
                    # wholesale change (a host-fallback leak epoch moved
                    # every score): full upload, like the other columns —
                    # a full-size scatter would pad past the warmed
                    # buckets and live-compile a donated kernel
                    self.scores = self._put(
                        "resident/scores", self._pad_col(scores, np.int32)
                    )
                else:
                    self._scatter1_col("scores", changed, scores)
        self.n = n
        self.mirror_bal = balances.copy()
        self.mirror_scores = scores.copy()
        self.mirror_part_prev = part_prev.copy()
        self.mirror_part_cur = part_cur.copy()
        self._stamp_deltas(state)
        set_gauge("resident_plane_validators", n)
        return True

    # ------------------------------------------------------- epoch steps

    def masks(self, reg: dict, prev_epoch: int, curr_epoch: int):
        active_prev = (reg["activation_epoch"] <= prev_epoch) & (
            prev_epoch < reg["exit_epoch"]
        )
        active_cur = (reg["activation_epoch"] <= curr_epoch) & (
            curr_epoch < reg["exit_epoch"]
        )
        eligible = active_prev | (
            reg["slashed"] & (prev_epoch + 1 < reg["withdrawable_epoch"])
        )
        return active_prev, active_cur, eligible, reg["slashed"]

    def epoch_sums(self, efb_incr, active_prev, active_cur, slashed):
        """[total_active, flag0, flag1, flag2, curr_target] increment sums."""
        k = self._kset()
        out = k["sums"](
            self._pad_col(efb_incr, np.int32),
            self.part_prev,
            self.part_cur,
            self._pad_col(active_prev, np.bool_),
            self._pad_col(active_cur, np.bool_),
            self._pad_col(slashed, np.bool_),
        )
        return [int(x) for x in np.asarray(out)]

    def sweep(self, efb_incr, eligible, active_prev, slashed, params, luts):
        """Dispatch the donated rewards/inactivity sweep; the plane's
        balance/score buffers are replaced by the in-place outputs."""
        k = self._kset()
        self.bal_lo, self.bal_hi, self.scores = k["sweep"](
            self.bal_lo, self.bal_hi, self.scores,
            self._pad_col(efb_incr, np.int32),
            self.part_prev,
            self._pad_col(eligible, np.bool_),
            self._pad_col(active_prev, np.bool_),
            self._pad_col(slashed, np.bool_),
            np.asarray(params, np.int32),
            np.asarray(luts, np.int32),
        )
        self.stats["sweeps"] += 1

    def slash_fixup(self, targets: np.ndarray, efb_incr: np.ndarray,
                    adjusted_total: int, total_balance: int, increment: int) -> None:
        """Exact per-target slashing penalties: gather the (rare) target
        balances, do the >64-bit arithmetic in host ints, scatter back."""
        k = self._kset()
        if self.sharded:
            idx = targets.astype(np.int64)
            g_rows, g_own = self._gather_rows(idx)
            g_lo, g_hi = k["gather2"](self.bal_lo, self.bal_hi, g_rows, g_own)
        else:
            idx = self._scatter_idx(targets.astype(np.int32))
            g_lo, g_hi = k["gather2"](self.bal_lo, self.bal_hi, idx)
        bal = np.asarray(g_lo).astype(np.uint64) | (
            np.asarray(g_hi).astype(np.uint64) << np.uint64(32)
        )
        new = bal.copy()
        # in the sharded case idx is exactly the kt targets and bal's
        # padded tail stays untouched (masked zero gather slots); in the
        # flat case idx is bucket-padded by repeating idx[0], so padded
        # slots recompute the identical penalty (duplicate same-value
        # writes stay deterministic)
        for j, i in enumerate(idx):
            pen_num = int(efb_incr[i]) * adjusted_total
            penalty = pen_num // total_balance * increment
            new[j] = max(0, int(bal[j]) - penalty)
        if self.sharded:
            kt = targets.size
            idx_rows, (vlo, vhi), own = self._shard_rows(
                idx,
                [
                    (new[:kt] & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                    (new[:kt] >> np.uint64(32)).astype(np.uint32),
                ],
            )
            self.bal_lo, self.bal_hi = k["scatter2"](
                self.bal_lo, self.bal_hi, idx_rows, vlo, vhi, own
            )
        else:
            self.bal_lo, self.bal_hi = k["scatter2"](
                self.bal_lo, self.bal_hi, idx,
                (new & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                (new >> np.uint64(32)).astype(np.uint32),
            )

    def hysteresis_mask(self, efb_incr, downward, upward, increment) -> np.ndarray:
        k = self._kset()
        mask = k["hysteresis"](
            self.bal_lo, self.bal_hi,
            self._pad_col(efb_incr, np.int32),
            np.asarray(
                [downward, upward, increment & 0xFFFF, increment >> 16],
                np.uint32,
            ),
        )
        return np.asarray(mask)[: self.n]

    def balances_to_host(self) -> np.ndarray:
        lo = np.asarray(self.bal_lo)[: self.n].astype(np.uint64)
        hi = np.asarray(self.bal_hi)[: self.n].astype(np.uint64)
        return lo | (hi << np.uint64(32))

    def scores_to_host(self) -> np.ndarray:
        return np.asarray(self.scores)[: self.n].astype(np.int64)

    def rotate_participation(self) -> None:
        """Device-side mirror of the epoch participation reset: previous
        adopts current's buffer, current becomes zeros (no upload).  The
        handed-over buffer keeps its layout, so the fresh zeros column
        must be PLACED in the rule-table layout too — a replicated
        current column would silently double the per-device footprint."""
        self.part_prev = self.part_cur
        if self.sharded:
            self.part_cur = self._put(
                "resident/part_cur", np.zeros(self.capacity, np.int32)
            )
        else:
            import jax.numpy as jnp

            self.part_cur = jnp.zeros(self.capacity, jnp.int32)
        self.mirror_part_prev = self.mirror_part_cur
        self.mirror_part_cur = np.zeros(self.n, np.uint8)


# -------------------------------------------------------- epoch sequence


def ensure_plane(state, spec: ChainSpec | None = None):
    """Attach a resident plane to the lineage when routing says so."""
    plane = getattr(state, "_resident_plane", None)
    if plane is not None:
        return plane
    n = len(state.validators)
    if not resident_enabled(n):
        return None
    plane = ResidentEpochPlane(n)
    try:
        state._resident_plane = plane
    except AttributeError:  # frozen container: attach out-of-band
        object.__setattr__(state, "_resident_plane", plane)
    return plane


def _reward_tables(spec: ChainSpec, brpi: int, in_leak: bool,
                   active_incr: int, flag_incr: list[int]) -> list[list[int]] | None:
    """Exact per-increment reward/penalty tables for the sweep kernel:
    rows 0-2 are flag rewards, rows 3-4 are source/target penalties
    (the head flag carries no penalty).  ``None`` when any entry would
    overflow a single uint32 limb."""
    max_incr = spec.MAX_EFFECTIVE_BALANCE // spec.EFFECTIVE_BALANCE_INCREMENT
    denom = active_incr * constants.WEIGHT_DENOMINATOR
    luts: list[list[int]] = []
    for f, weight in enumerate(constants.PARTICIPATION_FLAG_WEIGHTS):
        row = []
        for j in range(max_incr + 1):
            v = 0 if in_leak else (j * brpi) * weight * flag_incr[f] // denom
            if v >= _MAX_LUT:
                return None
            row.append(v)
        luts.append(row)
    for f in (constants.TIMELY_SOURCE_FLAG_INDEX, constants.TIMELY_TARGET_FLAG_INDEX):
        weight = constants.PARTICIPATION_FLAG_WEIGHTS[f]
        row = []
        for j in range(max_incr + 1):
            v = (j * brpi) * weight // constants.WEIGHT_DENOMINATOR
            if v >= _MAX_LUT:
                return None
            row.append(v)
        luts.append(row)
    return luts


def _inactivity_factors(spec: ChainSpec) -> tuple[int, int] | None:
    """Reduce ``efb * score // (bias * quotient)`` to an exact
    multiply-shift ``(efb_incr * mult * score) >> shift``; ``None`` when
    the spec constants don't factor into the kernel's limb bounds."""
    increment = spec.EFFECTIVE_BALANCE_INCREMENT
    denom = spec.INACTIVITY_SCORE_BIAS * spec.INACTIVITY_PENALTY_QUOTIENT_BELLATRIX
    g = math.gcd(increment, denom)
    mult, rest = increment // g, denom // g
    if rest & (rest - 1):  # must be a pure power of two (a shift)
        return None
    shift = rest.bit_length() - 1
    max_incr = spec.MAX_EFFECTIVE_BALANCE // increment
    if max_incr * mult >= _MAX_MULT or not 0 < shift < 32:
        return None
    return mult, shift


def process_epoch_resident(state, plane: ResidentEpochPlane,
                           spec: ChainSpec | None = None) -> bool:
    """The full epoch sequence through the resident plane.  Returns False
    (having changed nothing) when any guard fails — the caller then runs
    the bit-exact host path."""
    from . import accessors
    from .epoch import (
        process_eth1_data_reset,
        process_historical_summaries_update,
        process_participation_flag_updates,
        process_randao_mixes_reset,
        process_registry_updates,
        process_slashings_reset,
        process_sync_committee_updates,
        weigh_justification_and_finalization,
    )

    spec = spec or get_chain_spec()
    increment = spec.EFFECTIVE_BALANCE_INCREMENT
    max_incr = spec.MAX_EFFECTIVE_BALANCE // increment
    factors = _inactivity_factors(spec)
    if factors is None:
        plane.stats["fallbacks"] += 1
        return False
    n = len(state.validators)
    if n * max_incr >= (1 << 31):  # the i32 increment sums would overflow
        plane.stats["fallbacks"] += 1
        return False
    reg = state.registry()
    efb = reg["effective_balance"]
    if int(efb.max(initial=0)) > spec.MAX_EFFECTIVE_BALANCE or np.any(
        efb % np.uint64(increment)
    ):
        plane.stats["fallbacks"] += 1
        return False
    with span("epoch_plane_sync"):
        synced = plane.sync(state, spec)
    if not synced:
        plane.stats["fallbacks"] += 1
        return False

    efb_incr = (efb // np.uint64(increment)).astype(np.int32)
    curr_epoch = accessors.get_current_epoch(state, spec)
    prev_epoch = accessors.get_previous_epoch(state, spec)
    active_prev, active_cur, eligible, slashed = plane.masks(
        reg, prev_epoch, curr_epoch
    )

    # one span from the first dispatch (the sums) to the last fetched
    # result (the hysteresis mask): the donated sweep runs on the device
    # under the host's justification, registry updates and slashings
    with span("epoch_plane_sweep"):
        # device sums first, then EVERY remaining guard — no state mutation
        # may precede a possible False return, or the host fallback would
        # re-apply passes the resident path already ran
        sums = plane.epoch_sums(efb_incr, active_prev, active_cur, slashed)
        total_active = max(increment, sums[0] * increment)
        brpi = (
            increment * spec.BASE_REWARD_FACTOR // integer_squareroot(total_active)
        )
        flag_incr = [
            max(increment, sums[1 + f] * increment) // increment for f in range(3)
        ]
        # probe with in_leak=False (the LARGER table values; the leak
        # variant zeroes rewards) so the overflow guard can run before
        # justification mutates the state
        luts = _reward_tables(
            spec, brpi, False, total_active // increment, flag_incr
        )
        if luts is None:
            plane.stats["fallbacks"] += 1
            return False

        # (1) justification and finalization, from the device sums
        if curr_epoch > constants.GENESIS_EPOCH + 1:
            weigh_justification_and_finalization(
                state,
                total_active,
                max(increment, sums[2] * increment),
                max(increment, sums[4] * increment),
                spec,
            )

        # (2)+(3) inactivity updates + rewards/penalties, one donated sweep.
        # in_leak reads the finalized checkpoint just/fin may have moved.
        in_leak = accessors.is_in_inactivity_leak(state, spec)
        do_epoch = curr_epoch != constants.GENESIS_EPOCH
        if in_leak:
            luts = _reward_tables(
                spec, brpi, True, total_active // increment, flag_incr
            )
        mult, shift = factors
        plane.sweep(
            efb_incr, eligible, active_prev, slashed,
            [
                int(in_leak), int(do_epoch), int(do_epoch),
                spec.INACTIVITY_SCORE_BIAS, spec.INACTIVITY_SCORE_RECOVERY_RATE,
                mult, shift,
            ],
            luts,
        )

        # (4) registry updates: sequential churn/queue logic, host exact
        process_registry_updates(state, spec)

        # (5) slashings: rare targets, exact >64-bit host arithmetic
        targets = np.nonzero(
            slashed
            & (curr_epoch + spec.EPOCHS_PER_SLASHINGS_VECTOR // 2
               == reg["withdrawable_epoch"])
        )[0]
        if targets.size:
            adjusted_total = min(
                sum(state.slashings) * spec.PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX,
                total_active,
            )
            plane.slash_fixup(targets, efb_incr, adjusted_total, total_active, increment)

        process_eth1_data_reset(state, spec)

        # (7) effective-balance hysteresis: device mask, host fixups.  The
        # mask reads the post-sweep/post-slashing resident balances.
        mask = plane.hysteresis_mask(
            efb_incr,
            increment // spec.HYSTERESIS_QUOTIENT * spec.HYSTERESIS_DOWNWARD_MULTIPLIER,
            increment // spec.HYSTERESIS_QUOTIENT * spec.HYSTERESIS_UPWARD_MULTIPLIER,
            increment,
        )
    with span("epoch_writeback"):
        balances = plane.balances_to_host()
        scores = plane.scores_to_host()
        for i in np.nonzero(mask)[0]:
            b = int(balances[i])
            state.update_validator(
                int(i),
                effective_balance=min(b - b % increment, spec.MAX_EFFECTIVE_BALANCE),
            )

        # the deltas flow back: balances/scores lists adopt the device
        # results (the incremental engine rebuilds those two columns through
        # its backend), participation rotates structurally on all three
        # tiers — host lists, root-engine subtrees, resident buffers.
        state.set_balances(balances)
        state.inactivity_scores = [int(s) for s in scores]
        plane.mirror_bal = balances.copy()
        plane.mirror_scores = scores.copy()

    process_slashings_reset(state, spec)
    process_randao_mixes_reset(state, spec)
    process_historical_summaries_update(state, spec)
    process_participation_flag_updates(state, spec)
    plane.rotate_participation()
    process_sync_committee_updates(state, spec)
    # mirrors now match the post-epoch lists again: re-stamp so the NEXT
    # boundary's sync narrows its compare to the block deltas in between
    plane._stamp_deltas(state)
    set_gauge("resident_plane_sync_elems", plane.stats["scatter_elems"])
    return True


# ---------------------------------------------------------------- warmup


def warm_transition_programs(n_validators: int) -> float:
    """Load/compile every transition kernel at the padded registry shape
    (plus the scatter buckets) under the ``warmup:transition`` compile
    context, so a cold process's first epoch boundary dispatches resident
    programs instead of tracing them mid-replay.  Returns seconds spent."""
    import time

    t0 = time.perf_counter()
    cap = _pad_pow2(n_validators)
    # mirror ResidentEpochPlane's construction-time sharding decision so
    # the warmer compiles the kernel set the plane will actually dispatch
    sharded, mesh, nsh = False, None, 1
    if state_shard_enabled():
        from ..ops.mesh import default_mesh, mesh_devices

        mesh = default_mesh()
        nsh = mesh_devices(mesh)
        sharded = nsh > 1 and cap % nsh == 0
    k = _build_sharded_kernels(mesh) if sharded else _kernels()
    zb = np.zeros(cap, np.bool_)
    zi = np.zeros(cap, np.int32)
    # distinct buffers for the donated positions: numpy inputs are copied
    # to device anyway, but never reusing a donated name keeps this
    # warmup an example of the discipline the lint rule enforces
    d_lo = np.zeros(cap, np.uint32)
    d_hi = np.zeros(cap, np.uint32)
    d_scores = np.zeros(cap, np.int32)
    with compile_context("warmup:transition"):
        np.asarray(k["sums"](zi, zi, zi, zb, zb, zb))
        lo, hi, _scores = k["sweep"](
            d_lo, d_hi, d_scores, zi, zi, zb, zb, zb,
            np.zeros(7, np.int32), np.zeros((5, 33), np.int32),
        )
        np.asarray(k["hysteresis"](lo, hi, zi, np.zeros(4, np.uint32)))
        # every scatter/gather bucket sync() can dispatch — the donated
        # kernels have no disk tier, so an unwarmed bucket would compile
        # live inside the first epoch boundary
        for b in _scatter_buckets(cap):
            if sharded:
                idx = np.zeros((nsh, b), np.int32)
                own = np.zeros((nsh, b), np.bool_)
                u = idx.astype(np.uint32)
                lo, hi = k["scatter2"](lo, hi, idx, u, u, own)
                np.asarray(k["scatter1"](np.zeros(cap, np.int32), idx, idx, own))
                np.asarray(k["gather2"](lo, hi, idx, own)[0])
            else:
                idx = np.zeros(b, np.int32)
                lo, hi = k["scatter2"](lo, hi, idx, idx.astype(np.uint32),
                                       idx.astype(np.uint32))
                np.asarray(k["scatter1"](np.zeros(cap, np.int32), idx, idx))
                np.asarray(k["gather2"](lo, hi, idx)[0])
    register_shape_bucket("transition_validators", cap)
    for b in _scatter_buckets(cap):
        register_shape_bucket("transition_scatter", b)
    dt = time.perf_counter() - t0
    observe("warmup_phase_seconds", dt, phase="transition")
    return dt
