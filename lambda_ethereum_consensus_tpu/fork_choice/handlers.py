"""Fork-choice handlers (ref: lib/.../fork_choice/handlers.ex:28-350).

``on_block`` runs the *full* state transition (the reference copies the parent
state instead — ref: handlers.ex:80-88 — with the real path parked at
:157-189); unrealized-checkpoint pull-ups follow spec v1.3.
"""

from __future__ import annotations

import logging
import time as _time

from ..config import ChainSpec, constants, get_chain_spec
from ..state_transition import accessors, misc
from ..state_transition.core import state_transition
from ..state_transition.epoch import process_justification_and_finalization
from ..state_transition.errors import SpecError
from ..state_transition.mutable import BeaconStateMut
from ..state_transition.predicates import (
    is_slashable_attestation_data,
    is_valid_indexed_attestation,
)
from ..telemetry import device_fault, get_metrics, span
from ..types.beacon import Attestation, AttesterSlashing, Checkpoint, SignedBeaconBlock
from .store import ForkChoiceError, LatestMessage, Store, checkpoint_key

log = logging.getLogger("fork_choice")


def expect(cond: bool, reason: str) -> None:
    if not cond:
        raise ForkChoiceError(reason)


# -------------------------------------------------------------------- tick

def on_tick(store: Store, time: int, spec: ChainSpec | None = None) -> None:
    """Advance wall-clock time slot by slot (ref: handlers.ex:28-42)."""
    spec = spec or get_chain_spec()
    tick_slot = (time - store.genesis_time) // spec.SECONDS_PER_SLOT
    while store.current_slot(spec) < tick_slot:
        previous_time = store.genesis_time + (store.current_slot(spec) + 1) * spec.SECONDS_PER_SLOT
        _on_tick_per_slot(store, previous_time, spec)
    _on_tick_per_slot(store, time, spec)


def _on_tick_per_slot(store: Store, time: int, spec: ChainSpec) -> None:
    previous_slot = store.current_slot(spec)
    store.time = time
    current_slot = store.current_slot(spec)
    if current_slot > previous_slot:
        store.proposer_boost_root = b"\x00" * 32
        store.bump()
        if store.slots_since_epoch_start(spec) == 0:
            update_checkpoints(
                store,
                store.unrealized_justified_checkpoint,
                store.unrealized_finalized_checkpoint,
            )


def update_checkpoints(
    store: Store, justified: Checkpoint, finalized: Checkpoint
) -> None:
    forensics = getattr(store, "forensics", None)
    if justified.epoch > store.justified_checkpoint.epoch:
        store.justified_checkpoint = justified
        store.bump()
        if forensics is not None:
            forensics.note_justified(int(justified.epoch), bytes(justified.root))
    if finalized.epoch > store.finalized_checkpoint.epoch:
        store.finalized_checkpoint = finalized
        store.bump()
        if forensics is not None:
            forensics.note_finalized(int(finalized.epoch), bytes(finalized.root))
        if store.head_cache is not None:
            store.head_cache.prune(bytes(finalized.root))
        # checkpoint states + attestation contexts below the finalized
        # epoch can never be referenced again — free the states, committee
        # tables and device caches they pin
        store.prune_checkpoint_caches(int(finalized.epoch))


def update_unrealized_checkpoints(
    store: Store, justified: Checkpoint, finalized: Checkpoint
) -> None:
    if justified.epoch > store.unrealized_justified_checkpoint.epoch:
        store.unrealized_justified_checkpoint = justified
    if finalized.epoch > store.unrealized_finalized_checkpoint.epoch:
        store.unrealized_finalized_checkpoint = finalized


# ------------------------------------------------------------------- block

def on_block(
    store: Store,
    signed_block: SignedBeaconBlock,
    execution_engine=None,
    spec: ChainSpec | None = None,
) -> bytes:
    """Validate + apply a block; returns its root (ref: handlers.ex:51-90)."""
    with span("fork_choice_on_block"):
        return _on_block(store, signed_block, execution_engine, spec or get_chain_spec())


def _on_block(store: Store, signed_block, execution_engine, spec: ChainSpec) -> bytes:
    block = signed_block.message
    parent_root = bytes(block.parent_root)
    expect(parent_root in store.block_states, "unknown parent block")
    pre_state = store.block_states[parent_root]
    expect(store.current_slot(spec) >= block.slot, "block is from the future")
    finalized_slot = misc.compute_start_slot_at_epoch(
        store.finalized_checkpoint.epoch, spec
    )
    expect(block.slot > finalized_slot, "block slot not after finalized slot")
    expect(
        store.get_checkpoint_block(
            parent_root, store.finalized_checkpoint.epoch, spec
        )
        == bytes(store.finalized_checkpoint.root),
        "block does not descend from finalized checkpoint",
    )

    # The real compute: full state transition with validation on (the
    # block_transition span now lives inside state_transition itself, so
    # the replay drivers time the same region as the live on_block path).
    state = state_transition(
        pre_state, signed_block, validate_result=True,
        execution_engine=execution_engine, spec=spec,
    )
    with span("on_block_store_update"):
        root = block.hash_tree_root(spec)
        store.add_block(root, block, state)
        forensics = getattr(store, "forensics", None)
        if forensics is not None:
            # evidence ledger: a second distinct root for (slot, proposer)
            # is a double proposal — observed here, AFTER full validation,
            # so only blocks that actually entered fork choice count
            forensics.note_block(root, int(block.slot), int(block.proposer_index))

        # proposer boost for timely blocks (first 1/INTERVALS_PER_SLOT of the slot)
        time_into_slot = (store.time - store.genesis_time) % spec.SECONDS_PER_SLOT
        is_before_attesting_interval = time_into_slot < (
            spec.SECONDS_PER_SLOT // constants.INTERVALS_PER_SLOT
        )
        if store.current_slot(spec) == block.slot and is_before_attesting_interval:
            store.proposer_boost_root = root
            store.bump()

        update_checkpoints(
            store, state.current_justified_checkpoint, state.finalized_checkpoint
        )
    with span("on_block_pulled_up_tip"):
        compute_pulled_up_tip(store, root, state, spec)
    return root


def compute_pulled_up_tip(
    store: Store, block_root: bytes, state, spec: ChainSpec
) -> None:
    """Unrealized justification: run the FFG pass one epoch early
    (ref: handlers.ex compute_pulled_up_tip / spec v1.3)."""
    ws = BeaconStateMut(state)
    process_justification_and_finalization(ws, spec)
    unrealized_justified = ws.current_justified_checkpoint
    unrealized_finalized = ws.finalized_checkpoint
    store.unrealized_justifications[block_root] = unrealized_justified
    update_unrealized_checkpoints(store, unrealized_justified, unrealized_finalized)

    block = store.blocks[block_root]
    block_epoch = misc.compute_epoch_at_slot(block.slot, spec)
    current_epoch = misc.compute_epoch_at_slot(store.current_slot(spec), spec)
    if block_epoch < current_epoch:
        update_checkpoints(store, unrealized_justified, unrealized_finalized)


# ------------------------------------------------------------- attestation

def attestation_batch_target() -> int:
    """The smallest attestation batch worth a device dispatch — the
    ingest scheduler's coalescing target for the attestation lanes.

    Reads the SAME parse ``crypto.bls.batch._chain_enabled`` routes on
    (``device_chain_threshold``), so the hint and the actual device
    routing can never disagree — and a malformed env value fails node
    startup loudly instead of silently coalescing to a default.
    Clamped to >= 1 because a coalesce target of 0 is meaningless for a
    flush trigger (a 0 threshold means "device for everything" — flush
    on any depth)."""
    from ..crypto.bls.batch import device_chain_threshold

    return max(1, device_chain_threshold())


def validate_target_epoch_against_current_time(
    store: Store, attestation: Attestation, spec: ChainSpec
) -> None:
    target = attestation.data.target
    current_epoch = misc.compute_epoch_at_slot(store.current_slot(spec), spec)
    previous_epoch = max(current_epoch - 1, constants.GENESIS_EPOCH)
    expect(
        target.epoch in (current_epoch, previous_epoch),
        "attestation target epoch not current or previous",
    )


def validate_on_attestation(
    store: Store, attestation: Attestation, is_from_block: bool, spec: ChainSpec
) -> None:
    target = attestation.data.target
    if not is_from_block:
        validate_target_epoch_against_current_time(store, attestation, spec)
    expect(
        target.epoch == misc.compute_epoch_at_slot(attestation.data.slot, spec),
        "attestation target epoch does not match slot",
    )
    expect(bytes(target.root) in store.blocks, "unknown attestation target block")
    beacon_block_root = bytes(attestation.data.beacon_block_root)
    expect(beacon_block_root in store.blocks, "unknown attestation head block")
    expect(
        store.blocks[beacon_block_root].slot <= attestation.data.slot,
        "attestation head block is newer than attestation",
    )
    expect(
        store.get_checkpoint_block(beacon_block_root, target.epoch, spec)
        == bytes(target.root),
        "attestation target does not match head block's checkpoint",
    )
    expect(
        store.current_slot(spec) >= attestation.data.slot + 1,
        "attestation is for a future slot",
    )


def store_target_checkpoint_state(
    store: Store, target: Checkpoint, spec: ChainSpec
) -> None:
    from ..state_transition.core import process_slots

    key = checkpoint_key(target)
    if key not in store.checkpoint_states:
        base = store.block_states[bytes(target.root)]
        start_slot = misc.compute_start_slot_at_epoch(target.epoch, spec)
        if base.slot < start_slot:
            base = process_slots(base, start_slot, spec)
        store.checkpoint_states[key] = base


def update_latest_messages(
    store: Store, attesting_indices, attestation: Attestation
) -> None:
    target = attestation.data.target
    beacon_block_root = bytes(attestation.data.beacon_block_root)
    non_equivocating = [
        i for i in attesting_indices if i not in store.equivocating_indices
    ]
    cache = store.head_cache
    target_state = (
        store.checkpoint_states.get(checkpoint_key(target))
        if cache is not None
        else None
    )
    updated = False
    for i in non_equivocating:
        prev = store.latest_messages.get(i)
        if prev is None or target.epoch > prev.epoch:
            store.latest_messages[i] = LatestMessage(
                epoch=int(target.epoch), root=beacon_block_root
            )
            store.note_vote(i, int(target.epoch))
            updated = True
            if cache is not None and target_state is not None:
                cache.on_vote(
                    i,
                    beacon_block_root,
                    int(target_state.validators[i].effective_balance),
                )
    if updated:
        # one memo invalidation per attestation, not per validator
        store.bump()


def _prepare_attestation(
    store: Store, attestation: Attestation, is_from_block: bool, spec: ChainSpec
):
    """Shared validation prefix of the per-item and batched paths: fork-choice
    checks, checkpoint-state materialization, committee resolution.  Returns
    ``(target_state, indexed_attestation)``."""
    validate_on_attestation(store, attestation, is_from_block, spec)
    store_target_checkpoint_state(store, attestation.data.target, spec)
    target_state = store.checkpoint_states[checkpoint_key(attestation.data.target)]
    indexed = accessors.get_indexed_attestation(target_state, attestation, spec)
    return target_state, indexed


def on_attestation(
    store: Store,
    attestation: Attestation,
    is_from_block: bool = False,
    spec: ChainSpec | None = None,
) -> None:
    """Validate and record an attestation's LMD vote
    (ref: handlers.ex:100-119)."""
    spec = spec or get_chain_spec()
    try:
        target_state, indexed = _prepare_attestation(
            store, attestation, is_from_block, spec
        )
        expect(
            is_valid_indexed_attestation(target_state, indexed, spec),
            "invalid attestation signature",
        )
    except SpecError as e:
        raise ForkChoiceError(str(e)) from None
    update_latest_messages(store, indexed.attesting_indices, attestation)


def on_attestation_batch(
    store: Store,
    attestations: list[Attestation],
    is_from_block: bool = False,
    spec: ChainSpec | None = None,
    traces: list | None = None,
) -> list[ForkChoiceError | None]:
    """Record many attestations with ONE batched signature check.

    The TPU-shaped replacement for per-message verification (SURVEY.md §2.3:
    "collect N gossip messages -> one batched verify"): structural validation
    runs per item, and all signatures are checked in one random-linear-
    combination pairing product with bisection blame attribution (one bad
    item costs O(log N) sub-batches, not 2N pairings).  Returns one ``None``
    (accepted) or ``ForkChoiceError`` (rejected) per input.

    Two bodies behind one contract (VERDICT r4 next #1 — the node path must
    run the machinery the headline measures):

    - **cached device drain** (whenever the chained device pipeline is on,
      at any batch size): aggregate pubkeys come from the epoch-scoped
      ``DeviceCommitteeCache`` as ``full_sum[committee] - sum(missing
      members)`` — or the attesting members' sum, whichever list is
      shorter — computed ON DEVICE, participation is reduced
      with numpy bit ops, and accepted votes land through the vectorized
      latest-message/head-cache batch path;
    - **host path**: the per-item ``affine_add`` walk over cached pubkey
      points, for non-device hosts.

    ``traces`` (position-aligned with ``attestations``, entries may be
    None) links this ONE batched verify back to its member item traces:
    the batch span carries the member trace ids, each member records the
    batch id plus its outcome (``apply`` + the admission→apply latency
    histogram, or ``drop`` with the error) — the causal fan-in that
    makes "which flush verified this vote, and with whom" answerable
    from a ``/debug/trace`` dump.  Batch spans and trace records carry
    ``n_devices`` so a ``/debug/trace`` dump distinguishes sharded from
    single-device flushes.

    Path selection on a multi-device mesh (round 11): when the sharded
    DRAIN is opted in (``crypto.bls.batch.shard_drain_active`` —
    ``BLS_SHARD_DRAIN=1`` on top of an active sharded plane), the drain
    runs the host-prep body, whose ``batch_verify_each_points`` routes
    every RLC check through
    :func:`...ops.bls_shard.sharded_chain_verify` — points and
    coefficients dealt over the 8-chip ``dp`` axis.  Without the
    opt-in, a multi-device mesh keeps the epoch-committee device-cache
    drain (aggregate pubkeys never touch the host — the r04-measured
    body); the sharded plane still serves every point-based verify that
    routes through ``crypto.bls.batch``.  The opt-in exists because the
    sharded drain trades the device committee cache for host EC
    aggregation per attestation — a trade to be measured on a live
    mesh, not defaulted.
    """
    from ..crypto.bls.batch import chain_on, shard_drain_active

    spec = spec or get_chain_spec()
    results: list[ForkChoiceError | None] = [None] * len(attestations)
    # where the device chain is on, EVERY flush takes the cached drain — a
    # deadline flush below the coalescing target too: the host body walks
    # each attestation's committee and members in Python, which at mainnet
    # size costs ~0.5 s an attestation (a 60-entry flush: 31 s of event
    # loop, measured by the open-loop cell of PR 34) against one chained
    # dispatch whatever the batch
    device = bool(attestations) and chain_on()
    sharded = device and shard_drain_active()
    cached = device and not sharded
    path = "sharded" if sharded else ("cached" if cached else "host")
    n_devices = 1
    if sharded:
        from ..ops.mesh import device_count

        n_devices = device_count()
    live_traces = traces is not None and any(t is not None for t in traces)
    t0 = _time.monotonic() if live_traces else 0.0
    verify = _attestation_batch_cached if cached else _attestation_batch_host
    with span("attestation_batch_verify", path=path, n_devices=n_devices):
        verify(store, attestations, is_from_block, spec, results)
    batch_id = None
    if live_traces:
        from ..tracing import record_verify_batch

        batch_id = record_verify_batch(
            traces, results, path, t0, _time.monotonic() - t0,
            n_devices=n_devices,
        )
    forensics = getattr(store, "forensics", None)
    if forensics is not None and attestations:
        # weight-event log: this batch is a reorg-attribution candidate;
        # batch_id joins it to the flight recorder's batch span (None
        # when tracing was off — the forensic record still lands)
        forensics.note_attestation_batch(batch_id, path, len(attestations))
    return results


class _DrainContainment:
    """Generic per-item containment for UNEXPECTED drain errors (the
    ADVICE r5 class; graftlint exception-containment): wrap the exception
    into an ignore-polarity verdict so one bad message never drops the
    whole gossip batch, count it, and log the first traceback per drain —
    a systemic failure (a dead device) stays diagnosable without 8k
    traceback copies."""

    def __init__(self, where: str):
        self.where = where
        self.logged = False

    def verdict(self, e: Exception, count: int = 1, stage: str = "item"):
        if not self.logged:
            self.logged = True
            log.exception("unexpected error in %s", self.where)
        get_metrics().inc("gossip_batch_error_count", value=count, stage=stage)
        return ForkChoiceError(
            f"attestation drain internal error: {type(e).__name__}: {e}"
        )


def _attestation_batch_host(
    store, attestations, is_from_block, spec, results
) -> list[ForkChoiceError | None]:
    from ..crypto.bls import BlsError
    from ..crypto.bls.api import _pubkey_point
    from ..crypto.bls.batch import batch_verify_each_points
    from ..crypto.bls.curve import DeserializationError, g1, g2_from_bytes
    from ..state_transition.predicates import indexed_attestation_signature_inputs

    prepared = []  # (index, attestation, indexed, point entry)
    contain = _DrainContainment("host attestation drain")
    for i, attestation in enumerate(attestations):
        try:
            target_state, indexed = _prepare_attestation(
                store, attestation, is_from_block, spec
            )
            pubkeys, signing_root = indexed_attestation_signature_inputs(
                target_state, indexed, spec
            )
            # sum of individually subgroup-checked (cached) points is in the
            # subgroup — no compress/decompress/re-check round trip
            agg_pk = None
            for pk in pubkeys:
                pt = _pubkey_point(pk)
                if pt is None:
                    raise ForkChoiceError("identity pubkey in committee")
                agg_pk = pt if agg_pk is None else g1.affine_add(agg_pk, pt)
            sig_pt = g2_from_bytes(bytes(indexed.signature))
            prepared.append((i, attestation, indexed, (agg_pk, signing_root, sig_pt)))
        except ForkChoiceError as e:
            # keep the original verdict (its reject polarity matters)
            results[i] = e
        except (BlsError, DeserializationError) as e:
            # undecodable signature / bad point: protocol violation
            results[i] = ForkChoiceError(str(e), reject=True)
        except SpecError as e:
            # unknown block, timing, committee mismatch: could be a race
            # or missing context — ignore, don't penalize
            results[i] = ForkChoiceError(str(e))
        except Exception as e:
            # unexpected (e.g. an IndexError from a malformed bitfield)
            results[i] = contain.verdict(e)
    if prepared:
        flags = batch_verify_each_points([entry[3] for entry in prepared])
        for (i, attestation, indexed, _), ok in zip(prepared, flags):
            if ok:
                update_latest_messages(store, indexed.attesting_indices, attestation)
            else:
                results[i] = ForkChoiceError(
                    "invalid attestation signature", reject=True
                )
    return results


def _host_verify_group(ctx, group, contain, results):
    """Bit-exact host re-verify of one cached-drain context group after a
    contained device fault: aggregate each item's pubkey from the context
    state's registry and run the host-routed batch check.  Returns
    per-item flags aligned with ``group``, or
    ``None`` after writing verdicts when even host prep fails."""
    from ..crypto.bls.api import _pubkey_point
    from ..crypto.bls.batch import batch_verify_each_points
    from ..crypto.bls.curve import g1

    try:
        entries = []
        for _i, attestation, attesting, (cid, _miss, signing_root, sig) in group:
            agg_pk = None
            for v in attesting:
                pt = _pubkey_point(bytes(ctx.state.validators[v].pubkey))
                if pt is None:
                    raise ForkChoiceError("identity pubkey in committee")
                agg_pk = pt if agg_pk is None else g1.affine_add(agg_pk, pt)
            entries.append((agg_pk, signing_root, sig))
        return batch_verify_each_points(entries)
    except ForkChoiceError as e:
        for i, _, _, _ in group:
            results[i] = e
        return None
    except Exception as e:  # the fallback itself died: contain per item
        v = contain.verdict(e, count=len(group), stage="context")
        for i, _, _, _ in group:
            results[i] = v
        return None


def _attestation_batch_cached(
    store, attestations, is_from_block, spec, results
) -> None:
    """The epoch-cache device drain (module doc: fork_choice/attestation).

    Per item: fork-choice validation + numpy participation split + signing
    root; then ONE ``batch_verify_each_cached`` chain per target context
    (aggregate pubkeys never touch the host).  An entry with exactly one
    attester goes as ``(validator_index, None, ...)``: the chain gathers
    its pubkey from the device registry planes.  Every other entry goes
    by the shorter of its two member lists (``smaller_side``): the
    missing members subtracted from the cached committee sum, or — below
    half participation — the participants summed from the identity, on
    the device either way; a sparse flush selects a wider gather, never
    the host.  Accepted votes apply through the vectorized batch updater.
    """
    from ..crypto.bls import BlsError
    from ..crypto.bls.batch import batch_verify_each_cached
    from ..crypto.bls.curve import DeserializationError, g2_from_bytes_batch
    from ..ops.bls_batch import smaller_side
    from .attestation import get_attestation_context

    pending = []  # (i, att, ctx, cid, attesting, missing, sroot)
    contain = _DrainContainment("cached attestation drain")
    with span("attestation_prepare"):
        for i, attestation in enumerate(attestations):
            try:
                validate_on_attestation(store, attestation, is_from_block, spec)
                store_target_checkpoint_state(store, attestation.data.target, spec)
                target_state = store.checkpoint_states[
                    checkpoint_key(attestation.data.target)
                ]
                ctx = get_attestation_context(
                    store, attestation.data.target, target_state, spec
                )
                cid, attesting, missing = ctx.participation(attestation)
                if len(attesting) == 0:
                    raise ForkChoiceError("attestation has no participants", reject=True)
                signing_root = ctx.signing_root(attestation.data)
                pending.append(
                    (i, attestation, ctx, cid, attesting, missing, signing_root)
                )
            except ForkChoiceError as e:
                results[i] = e
            except (BlsError, DeserializationError) as e:
                results[i] = ForkChoiceError(str(e), reject=True)
            except (SpecError, ValueError) as e:
                # context build / numpy participation split can surface plain
                # ValueError (bad bitfield buffer, cache shape checks) — same
                # blast-radius rule as the device-cache loop below
                results[i] = ForkChoiceError(str(e))
            except Exception as e:
                # remaining ADVICE r5 gap: the PREP loop lacked the generic
                # per-item containment the verify loop below already has
                results[i] = contain.verdict(e)

    # one thread-pooled decompression pass (C++ when available) — AFTER
    # validation, so junk that fork choice rejects anyway never costs the
    # ~10 ms/sig Python fallback (an event-loop DoS at gossip batch sizes)
    with span("signature_decompress"):
        sig_points = g2_from_bytes_batch([bytes(p[1].signature) for p in pending])

    by_ctx: dict[int, list] = {}  # id(ctx) -> [(i, att, attesting, entry)]
    ctxs: dict[int, object] = {}
    for (i, attestation, ctx, cid, attesting, missing, signing_root), sig_pt in zip(
            pending, sig_points):
        try:
            if sig_pt is False:
                raise ForkChoiceError("undecodable signature", reject=True)
            if sig_pt is None:
                raise ForkChoiceError("infinity signature", reject=True)
            ctx.device_cache()  # built here, so that a failure is this item's
            if len(attesting) == 1:
                # an unaggregated vote: its pubkey is one registry column,
                # gathered on the device by validator index
                entry = (int(attesting[0]), None, signing_root, sig_pt)
            else:
                # an aggregate at ANY participation: the shorter of its
                # missing and its attesting members, summed on the device
                entry = (cid, smaller_side(attesting, missing), signing_root, sig_pt)
            by_ctx.setdefault(id(ctx), []).append((i, attestation, attesting, entry))
            ctxs[id(ctx)] = ctx
        except ForkChoiceError as e:
            results[i] = e
        except (BlsError, DeserializationError) as e:
            results[i] = ForkChoiceError(str(e), reject=True)
        except (SpecError, ValueError) as e:
            # ctx.device_cache() can raise here (invalid registry pubkey,
            # inconsistent cache shapes) — one bad item must not drop the
            # whole gossip batch, repeatedly, for every future drain
            get_metrics().inc("gossip_batch_error_count", stage="item")
            results[i] = ForkChoiceError(str(e))
        except Exception as e:  # unexpected: contain to the item
            results[i] = contain.verdict(e)

    accepted = []  # (batch index, ctx, attestation, attesting array)

    for ctx_id, group in by_ctx.items():
        ctx = ctxs[ctx_id]
        try:
            flags = batch_verify_each_cached(
                ctx.device_cache(),
                [entry for _, _, _, entry in group],
                message_points=ctx.message_points,
            )
        except (SpecError, ValueError) as e:
            # e.g. an invalid registry pubkey surfacing from the device
            # cache build: fail THIS context's items, not the whole batch
            get_metrics().inc(
                "gossip_batch_error_count", value=len(group), stage="context"
            )
            for i, _, _, _ in group:
                results[i] = ForkChoiceError(str(e))
            continue
        except Exception:
            # device-runtime fault (XlaRuntimeError, lost PJRT client)
            # mid-dispatch: round 20 containment — re-verify this
            # context's items on the bit-exact HOST path (aggregate from
            # the context state's registry pubkeys) instead of dropping
            # the whole group.
            # Counted + latched so the fallback stays operator-visible.
            log.exception(
                "device verify fault on a %d-item context group; "
                "host fallback", len(group),
            )
            device_fault("bls_verify")
            flags = _host_verify_group(ctx, group, contain, results)
            if flags is None:
                continue
        for (i, attestation, attesting, _), ok in zip(group, flags):
            if ok:
                accepted.append((i, ctx, attestation, attesting))
            else:
                results[i] = ForkChoiceError(
                    "invalid attestation signature", reject=True
                )

    with span("votes_apply"):
        update_latest_messages_batch(store, accepted)


def update_latest_messages_batch(store, accepted) -> None:
    """Vectorized LMD vote application for a drain's accepted
    attestations — ``accepted`` is ``[(batch_index, ctx, attestation,
    attesting_array)]``.  Semantics match per-item
    :func:`update_latest_messages` EXACTLY, including within-batch
    ordering: a claim pass in batch-index order decides which attestation
    a validator's same-epoch vote came from (first valid wins; a strictly
    newer epoch later in the batch still overrides), then per-(epoch,
    root) buckets apply epoch-ascending through one numpy filter, one
    shared ``LatestMessage``, and ``HeadCache.on_votes_batch``."""
    import numpy as np

    if not accepted:
        return
    n = max(ctx.n_validators for _, ctx, _, _ in accepted)
    claim_epoch = np.full(n, -1, np.int64)  # within-batch claims only
    buckets: dict[tuple[int, bytes], list] = {}
    bucket_ctx: dict[tuple[int, bytes], object] = {}
    for _, ctx, attestation, attesting in sorted(accepted, key=lambda t: t[0]):
        epoch = int(attestation.data.target.epoch)
        root = bytes(attestation.data.beacon_block_root)
        attesting = np.asarray(attesting, np.int64)
        newly = attesting[claim_epoch[attesting] < epoch]
        if not len(newly):
            continue
        claim_epoch[newly] = epoch
        buckets.setdefault((epoch, root), []).append(newly)
        bucket_ctx[(epoch, root)] = ctx

    updated = False
    for (epoch, root) in sorted(buckets, key=lambda k: k[0]):
        ctx = bucket_ctx[(epoch, root)]
        uniq = np.unique(np.concatenate(buckets[(epoch, root)]))
        if store.equivocating_indices:
            uniq = uniq[
                ~np.isin(uniq, np.fromiter(store.equivocating_indices, np.int64))
            ]
        epochs = store.vote_epoch_array(ctx.n_validators)
        moved = uniq[epochs[uniq] < epoch]
        if not len(moved):
            continue
        epochs[moved] = epoch
        lm = LatestMessage(epoch=epoch, root=root)
        store.latest_messages.update(dict.fromkeys(moved.tolist(), lm))
        if store.head_cache is not None:
            store.head_cache.on_votes_batch(moved, ctx.eff_balance[moved], root)
        updated = True
    if updated:
        store.bump()


# -------------------------------------------------------- attester slashing

def on_attester_slashing(
    store: Store, attester_slashing: AttesterSlashing, spec: ChainSpec | None = None
) -> None:
    """Track equivocating validators (ref: handlers.ex:127-154)."""
    spec = spec or get_chain_spec()
    att1 = attester_slashing.attestation_1
    att2 = attester_slashing.attestation_2
    expect(
        is_slashable_attestation_data(att1.data, att2.data),
        "attestations are not slashable",
    )
    state = store.block_states[bytes(store.justified_checkpoint.root)]
    expect(is_valid_indexed_attestation(state, att1, spec), "attestation 1 invalid")
    expect(is_valid_indexed_attestation(state, att2, spec), "attestation 2 invalid")
    equivocators = set(att1.attesting_indices) & set(att2.attesting_indices)
    store.equivocating_indices.update(equivocators)
    store.bump()
    if store.head_cache is not None:
        for i in equivocators:
            store.head_cache.on_equivocation(i)
    forensics = getattr(store, "forensics", None)
    if forensics is not None:
        forensics.note_attester_slashing(equivocators)
