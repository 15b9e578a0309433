"""Per-block operation processing, capella-complete.

The reference implements sync-aggregate, withdrawals and the slashing/exit/
attestation family but stubs header/randao/eth1/deposit/execution-payload
(ref: lib/.../state_transition/operations.ex:20-716 and
state_transition.ex:117-126).  This module implements the full capella set;
the consensus-spec-tests ``operations`` corpus is the oracle.

All functions mutate a :class:`~.mutable.BeaconStateMut` and raise
:class:`OperationError` on invalid input (the reference returns
``{:error, reason}`` tuples).
"""

from __future__ import annotations

from ..config import ChainSpec, constants, get_chain_spec
from ..crypto import bls
from ..ssz import hash as ssz_hash
from ..telemetry import span
from ..types.beacon import (
    BeaconBlockHeader,
    Validator,
)
from . import accessors, misc, predicates
from .mutable import BeaconStateMut
from .mutators import (
    decrease_balance,
    increase_balance,
    initiate_validator_exit,
    slash_validator,
)

hash_bytes = ssz_hash.sha256

from .errors import OperationError  # noqa: E402  (re-exported; shared hierarchy)


def expect(cond: bool, reason: str) -> None:
    if not cond:
        raise OperationError(reason)


# ------------------------------------------------------------ block header

def process_block_header(
    state: BeaconStateMut, block, spec: ChainSpec | None = None
) -> None:
    spec = spec or get_chain_spec()
    expect(block.slot == state.slot, "block slot does not match state slot")
    expect(
        block.slot > state.latest_block_header.slot,
        "block is older than latest header",
    )
    expect(
        block.proposer_index == accessors.get_beacon_proposer_index(state, spec),
        "incorrect proposer index",
    )
    expect(
        bytes(block.parent_root) == state.latest_block_header.hash_tree_root(spec),
        "parent root mismatch",
    )
    proposer = state.validators[block.proposer_index]
    expect(not proposer.slashed, "proposer is slashed")
    state.latest_block_header = BeaconBlockHeader(
        slot=block.slot,
        proposer_index=block.proposer_index,
        parent_root=bytes(block.parent_root),
        state_root=b"\x00" * 32,  # overwritten at next process_slot
        body_root=block.body.hash_tree_root(spec),
    )


# ----------------------------------------------------------------- randao

def process_randao(state: BeaconStateMut, body, spec: ChainSpec | None = None) -> None:
    spec = spec or get_chain_spec()
    epoch = accessors.get_current_epoch(state, spec)
    proposer = state.validators[accessors.get_beacon_proposer_index(state, spec)]
    signing_root = misc.compute_signing_root_epoch(
        epoch, accessors.get_domain(state, constants.DOMAIN_RANDAO, epoch, spec)
    )
    expect(
        bls.verify(bytes(proposer.pubkey), signing_root, bytes(body.randao_reveal)),
        "invalid randao reveal",
    )
    mix = bytes(
        a ^ b
        for a, b in zip(
            accessors.get_randao_mix(state, epoch, spec),
            hash_bytes(bytes(body.randao_reveal)),
        )
    )
    state.randao_mixes[epoch % spec.EPOCHS_PER_HISTORICAL_VECTOR] = mix


# -------------------------------------------------------------- eth1 data

def process_eth1_data(state: BeaconStateMut, body, spec: ChainSpec | None = None) -> None:
    spec = spec or get_chain_spec()
    state.eth1_data_votes = state.eth1_data_votes + [body.eth1_data]
    period_len = spec.EPOCHS_PER_ETH1_VOTING_PERIOD * spec.SLOTS_PER_EPOCH
    votes = sum(1 for v in state.eth1_data_votes if v == body.eth1_data)
    if votes * 2 > period_len:
        state.eth1_data = body.eth1_data


# ------------------------------------------------------ proposer slashing

def process_proposer_slashing(
    state: BeaconStateMut, proposer_slashing, spec: ChainSpec | None = None
) -> None:
    spec = spec or get_chain_spec()
    h1 = proposer_slashing.signed_header_1.message
    h2 = proposer_slashing.signed_header_2.message
    expect(h1.slot == h2.slot, "slashing headers not for same slot")
    expect(h1.proposer_index == h2.proposer_index, "different proposers")
    expect(h1 != h2, "headers are identical")
    expect(h1.proposer_index < len(state.validators), "unknown proposer")
    proposer = state.validators[h1.proposer_index]
    expect(
        predicates.is_slashable_validator(
            proposer, accessors.get_current_epoch(state, spec)
        ),
        "proposer not slashable",
    )
    for signed_header in (
        proposer_slashing.signed_header_1,
        proposer_slashing.signed_header_2,
    ):
        domain = accessors.get_domain(
            state,
            constants.DOMAIN_BEACON_PROPOSER,
            misc.compute_epoch_at_slot(signed_header.message.slot, spec),
            spec,
        )
        signing_root = misc.compute_signing_root(signed_header.message, domain)
        expect(
            bls.verify(
                bytes(proposer.pubkey), signing_root, bytes(signed_header.signature)
            ),
            "invalid slashing header signature",
        )
    slash_validator(state, h1.proposer_index, spec=spec)


# ------------------------------------------------------ attester slashing

def process_attester_slashing(
    state: BeaconStateMut, attester_slashing, spec: ChainSpec | None = None
) -> None:
    spec = spec or get_chain_spec()
    att1 = attester_slashing.attestation_1
    att2 = attester_slashing.attestation_2
    expect(
        predicates.is_slashable_attestation_data(att1.data, att2.data),
        "attestation data not slashable",
    )
    expect(
        predicates.is_valid_indexed_attestation(state, att1, spec),
        "attestation 1 invalid",
    )
    expect(
        predicates.is_valid_indexed_attestation(state, att2, spec),
        "attestation 2 invalid",
    )
    slashed_any = False
    current_epoch = accessors.get_current_epoch(state, spec)
    common = set(att1.attesting_indices) & set(att2.attesting_indices)
    for index in sorted(common):
        if predicates.is_slashable_validator(state.validators[index], current_epoch):
            slash_validator(state, index, spec=spec)
            slashed_any = True
    expect(slashed_any, "no validator slashed")


# ---------------------------------------------------------- attestations

def process_attestation(
    state: BeaconStateMut, attestation, spec: ChainSpec | None = None,
    defer_signatures: list | None = None,
) -> None:
    """One block attestation: structural checks + participation/reward
    accounting + signature check.

    ``defer_signatures`` (a list) switches the signature check to
    COLLECTION: the ``(attestation, indexed)`` pair is appended and
    verified later by :func:`_verify_deferred_attestations` as one RLC
    batch — the reference pays blst per attestation
    (state_transition/predicates.ex:109-136); a TPU block wants ONE
    drain for all ~64-128 of them.  Spec-equivalent because a failed
    signature anywhere makes the whole block invalid and the transition's
    working state is discarded wholesale.
    """
    spec = spec or get_chain_spec()
    data = attestation.data
    current_epoch = accessors.get_current_epoch(state, spec)
    previous_epoch = accessors.get_previous_epoch(state, spec)
    expect(
        data.target.epoch in (previous_epoch, current_epoch),
        "target epoch not current or previous",
    )
    expect(
        data.target.epoch == misc.compute_epoch_at_slot(data.slot, spec),
        "target epoch does not match slot",
    )
    expect(
        data.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY
        <= state.slot
        <= data.slot + spec.SLOTS_PER_EPOCH,
        "attestation not in inclusion window",
    )
    expect(
        data.index
        < accessors.get_committee_count_per_slot(state, data.target.epoch, spec),
        "committee index out of range",
    )

    with span("block_att_committee"):
        # participation accounting (altair): may raise for bad source
        try:
            flag_indices = accessors.get_attestation_participation_flag_indices(
                state, data, state.slot - data.slot, spec
            )
        except ValueError as e:
            raise OperationError(str(e)) from None

        indexed = accessors.get_indexed_attestation(state, attestation, spec)
    if defer_signatures is not None:
        # structural validity of the index set still checks NOW (sorted,
        # unique, in-range — OperationError on failure); only the pairing
        # work defers.  The inputs ride along so verification never
        # recomputes the pubkey extraction / signing root.
        with span("block_att_signature_inputs"):
            pubkeys, signing_root = predicates.indexed_attestation_signature_inputs(
                state, indexed, spec
            )
        defer_signatures.append((attestation, indexed, pubkeys, signing_root))
    else:
        expect(
            predicates.is_valid_indexed_attestation(state, indexed, spec),
            "invalid attestation signature",
        )

    with span("block_att_participation"):
        which = "current" if data.target.epoch == current_epoch else "previous"
        participation = getattr(state, f"{which}_epoch_participation")

        proposer_reward_numerator = 0
        # get_base_reward per attester, with its per-increment factor (one
        # O(registry) total-active-balance reduction) taken once, not per index
        per_increment = accessors.get_base_reward_per_increment(state, spec)
        base_rewards = {
            i: state.validators[i].effective_balance
            // spec.EFFECTIVE_BALANCE_INCREMENT * per_increment
            for i in indexed.attesting_indices
        }
        for index in indexed.attesting_indices:
            for flag_index, weight in enumerate(constants.PARTICIPATION_FLAG_WEIGHTS):
                flag = 1 << flag_index
                if flag_index in flag_indices and not participation[index] & flag:
                    participation[index] |= flag
                    proposer_reward_numerator += base_rewards[index] * weight

        proposer_reward_denominator = (
            (constants.WEIGHT_DENOMINATOR - constants.PROPOSER_WEIGHT)
            * constants.WEIGHT_DENOMINATOR
            // constants.PROPOSER_WEIGHT
        )
        proposer_reward = proposer_reward_numerator // proposer_reward_denominator
        increase_balance(
            state, accessors.get_beacon_proposer_index(state, spec), proposer_reward
        )


def _verify_deferred_attestations(state, deferred, spec) -> bool:
    """All of a block's attestation signatures as ONE batched check.

    Signatures decompress in one native thread-pool pass; on device-
    enabled hosts with enough total committee membership the aggregate
    pubkeys come from the epoch committee cache
    (:func:`_verify_deferred_cached` — the same machinery the gossip
    drain runs), otherwise a single host RLC check replaces the
    per-attestation pairings.
    """
    import os

    from ..crypto.bls.api import _pubkey_point
    from ..crypto.bls.batch import chain_on, verify_points
    from ..crypto.bls.curve import g1, g2_from_bytes_batch

    sigs = g2_from_bytes_batch([bytes(ind.signature) for _, ind, _, _ in deferred])
    if any(s is False or s is None for s in sigs):
        return False

    total_members = sum(len(ind.attesting_indices) for _, ind, _, _ in deferred)
    min_members = int(os.environ.get("BLS_BLOCK_BATCH_MIN_MEMBERS", "4096"))
    use_cached = total_members >= min_members and chain_on()
    if use_cached:
        try:
            return _verify_deferred_cached(state, deferred, sigs, spec)
        except ValueError:
            # a real validation failure (SpecError subclasses ValueError:
            # invalid registry pubkey, shape contract breach) fails on
            # host just the same — propagate
            raise
        except Exception:
            # device-runtime fault (XlaRuntimeError & co) mid block
            # verify: contained — the bit-exact host RLC below answers
            # instead, and the latched /debug/slo flag keeps it visible
            from ..telemetry import device_fault

            device_fault("bls_verify")

    # no committee context: a host without the device chain, or a block
    # too small to be worth a dispatch — host points, one RLC check
    entries = []
    for (att, ind, pubkeys, signing_root), sig in zip(deferred, sigs):
        agg = None
        for pk in pubkeys:
            pt = _pubkey_point(pk)
            if pt is None:
                return False
            agg = pt if agg is None else g1.affine_add(agg, pt)
        entries.append((agg, signing_root, sig))
    return verify_points(entries)


def _verify_deferred_cached(state, deferred, sigs, spec) -> bool:
    """A block's aggregates through the cached device chain, one call per
    target context: every entry by ``(committee, the shorter of its
    missing and its attesting members)`` from numpy bit ops — a single
    signer by its registry index — so no aggregate public key is summed
    on the host, at any participation."""
    from ..crypto.bls.batch import batch_verify_each_cached
    from ..fork_choice.attestation import get_state_attestation_context
    from ..ops.bls_batch import smaller_side

    frozen = state.freeze()
    by_ctx: dict[int, tuple] = {}
    for (att, _ind, _pubkeys, signing_root), sig in zip(deferred, sigs):
        ctx = get_state_attestation_context(
            frozen, int(att.data.target.epoch), spec
        )
        cid, attesting, missing = ctx.participation(att)
        if len(attesting) == 1:
            entry = (int(attesting[0]), None, signing_root, sig)
        else:
            entry = (cid, smaller_side(attesting, missing), signing_root, sig)
        by_ctx.setdefault(id(ctx), (ctx, []))[1].append(entry)
    for ctx, entries in by_ctx.values():
        flags = batch_verify_each_cached(
            ctx.device_cache(), entries,
            message_points=ctx.message_points,
        )
        if not all(flags):
            return False
    return True


# --------------------------------------------------------------- deposits

def is_valid_merkle_branch(
    leaf: bytes, branch, depth: int, index: int, root: bytes
) -> bool:
    value = leaf
    for i in range(depth):
        sibling = bytes(branch[i])
        if (index >> i) & 1:
            value = hash_bytes(sibling + value)
        else:
            value = hash_bytes(value + sibling)
    return value == root


def get_validator_from_deposit(
    pubkey: bytes, withdrawal_credentials: bytes, amount: int, spec: ChainSpec
) -> Validator:
    effective = min(
        amount - amount % spec.EFFECTIVE_BALANCE_INCREMENT, spec.MAX_EFFECTIVE_BALANCE
    )
    return Validator(
        pubkey=pubkey,
        withdrawal_credentials=withdrawal_credentials,
        effective_balance=effective,
        slashed=False,
        activation_eligibility_epoch=constants.FAR_FUTURE_EPOCH,
        activation_epoch=constants.FAR_FUTURE_EPOCH,
        exit_epoch=constants.FAR_FUTURE_EPOCH,
        withdrawable_epoch=constants.FAR_FUTURE_EPOCH,
    )


def apply_deposit(
    state: BeaconStateMut,
    pubkey: bytes,
    withdrawal_credentials: bytes,
    amount: int,
    signature: bytes,
    spec: ChainSpec,
) -> None:
    index = state.pubkey_index().get(pubkey)
    if index is None:
        # new validator: the deposit signature must verify (proof of possession,
        # checked with the *genesis* domain so deposits survive forks)
        from ..types.beacon import DepositMessage

        deposit_message = DepositMessage(
            pubkey=pubkey,
            withdrawal_credentials=withdrawal_credentials,
            amount=amount,
        )
        domain = misc.compute_domain(constants.DOMAIN_DEPOSIT, spec=spec)
        signing_root = misc.compute_signing_root(deposit_message, domain)
        if not bls.verify(pubkey, signing_root, signature):
            return  # invalid signature: deposit is skipped, not an error
        state.append_validator(
            get_validator_from_deposit(pubkey, withdrawal_credentials, amount, spec),
            amount,
        )
    else:
        increase_balance(state, index, amount)


def process_deposit(
    state: BeaconStateMut, deposit, spec: ChainSpec | None = None
) -> None:
    spec = spec or get_chain_spec()
    expect(
        is_valid_merkle_branch(
            deposit.data.hash_tree_root(spec),
            deposit.proof,
            constants.DEPOSIT_CONTRACT_TREE_DEPTH + 1,  # +1 for deposit-count mix-in
            state.eth1_deposit_index,
            bytes(state.eth1_data.deposit_root),
        ),
        "invalid deposit merkle proof",
    )
    state.eth1_deposit_index += 1
    apply_deposit(
        state,
        bytes(deposit.data.pubkey),
        bytes(deposit.data.withdrawal_credentials),
        deposit.data.amount,
        bytes(deposit.data.signature),
        spec,
    )


# -------------------------------------------------------- voluntary exits

def process_voluntary_exit(
    state: BeaconStateMut, signed_voluntary_exit, spec: ChainSpec | None = None
) -> None:
    spec = spec or get_chain_spec()
    voluntary_exit = signed_voluntary_exit.message
    expect(
        voluntary_exit.validator_index < len(state.validators), "unknown validator"
    )
    validator = state.validators[voluntary_exit.validator_index]
    current_epoch = accessors.get_current_epoch(state, spec)
    expect(
        predicates.is_active_validator(validator, current_epoch),
        "validator not active",
    )
    expect(
        validator.exit_epoch == constants.FAR_FUTURE_EPOCH, "exit already initiated"
    )
    expect(current_epoch >= voluntary_exit.epoch, "exit epoch in the future")
    expect(
        current_epoch >= validator.activation_epoch + spec.SHARD_COMMITTEE_PERIOD,
        "validator too young to exit",
    )
    domain = accessors.get_domain(
        state, constants.DOMAIN_VOLUNTARY_EXIT, voluntary_exit.epoch, spec
    )
    signing_root = misc.compute_signing_root(voluntary_exit, domain)
    expect(
        bls.verify(
            bytes(validator.pubkey), signing_root, bytes(signed_voluntary_exit.signature)
        ),
        "invalid exit signature",
    )
    initiate_validator_exit(state, voluntary_exit.validator_index, spec)


# ----------------------------------------------- bls-to-execution changes

def process_bls_to_execution_change(
    state: BeaconStateMut, signed_change, spec: ChainSpec | None = None
) -> None:
    spec = spec or get_chain_spec()
    change = signed_change.message
    expect(change.validator_index < len(state.validators), "unknown validator")
    validator = state.validators[change.validator_index]
    creds = bytes(validator.withdrawal_credentials)
    expect(
        creds[:1] == constants.BLS_WITHDRAWAL_PREFIX, "not a BLS withdrawal credential"
    )
    expect(
        creds[1:] == hash_bytes(bytes(change.from_bls_pubkey))[1:],
        "withdrawal credential does not match BLS key",
    )
    # signed with the *genesis* domain, ignoring the current fork
    domain = misc.compute_domain(
        constants.DOMAIN_BLS_TO_EXECUTION_CHANGE,
        spec.GENESIS_FORK_VERSION,
        bytes(state.genesis_validators_root),
        spec,
    )
    signing_root = misc.compute_signing_root(change, domain)
    expect(
        bls.verify(
            bytes(change.from_bls_pubkey), signing_root, bytes(signed_change.signature)
        ),
        "invalid BLS-to-execution-change signature",
    )
    state.update_validator(
        change.validator_index,
        withdrawal_credentials=(
            constants.ETH1_ADDRESS_WITHDRAWAL_PREFIX
            + b"\x00" * 11
            + bytes(change.to_execution_address)
        ),
    )


# ------------------------------------------------------------ withdrawals

def process_withdrawals(
    state: BeaconStateMut, payload, spec: ChainSpec | None = None
) -> None:
    spec = spec or get_chain_spec()
    expected = accessors.get_expected_withdrawals(state, spec)
    actual = list(payload.withdrawals)
    expect(len(actual) == len(expected), "withdrawal count mismatch")
    for got, want in zip(actual, expected):
        expect(got == want, "withdrawal mismatch")
        decrease_balance(state, got.validator_index, got.amount)
    if expected:
        state.next_withdrawal_index = expected[-1].index + 1
    n = len(state.validators)
    if len(expected) == spec.MAX_WITHDRAWALS_PER_PAYLOAD:
        state.next_withdrawal_validator_index = (
            expected[-1].validator_index + 1
        ) % n
    else:
        state.next_withdrawal_validator_index = (
            state.next_withdrawal_validator_index
            + spec.MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP
        ) % n


# ------------------------------------------------------ execution payload

def process_execution_payload(
    state: BeaconStateMut,
    body,
    execution_engine=None,
    spec: ChainSpec | None = None,
) -> None:
    """Validate the payload against chain state and notify the execution
    engine (``execution_engine.verify_and_notify(payload) -> bool``; ``None``
    accepts optimistically, as the reference's disabled EL does)."""
    from ..types.beacon import ExecutionPayloadHeader

    spec = spec or get_chain_spec()
    payload = body.execution_payload
    if predicates.is_merge_transition_complete(state):
        expect(
            bytes(payload.parent_hash)
            == bytes(state.latest_execution_payload_header.block_hash),
            "payload parent hash mismatch",
        )
    expect(
        bytes(payload.prev_randao)
        == accessors.get_randao_mix(
            state, accessors.get_current_epoch(state, spec), spec
        ),
        "payload prev_randao mismatch",
    )
    expect(
        payload.timestamp == misc.compute_timestamp_at_slot(state, state.slot, spec),
        "payload timestamp mismatch",
    )
    if execution_engine is not None:
        expect(
            execution_engine.verify_and_notify(payload),
            "execution engine rejected payload",
        )
    state.latest_execution_payload_header = ExecutionPayloadHeader(
        parent_hash=bytes(payload.parent_hash),
        fee_recipient=bytes(payload.fee_recipient),
        state_root=bytes(payload.state_root),
        receipts_root=bytes(payload.receipts_root),
        logs_bloom=bytes(payload.logs_bloom),
        prev_randao=bytes(payload.prev_randao),
        block_number=payload.block_number,
        gas_limit=payload.gas_limit,
        gas_used=payload.gas_used,
        timestamp=payload.timestamp,
        extra_data=bytes(payload.extra_data),
        base_fee_per_gas=payload.base_fee_per_gas,
        block_hash=bytes(payload.block_hash),
        transactions_root=type(body.execution_payload)
        .fields()["transactions"]
        .hash_tree_root(payload.transactions, spec),
        withdrawals_root=type(body.execution_payload)
        .fields()["withdrawals"]
        .hash_tree_root(payload.withdrawals, spec),
    )


# --------------------------------------------------------- sync aggregate

def process_sync_aggregate(
    state: BeaconStateMut, aggregate, spec: ChainSpec | None = None
) -> None:
    spec = spec or get_chain_spec()
    committee_pubkeys = [bytes(pk) for pk in state.current_sync_committee.pubkeys]
    bits = aggregate.sync_committee_bits
    participant_pubkeys = [
        pk for i, pk in enumerate(committee_pubkeys) if bits[i]
    ]
    previous_slot = max(state.slot, 1) - 1
    domain = accessors.get_domain(
        state,
        constants.DOMAIN_SYNC_COMMITTEE,
        misc.compute_epoch_at_slot(previous_slot, spec),
        spec,
    )
    signing_root = misc.compute_signing_root_bytes(
        accessors.get_block_root_at_slot(state, previous_slot, spec), domain
    )
    expect(
        bls.eth_fast_aggregate_verify(
            participant_pubkeys, signing_root, bytes(aggregate.sync_committee_signature)
        ),
        "invalid sync committee signature",
    )

    # rewards: split the slot's sync weight over committee members
    total_active_increments = accessors.get_total_active_balance(
        state, spec
    ) // spec.EFFECTIVE_BALANCE_INCREMENT
    total_base_rewards = (
        accessors.get_base_reward_per_increment(state, spec) * total_active_increments
    )
    max_participant_rewards = (
        total_base_rewards
        * constants.SYNC_REWARD_WEIGHT
        // constants.WEIGHT_DENOMINATOR
        // spec.SLOTS_PER_EPOCH
    )
    participant_reward = max_participant_rewards // spec.SYNC_COMMITTEE_SIZE
    proposer_reward = (
        participant_reward
        * constants.PROPOSER_WEIGHT
        // (constants.WEIGHT_DENOMINATOR - constants.PROPOSER_WEIGHT)
    )

    pubkey_index = state.pubkey_index()
    proposer_index = accessors.get_beacon_proposer_index(state, spec)
    for i, pk in enumerate(committee_pubkeys):
        participant_index = pubkey_index[pk]
        if bits[i]:
            increase_balance(state, participant_index, participant_reward)
            increase_balance(state, proposer_index, proposer_reward)
        else:
            decrease_balance(state, participant_index, participant_reward)


# ------------------------------------------------------- operations driver

def process_operations(
    state: BeaconStateMut, body, execution_engine=None, spec: ChainSpec | None = None
) -> None:
    spec = spec or get_chain_spec()
    expected_deposits = min(
        spec.MAX_DEPOSITS,
        state.eth1_data.deposit_count - state.eth1_deposit_index,
    )
    expect(
        len(body.deposits) == expected_deposits,
        "wrong number of deposits in block",
    )
    for op in body.proposer_slashings:
        process_proposer_slashing(state, op, spec)
    for op in body.attester_slashings:
        process_attester_slashing(state, op, spec)
    deferred: list = []
    with span("block_attestations"):
        for op in body.attestations:
            process_attestation(state, op, spec, defer_signatures=deferred)
    if deferred:
        with span("block_att_verify"):
            verified = _verify_deferred_attestations(state, deferred, spec)
        expect(verified, "invalid attestation signature")
    for op in body.deposits:
        process_deposit(state, op, spec)
    for op in body.voluntary_exits:
        process_voluntary_exit(state, op, spec)
    for op in body.bls_to_execution_changes:
        process_bls_to_execution_change(state, op, spec)
