"""State-transition core: shuffle, committees, epoch passes, full block apply."""

import pytest

from lambda_ethereum_consensus_tpu.config import (
    constants,
    mainnet_spec,
    minimal_spec,
    use_chain_spec,
)
from lambda_ethereum_consensus_tpu.crypto import bls
from lambda_ethereum_consensus_tpu.state_transition import (
    StateTransitionError,
    process_slots,
    state_transition,
)
from lambda_ethereum_consensus_tpu.state_transition import accessors, misc
from lambda_ethereum_consensus_tpu.state_transition.core import (
    process_block,
    verify_block_signature,
)
from lambda_ethereum_consensus_tpu.state_transition.genesis import build_genesis_state
from lambda_ethereum_consensus_tpu.state_transition.mutable import BeaconStateMut
from lambda_ethereum_consensus_tpu.telemetry import get_metrics
from lambda_ethereum_consensus_tpu.types.beacon import (
    BeaconBlock,
    BeaconBlockBody,
    Eth1Data,
    ExecutionPayload,
    SignedBeaconBlock,
    SyncAggregate,
)

N_VALIDATORS = 64
SECRET_KEYS = [(i + 1).to_bytes(32, "big") for i in range(N_VALIDATORS)]


@pytest.fixture(scope="module")
def keys():
    return [bls.sk_to_pk(sk) for sk in SECRET_KEYS]


@pytest.fixture(scope="module")
def genesis(keys):
    with use_chain_spec(minimal_spec()) as spec:
        yield build_genesis_state(keys, spec=spec), spec


# ------------------------------------------------------------------ shuffle

def test_vectorized_shuffle_matches_scalar_oracle(minimal):
    seed = b"\x5e" * 32
    n = 37
    perm = misc.compute_shuffled_indices(n, seed, minimal.SHUFFLE_ROUND_COUNT)
    for i in range(n):
        assert perm[i] == misc.compute_shuffled_index(i, n, seed, minimal)
    assert sorted(perm) == list(range(n))


def _proposer_by_permutation(ebs, indices, seed, spec):
    """The proposer as chosen from the whole permutation, and how many
    candidates the acceptance loop turned down before it."""
    total = len(indices)
    perm = misc.compute_shuffled_indices(total, seed, spec.SHUFFLE_ROUND_COUNT)
    i = 0
    while True:
        candidate = indices[perm[i % total]]
        random_byte = misc.hash_bytes(seed + (i // 32).to_bytes(8, "little"))[i % 32]
        if ebs[candidate] * 255 >= spec.MAX_EFFECTIVE_BALANCE * random_byte:
            return int(candidate), i
        i += 1


def _balances(mix, n, spec):
    full = spec.MAX_EFFECTIVE_BALANCE
    step = spec.EFFECTIVE_BALANCE_INCREMENT
    if mix == "full":
        return [full] * n
    if mix == "mixed":
        return [(v * 7 % 32 + 1) * step for v in range(n)]
    return [step] * n  # "low": one candidate in ~32 is accepted


@pytest.mark.parametrize("mix", ["full", "mixed", "low"])
@pytest.mark.parametrize("count", [1, 2, 37, 64, 300])
@pytest.mark.parametrize("preset", ["minimal", "mainnet"])
def test_proposer_walk_matches_whole_permutation(preset, count, mix):
    spec = minimal_spec() if preset == "minimal" else mainnet_spec()
    # validator indices that are not the positions: every third one is active
    indices = [3 * k + 1 for k in range(count)]
    ebs = _balances(mix, 3 * count + 2, spec)
    turned_down = 0
    for s in range(4):
        seed = misc.hash_bytes(bytes([s, count % 256, len(preset)]))
        want, rejected = _proposer_by_permutation(ebs, indices, seed, spec)
        assert misc.compute_proposer_index(ebs, indices, seed, spec) == want
        turned_down += rejected
    if mix == "low":
        assert turned_down > 0


def _with_balances(state, mix, spec):
    ebs = _balances(mix, len(state.validators), spec)
    return state.copy(
        validators=[
            v.copy(effective_balance=eb) for v, eb in zip(state.validators, ebs)
        ]
    )


@pytest.mark.parametrize("slot", [0, 3, 7])
@pytest.mark.parametrize("mix", ["full", "mixed"])
def test_proposer_same_on_mutable_and_frozen_state(genesis, mix, slot):
    state, spec = genesis
    with use_chain_spec(spec):
        frozen = _with_balances(state, mix, spec)
        assert not hasattr(frozen, "registry")  # the list fallback
        assert accessors.get_beacon_proposer_index(
            BeaconStateMut(frozen), spec, slot=slot
        ) == accessors.get_beacon_proposer_index(frozen, spec, slot=slot)


def test_proposer_lookups_walk_once_per_seed(genesis):
    state, spec = genesis
    metrics = get_metrics()
    was = metrics.enabled
    metrics.set_enabled(True)
    try:
        with use_chain_spec(spec):
            ws = BeaconStateMut(state)
            misc._proposer_candidate_position.cache_clear()
            walks = metrics.get("proposer_shuffle_walks_total")
            misses = misc.compute_shuffled_indices.cache_info().misses
            first = accessors.get_beacon_proposer_index(ws, spec)
            assert metrics.get("proposer_shuffle_walks_total") == walks + 1
            assert accessors.get_beacon_proposer_index(ws, spec) == first
            assert metrics.get("proposer_shuffle_walks_total") == walks + 1
            assert misc.compute_shuffled_indices.cache_info().misses == misses
    finally:
        metrics.set_enabled(was)


def test_committees_partition_active_set(genesis):
    state, spec = genesis
    with use_chain_spec(spec):
        ws = BeaconStateMut(state)
        epoch = accessors.get_current_epoch(ws, spec)
        per_slot = accessors.get_committee_count_per_slot(ws, epoch, spec)
        seen = []
        for slot in range(spec.SLOTS_PER_EPOCH):
            for index in range(per_slot):
                seen += accessors.get_beacon_committee(ws, slot, index, spec)
        assert sorted(seen) == list(range(N_VALIDATORS))


def test_proposer_is_active_validator(genesis):
    state, spec = genesis
    with use_chain_spec(spec):
        ws = BeaconStateMut(state)
        proposer = accessors.get_beacon_proposer_index(ws, spec)
        assert 0 <= proposer < N_VALIDATORS


# -------------------------------------------------------------- slot advance

def test_process_slots_fills_history_roots(genesis):
    state, spec = genesis
    with use_chain_spec(spec):
        advanced = process_slots(state, 3, spec)
        assert advanced.slot == 3
        # roots for slots 0..2 must be cached and non-zero
        for s in range(3):
            assert bytes(advanced.block_roots[s % spec.SLOTS_PER_HISTORICAL_ROOT]) != b"\x00" * 32
        # header got its state root backfilled
        assert bytes(advanced.latest_block_header.state_root) != b"\x00" * 32


def test_process_slots_rejects_backwards(genesis):
    state, spec = genesis
    with use_chain_spec(spec):
        with pytest.raises(StateTransitionError):
            process_slots(process_slots(state, 2, spec), 1, spec)


def test_epoch_boundary_applies_penalties(genesis):
    """With no attestations everyone gets penalized at the epoch boundary."""
    state, spec = genesis
    with use_chain_spec(spec):
        advanced = process_slots(state, spec.SLOTS_PER_EPOCH * 2, spec)
        assert advanced.slot == spec.SLOTS_PER_EPOCH * 2
        # balances dropped (source/target penalties; no rewards earned)
        assert sum(advanced.balances) < sum(state.balances)


# --------------------------------------------------------------- full block


def _build_block(state, spec, slot, sks):
    """Produce a valid signed block for ``slot`` on top of ``state``."""
    pre = process_slots(state, slot, spec)
    ws = BeaconStateMut(pre)
    proposer = accessors.get_beacon_proposer_index(ws, spec)
    epoch = accessors.get_current_epoch(ws, spec)

    randao_domain = accessors.get_domain(ws, constants.DOMAIN_RANDAO, epoch, spec)
    randao_reveal = bls.sign(
        sks[proposer], misc.compute_signing_root_epoch(epoch, randao_domain)
    )
    payload = ExecutionPayload(
        parent_hash=bytes(pre.latest_execution_payload_header.block_hash),
        prev_randao=accessors.get_randao_mix(ws, epoch, spec),
        timestamp=misc.compute_timestamp_at_slot(ws, slot, spec),
        block_number=slot,
        block_hash=bytes([slot % 256]) * 32,
    )
    body = BeaconBlockBody(
        randao_reveal=randao_reveal,
        eth1_data=pre.eth1_data,
        sync_aggregate=SyncAggregate(
            sync_committee_signature=bls.G2_POINT_AT_INFINITY
        ),
        execution_payload=payload,
    )
    block = BeaconBlock(
        slot=slot,
        proposer_index=proposer,
        parent_root=pre.latest_block_header.copy(
            state_root=pre.hash_tree_root(spec)
            if bytes(pre.latest_block_header.state_root) == b"\x00" * 32
            else bytes(pre.latest_block_header.state_root)
        ).hash_tree_root(spec),
        state_root=b"\x00" * 32,
        body=body,
    )
    # fill in the post-state root by dry-running the transition
    post = state_transition(
        state, SignedBeaconBlock(message=block), validate_result=False, spec=spec
    )
    block = block.copy(state_root=post.hash_tree_root(spec))
    domain = accessors.get_domain(ws, constants.DOMAIN_BEACON_PROPOSER, spec=spec)
    signature = bls.sign(sks[proposer], misc.compute_signing_root(block, domain))
    return SignedBeaconBlock(message=block, signature=signature)


def test_full_block_transition_with_validation(genesis):
    state, spec = genesis
    with use_chain_spec(spec):
        signed = _build_block(state, spec, 1, SECRET_KEYS)
        post = state_transition(state, signed, validate_result=True, spec=spec)
        assert post.slot == 1
        assert bytes(post.latest_block_header.body_root) == (
            signed.message.body.hash_tree_root(spec)
        )


def test_block_with_bad_signature_rejected(genesis):
    state, spec = genesis
    with use_chain_spec(spec):
        signed = _build_block(state, spec, 1, SECRET_KEYS)
        tampered = SignedBeaconBlock(
            message=signed.message, signature=bls.sign(SECRET_KEYS[0], b"\x00" * 32)
        )
        with pytest.raises(StateTransitionError, match="signature"):
            state_transition(state, tampered, validate_result=True, spec=spec)


def test_block_with_bad_state_root_rejected(genesis):
    state, spec = genesis
    with use_chain_spec(spec):
        signed = _build_block(state, spec, 1, SECRET_KEYS)
        bad_block = signed.message.copy(state_root=b"\xaa" * 32)
        proposer = bad_block.proposer_index
        ws = BeaconStateMut(process_slots(state, 1, spec))
        domain = accessors.get_domain(ws, constants.DOMAIN_BEACON_PROPOSER, spec=spec)
        resigned = SignedBeaconBlock(
            message=bad_block,
            signature=bls.sign(
                SECRET_KEYS[proposer], misc.compute_signing_root(bad_block, domain)
            ),
        )
        with pytest.raises(StateTransitionError, match="state root"):
            state_transition(state, resigned, validate_result=True, spec=spec)
