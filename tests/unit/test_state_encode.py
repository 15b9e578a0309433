"""The encoded image and the column-wise serializer vs the element loop.

``EncodedImage`` (ssz/encoded.py) builds a post-state's SSZ from what the
``TrackedList`` delta chain says changed; ``_serialize_rows``
(ssz/core.py) fills a big field a column at a time.  Both must be exact:
every case replays one mutation class of the slot/block/epoch
transitions through ONE image and holds each state's bytes to the
per-element ``Container.serialize`` loop, byte for byte.
"""

import pytest

from lambda_ethereum_consensus_tpu.config import minimal_spec, use_chain_spec
from lambda_ethereum_consensus_tpu.ssz import core
from lambda_ethereum_consensus_tpu.ssz.core import SSZError
from lambda_ethereum_consensus_tpu.ssz.encoded import EncodedImage
from lambda_ethereum_consensus_tpu.state_transition import process_slots
from lambda_ethereum_consensus_tpu.state_transition.genesis import build_genesis_state
from lambda_ethereum_consensus_tpu.state_transition.mutable import (
    _LIST_FIELDS,
    _MAX_CHAIN,
    BeaconStateMut,
)
from lambda_ethereum_consensus_tpu.types.beacon import BeaconState

REGISTRY_FIELDS = (
    "validators", "balances", "previous_epoch_participation",
    "current_epoch_participation", "inactivity_scores",
)


@pytest.fixture(scope="module")
def spec():
    return minimal_spec()


@pytest.fixture(scope="module")
def genesis(spec):
    from lambda_ethereum_consensus_tpu.crypto.bls import curve as C

    with use_chain_spec(spec):
        base = [
            C.g1_to_bytes(C.g1.multiply_raw(C.G1_GENERATOR, 3 + i)) for i in range(8)
        ]
        return build_genesis_state([base[i % 8] for i in range(64)], spec=spec)


def loop_oracle(state, spec) -> bytes:
    """``Container.serialize`` with batching out of reach: every list goes
    through ``b"".join(elem.serialize(v) for v in values)``."""
    saved = core._BATCH_MIN
    core._BATCH_MIN = 1 << 62
    try:
        return BeaconState.serialize(state, spec)
    finally:
        core._BATCH_MIN = saved


def mutated(state, fn) -> BeaconState:
    """One freeze/thaw cycle (one adopt-copy of every tracked list)."""
    ws = BeaconStateMut(state)
    fn(ws)
    return ws.freeze()


def resized(state, n) -> BeaconState:
    """``state`` with a registry of ``n`` validators (encoding only)."""
    reps = -(-n // len(state.validators))
    return state.copy(
        **{f: (list(getattr(state, f)) * reps)[:n] for f in REGISTRY_FIELDS}
    )


def touch_registry_row(i):
    def fn(ws):
        ws.update_validator(i, effective_balance=17 * 10**9, slashed=True)
        ws.balances[i] += 12345
        ws.current_epoch_participation[i] |= 5
        ws.inactivity_scores[i] = 9

    return fn


# ------------------------------------------------------------- the cases
# each yields the states ONE image encodes, in order


def case_plain_lists(genesis, spec):
    plain = genesis.copy(**{f: list(getattr(genesis, f)) for f in _LIST_FIELDS})
    assert type(plain.validators) is list
    yield plain
    yield plain  # unstamped: rebuilt again, same bytes
    yield mutated(plain, touch_registry_row(3))  # unknown provenance


def case_point_writes(genesis, spec):
    state = genesis
    yield state

    def write(field):
        def fn(ws):
            lst = getattr(ws, field)
            if field == "validators":
                ws.update_validator(5, exit_epoch=77)
            elif isinstance(lst[0], bytes):
                lst[len(lst) - 1] = b"\x5a" * 32
            else:
                lst[-2] = 3  # a negative index logs the resolved one

        return fn

    for field in _LIST_FIELDS:
        if len(getattr(state, field)):
            state = mutated(state, write(field))
            yield state
    yield mutated(state, lambda ws: None)  # nothing touched: every field reused


def case_append_validator(genesis, spec):
    yield genesis
    state = genesis
    for k in range(3):  # the image grows a row a time, and two at once
        def fn(ws, k=k):
            for j in range(1 + (k == 2)):
                v = ws.validators[j].copy(withdrawal_credentials=bytes([k + 1]) * 32)
                ws.append_validator(v, 31 * 10**9 + k)

        state = mutated(state, fn)
        yield state
    assert len(state.validators) == 68


def case_structural(genesis, spec):
    yield genesis
    state = mutated(genesis, lambda ws: ws.balances.__setitem__(slice(0, 2), [1, 2]))
    yield state
    state = mutated(state, lambda ws: ws.set_balances(b + 7 for b in ws.balances))
    yield state
    state = mutated(state, lambda ws: ws.inactivity_scores.reverse())
    yield state
    state = mutated(state, lambda ws: ws.validators.pop())  # a shrink
    yield state
    state = mutated(state, lambda ws: ws.validators.extend(ws.validators[:2]))
    yield state
    yield mutated(state, touch_registry_row(1))  # and back to point writes


def case_branched_lineage(genesis, spec):
    yield genesis
    a = mutated(genesis, touch_registry_row(2))
    b = mutated(genesis, touch_registry_row(40))
    yield a
    yield b
    yield a
    yield mutated(b, touch_registry_row(41))
    yield mutated(a, touch_registry_row(3))


def case_chain_cut(genesis, spec):
    yield genesis
    state = genesis
    for k in range(_MAX_CHAIN + 3):  # transitions nobody encodes
        state = mutated(state, touch_registry_row(10 + k))
    yield state


def case_epoch_boundary(genesis, spec):
    # balances far enough from 32 ETH that the boundary's effective-balance
    # update rewrites validators; flags so that the rotation carries data
    def prepare(ws):
        for i in range(0, 64, 5):
            ws.balances[i] = 20 * 10**9
            ws.current_epoch_participation[i] = 7

    state = mutated(genesis, prepare)
    yield state
    for slot in range(1, 2 * int(spec.SLOTS_PER_EPOCH) + 2):
        state = process_slots(state, slot, spec)
        yield state
    assert state.validators[5].effective_balance == 20 * 10**9


def case_63_elements(genesis, spec):
    state = resized(genesis, 63)
    yield state
    yield mutated(state, touch_registry_row(62))


def case_64_elements(genesis, spec):
    state = resized(genesis, 64)
    yield state
    yield mutated(state, touch_registry_row(63))


def case_65536_validators(genesis, spec):
    state = resized(genesis, 1 << 16)
    yield state
    state = mutated(state, touch_registry_row(65_535))
    yield state

    def flags(ws):
        for i in range(0, 1 << 16, 3):
            ws.current_epoch_participation[i] |= 2

    yield mutated(state, flags)  # a third of a field: past the rebuild line


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_image_matches_loop_oracle(case, genesis, spec):
    with use_chain_spec(spec):
        image = EncodedImage(BeaconState)
        for step, state in enumerate(case(genesis, spec)):
            fast = image.encode(state, spec)
            assert type(fast) is bytes
            assert fast == loop_oracle(state, spec), f"image differs at step {step}"
            # the cold path behind BeaconState.encode: same bytes
            assert state.encode(spec) == fast, f"encode differs at step {step}"
            back = BeaconState.decode(fast, spec)
            assert back == state and loop_oracle(back, spec) == fast


# --------------------------------------------------------- malformed values

MALFORMED = {
    "pubkey_47_bytes": lambda ws: ws.update_validator(3, pubkey=b"\x01" * 47),
    "credentials_not_bytes": lambda ws: ws.update_validator(
        3, withdrawal_credentials=[0] * 31),
    "effective_balance_2_64": lambda ws: ws.update_validator(
        3, effective_balance=1 << 64),
    "exit_epoch_negative": lambda ws: ws.update_validator(3, exit_epoch=-1),
    "slashed_2": lambda ws: ws.update_validator(3, slashed=2),
    "slashed_half": lambda ws: ws.update_validator(3, slashed=0.5),
    "balance_2_64": lambda ws: ws.balances.__setitem__(3, 1 << 64),
    "balance_negative": lambda ws: ws.balances.__setitem__(3, -1),
    "participation_256": lambda ws: ws.current_epoch_participation.__setitem__(3, 256),
    "block_root_31_bytes": lambda ws: ws.block_roots.__setitem__(3, b"\x02" * 31),
    "validators_over_limit": None,  # built below: needs the spec's limit
}


@pytest.mark.parametrize("kind", sorted(MALFORMED))
def test_malformed_value_raises_the_loops_error(kind, genesis, spec):
    """Validity must not depend on the path: the column builder, the
    image's full build and its patch all hand a malformed value to the
    element loop, which raises its own ``SSZError``."""
    with use_chain_spec(spec):
        if kind == "validators_over_limit":
            small = spec.replace(VALIDATOR_REGISTRY_LIMIT=63)
            bad, spec_used = genesis, small
        else:
            bad, spec_used = mutated(genesis, MALFORMED[kind]), spec
        with pytest.raises(SSZError) as oracle:
            loop_oracle(bad, spec_used)
        with pytest.raises(SSZError) as cold:
            bad.encode(spec_used)
        assert str(cold.value) == str(oracle.value)
        with pytest.raises(SSZError) as rebuilt:
            EncodedImage(BeaconState).encode(bad, spec_used)
        assert str(rebuilt.value) == str(oracle.value)
        # the patch path: an image level with the sound parent meets the
        # malformed element through the delta chain
        image = EncodedImage(BeaconState)
        image.encode(genesis, spec_used if kind != "validators_over_limit" else spec)
        with pytest.raises(SSZError) as patched:
            image.encode(bad, spec_used)
        assert str(patched.value) == str(oracle.value)
        # and the image is still sound afterwards
        assert image.encode(genesis, spec) == loop_oracle(genesis, spec)


def test_values_the_loop_accepts_encode_alike(genesis, spec):
    """What ``int()`` / ``bytes()`` / the 0-1 rule let through in the
    loop goes through the column builder with the same bytes (or falls
    to the loop): a float balance, a bool flag, a bytearray root."""
    with use_chain_spec(spec):
        def odd(ws):
            ws.balances[1] = 31.0e9
            ws.current_epoch_participation[2] = True
            ws.block_roots[3] = bytearray(b"\x07" * 32)
            ws.update_validator(4, slashed=1.0, exit_epoch=5.0)

        state = mutated(genesis, odd)
        want = loop_oracle(state, spec)
        assert state.encode(spec) == want
        image = EncodedImage(BeaconState)
        image.encode(genesis, spec)
        assert image.encode(state, spec) == want


def test_returned_bytes_are_a_snapshot_not_a_view(genesis, spec):
    """The kv engine may keep the object ``store_state`` hands it: the
    bytes of state k must not move when state k+1 is patched into the
    same arrays."""
    with use_chain_spec(spec):
        image = EncodedImage(BeaconState)
        first = image.encode(genesis, spec)
        kept = bytes(bytearray(first))  # a copy that shares nothing
        nxt = mutated(genesis, touch_registry_row(0))
        second = image.encode(nxt, spec)
        assert second != first
        assert first == kept == loop_oracle(genesis, spec)
        assert image.retained_bytes() >= 64 * 121
