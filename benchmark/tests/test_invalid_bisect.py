"""A flush with invalid aggregates through the node's verify, held to the
plain reference of the aggregate channel (CPU, interpret mode): the seeded
genesis and aggregates of ``test_plainref_agg.py`` (minimal preset, 512
validators, committees of 16), two of them given each other's signature — a
well-formed subgroup point that only the pairing rejects — pushed in a
shuffled order through ``on_attestation_batch`` on the cached device path
(``batch_verify_each_cached``: bisection on the committee cache).  Every
verdict and ``store.latest_messages`` have to equal ``plainref_agg.py``'s
answers and table, exact, as ``generators/invalid_bursts.py`` compares them
on the chip; a store where a bad aggregate's votes were applied has to fail
the comparison.

    python3 -m pytest benchmark/tests/test_invalid_bisect.py -q
"""

from __future__ import annotations

import functools
import os
import random
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "tests"))

from test_plainref_agg import SLOT, world  # noqa: E402,F401  (the seeded genesis, its aggregates)

BAD = (1, 4)  # these two aggregates carry each other's signature


@pytest.fixture(scope="module")
def flushed(world):  # noqa: F811
    """The aggregates, two made invalid, shuffled, through one
    ``on_attestation_batch`` on the cached path; the plain reference told
    each one's validity bit, pushed one slot after the newest."""
    from lambda_ethereum_consensus_tpu.config import use_chain_spec
    from lambda_ethereum_consensus_tpu.crypto.bls import batch as batch_mod
    from lambda_ethereum_consensus_tpu.fork_choice import on_attestation_batch
    from lambda_ethereum_consensus_tpu.fork_choice.handlers import on_tick
    from lambda_ethereum_consensus_tpu.fork_choice.store import get_forkchoice_store
    from lambda_ethereum_consensus_tpu.ops import bls_batch as BB
    from lambda_ethereum_consensus_tpu.types.beacon import BeaconBlock, BeaconBlockBody
    from lambda_ethereum_consensus_tpu.types.validator import SignedAggregateAndProof

    spec, genesis = world["spec"], world["genesis"]
    minted = [dict(m) for m in world["minted"]]
    a, b = (minted[i] for i in BAD)
    a["att"], b["att"] = (a["att"].copy(signature=b["att"].signature),
                          b["att"].copy(signature=a["att"].signature))
    for m in (a, b):
        wrapped = SignedAggregateAndProof.decode(m["ssz"], spec)
        m["ssz"] = wrapped.copy(message=wrapped.message.copy(aggregate=m["att"])).encode(spec)
    for i, m in enumerate(minted):
        m["valid"] = i not in BAD
    random.Random(38).shuffle(minted)
    pushed = SLOT + 3  # the newest aggregates are of slot SLOT + 2
    with use_chain_spec(spec), pytest.MonkeyPatch.context() as mp:
        mp.setenv("BLS_DEVICE_CHAIN", "1")
        mp.setenv("BLS_DEVICE_CHAIN_MIN", "1")
        mp.setattr(batch_mod, "_COEFF_BITS", 16)
        mp.setattr(BB, "chain_verify_cached",
                   functools.partial(BB.chain_verify_cached, coeff_bits=16))
        block = BeaconBlock(slot=0, proposer_index=0,
                            parent_root=bytes(genesis.latest_block_header.parent_root),
                            state_root=genesis.hash_tree_root(spec), body=BeaconBlockBody())
        assert block.hash_tree_root(spec) == world["anchor"]
        store = get_forkchoice_store(genesis, block, spec)
        on_tick(store, int(genesis.genesis_time) + pushed * int(spec.SECONDS_PER_SLOT), spec)
        results = on_attestation_batch(store, [m["att"] for m in minted], spec=spec)
    ref = world["fresh"]()
    letters = [ref.verdict(m["ssz"], pushed, m["valid"])[0] for m in minted]
    return {"store": store, "results": results, "ref": ref, "letters": letters,
            "minted": minted, "spec": spec}


def letter(result) -> str:
    if result is None:
        return "A"
    return "R" if result.reject else "I"


def differences(latest: dict, results, letters, table) -> int:
    """Verdicts and latest messages that differ from the plain reference's,
    as the generator counts them."""
    differ = sum(letter(r) != want for r, want in zip(results, letters))
    validators = np.frombuffer(bytes.fromhex(table["validators"]), "<u4").tolist()
    epochs = np.frombuffer(bytes.fromhex(table["epochs"]), "<u4").tolist()
    root_ids = np.frombuffer(bytes.fromhex(table["root_ids"]), "<u4").tolist()
    roots = [bytes.fromhex(r) for r in table["roots"]]
    differ += len(set(latest) ^ set(validators))
    for v, e, r in zip(validators, epochs, root_ids):
        m = latest.get(v)
        differ += m is not None and (int(m.epoch) != e or bytes(m.root) != roots[r])
    return differ


def test_the_invalid_are_rejected_and_the_rest_accepted(flushed):
    got = "".join(letter(r) for r in flushed["results"])
    want = "".join("R" if not m["valid"] or m["misses"] == 16 else "A"
                   for m in flushed["minted"])
    assert got == want == "".join(flushed["letters"])
    assert got.count("R") == len(BAD) + 1  # the two bad ones and the empty aggregate


def test_verdicts_and_latest_messages_equal_the_plain_reference(flushed):
    table = flushed["ref"].table()
    latest = flushed["store"].latest_messages
    assert differences(latest, flushed["results"], flushed["letters"], table) == 0
    assert len(flushed["store"].latest_messages) == sum(
        16 - m["misses"] for m in flushed["minted"] if m["valid"])


def test_a_bad_aggregates_votes_applied_fail_the_comparison(flushed):
    """The fault the guarantee excludes: the invalid aggregate's attesters
    written into the latest-message table as an acceptance would."""
    from lambda_ethereum_consensus_tpu.fork_choice.store import LatestMessage

    latest = dict(flushed["store"].latest_messages)
    bad = next(m for m in flushed["minted"] if not m["valid"])
    data = bad["att"].data
    for v in bad["program_committee"][bad["bits"]].tolist():
        latest[v] = LatestMessage(epoch=int(data.target.epoch),
                                  root=bytes(data.beacon_block_root))
    table = flushed["ref"].table()
    assert differences(latest, flushed["results"], flushed["letters"], table) == 16 - bad["misses"]
