"""``plainref_agg.py`` against the program's host oracle and its device
aggregation (CPU, interpret mode) on seeded aggregates: minimal preset, 512
validators with a key each, so committees of 16 and the committee cache's
three gather widths (2, 4, 8).  For every miss count at which the side or
the width changes the plain reference's attesting indices and aggregate
public key have to equal the host oracle's (``get_attesting_indices``, a
``g1.affine_add`` walk) **and** what ``DeviceCommitteeCache.aggregate`` gives
on the entry the drain builds; its verdicts and latest-message table have to
follow the minted truth, and to change once a validity bit, a pushed slot, a
head root or the bytes are altered.

    python3 -m pytest benchmark/tests/test_plainref_agg.py -q

The mainnet-size agreement (2^20 validators, width 256) is a chip run's: every
run of ``head.agg-sparse`` compares it (PERF.md section 6).
"""

from __future__ import annotations

import ast
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

K = 16
SLOT = 33
# misses 0, 1, mmax, mmax+1, k/2, k/2+1, k-2, k-1 (one signer), k (nobody)
MISSES = (0, 1, 2, 3, 8, 9, 14, 15, 16)


def test_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "plainref_agg.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or ".").split(".")[0])
    # plainref.py and plainref_epoch.py are the plain references of block
    # import, themselves held to the same rule
    assert names <= {"__future__", "json", "sys", "time", "numpy", "plainref",
                     "plainref_epoch"}, names


@pytest.fixture(scope="module")
def world():
    """The seeded genesis, one aggregate per miss count (committee ``i`` of
    slot 33 misses ``MISSES[i]`` seeded members; four committees a slot, so
    three slots), and a way to a fresh reference holding the anchor state."""
    import random

    import hostside
    from lambda_ethereum_consensus_tpu.config import constants, use_chain_spec
    from lambda_ethereum_consensus_tpu.ssz.hash import HashlibBackend, set_hash_backend
    from lambda_ethereum_consensus_tpu.state_transition import accessors
    from lambda_ethereum_consensus_tpu.types.beacon import Attestation
    from lambda_ethereum_consensus_tpu.types.validator import (
        AggregateAndProof, SignedAggregateAndProof,
    )

    os.environ.update(hostside.HOST_ENV)
    cfg = {"key_cycle": 512, "rehearse": {"preset": "minimal", "validators": 512}}
    set_hash_backend(HashlibBackend())
    spec, n = hostside.chain_spec(cfg, True)
    rng = random.Random(34)
    with use_chain_spec(spec):
        spec, keys, genesis = hostside.build_genesis(
            cfg, 34, int(time.time()) - 40 * int(spec.SECONDS_PER_SLOT), True)
        state_root = genesis.hash_tree_root(spec, backend=HashlibBackend())
        header = genesis.latest_block_header.copy(state_root=state_root)
        chain = hostside.Chain(spec, n, {
            "block_root": header.hash_tree_root(spec),
            "genesis_validators_root": bytes(genesis.genesis_validators_root),
            "seeds": {e: accessors.get_seed(genesis, e, constants.DOMAIN_BEACON_ATTESTER, spec)
                      for e in range(8)}})
        placeholder = b"\xc0" + b"\x00" * 95
        minted = []
        for i, misses in enumerate(MISSES):
            slot, index = SLOT + i // 4, i % 4
            committee = chain.committee(slot, index)
            assert len(committee) == K
            bits = np.ones(K, bool)
            bits[rng.sample(range(K), misses)] = False
            data = chain.attestation_data(slot, index)
            sig = hostside.g2_mul(keys.C, chain.h_point(chain.signing_root(data)),
                                  keys.agg_sk(committee[bits]) or 1)
            att = Attestation(aggregation_bits=bits.tolist(), data=data,
                              signature=keys.C.g2_to_bytes(sig))
            wrapped = SignedAggregateAndProof(
                message=AggregateAndProof(aggregator_index=int(committee[0]), aggregate=att,
                                          selection_proof=placeholder),
                signature=placeholder)
            minted.append({"misses": misses, "slot": slot, "index": index, "bits": bits,
                           "att": att, "ssz": wrapped.encode(spec),
                           "program_committee": np.asarray(
                               accessors.get_beacon_committee(genesis, slot, index, spec))})
        ssz = genesis.encode(spec)

    def fresh():
        import plainref_agg

        ref = plainref_agg.AggregateReference("minimal", int(spec.SECONDS_PER_SLOT))
        assert ref.hold(ref.t.BeaconState.decode(ssz)) == chain.block_root
        return ref

    return {"fresh": fresh, "minted": minted, "genesis": genesis, "spec": spec,
            "anchor": chain.block_root}


@pytest.fixture(scope="module")
def answers(world):
    """The reference's answer to each minted aggregate, pushed two slots on."""
    ref = world["fresh"]()
    out = []
    for m in world["minted"]:
        letter, attesters = ref.verdict(m["ssz"], m["slot"] + 2, True)
        out.append({"letter": letter, "attesters": attesters,
                    "key": ref.aggregate_key(attesters)})
    return ref, out


@pytest.fixture(scope="module")
def device(world):
    """The program's own aggregation of the same aggregates, on the entries
    the drain builds, one call of the chain's packing per aggregate so that
    each runs at its own width."""
    from lambda_ethereum_consensus_tpu.config import use_chain_spec
    from lambda_ethereum_consensus_tpu.fork_choice.attestation import EpochAttestationContext
    from lambda_ethereum_consensus_tpu.ops import bls_batch as BB
    from lambda_ethereum_consensus_tpu.ops.bls_g1 import _ints_batch

    spec = world["spec"]
    out = []
    with use_chain_spec(spec):
        ctx = EpochAttestationContext(world["genesis"], SLOT // int(spec.SLOTS_PER_EPOCH), spec)
        store = BB.RegistryPlaneStore(interpret=True, min_capacity=512)
        from lambda_ethereum_consensus_tpu.fork_choice.attestation import registry_planes

        store.update(*registry_planes(world["genesis"], spec))
        cache = BB.DeviceCommitteeCache(store, ctx.committees, lengths=ctx.lengths,
                                        chunk=ctx.count)
        assert cache.widths == (2, 4, 8)
        for m in world["minted"]:
            cid, attesting, missing = ctx.participation(m["att"])
            side = BB.smaller_side(attesting, missing)
            planes = BB._pack_members(cache, [(cid, side, None, None)], 8)
            ax, ay, inf = cache.aggregate(planes[0], *planes[2:])
            x = _ints_batch(np.asarray(ax).T[:1].astype(np.int32))[0]
            y = _ints_batch(np.asarray(ay).T[:1].astype(np.int32))[0]
            out.append({"key": None if bool(np.asarray(inf)[0]) else (x, y),
                        "width": planes[2].shape[1], "attesting_side": side[1],
                        "attesting": attesting})
    return out


@pytest.mark.parametrize("at", range(len(MISSES)), ids=[f"misses{m}" for m in MISSES])
def test_indices_and_key_equal_the_host_oracle_and_the_device_sum(world, answers, device, at):
    from lambda_ethereum_consensus_tpu.crypto.bls.api import _pubkey_point
    from lambda_ethereum_consensus_tpu.crypto.bls.curve import g1

    m, (_ref, got), dev = world["minted"][at], answers, device[at]
    plain = got[at]
    # the host oracle: the program's committee, its bits, its affine walk
    members = m["program_committee"][m["bits"]]
    assert plain["attesters"].tolist() == members.tolist() == dev["attesting"].tolist()
    oracle = None
    for v in members.tolist():
        pt = _pubkey_point(bytes(world["genesis"].validators[v].pubkey))
        oracle = pt if oracle is None else g1.affine_add(oracle, pt)
    assert plain["key"] == oracle == dev["key"]
    assert plain["letter"] == ("R" if m["misses"] == K else "A")
    # both sides and all three widths are met over the cases
    assert dev["attesting_side"] is (m["misses"] > K // 2)
    assert dev["width"] == {0: 2, 1: 2, 2: 2, 3: 4, 8: 8, 9: 8, 14: 2, 15: 2, 16: 2}[m["misses"]]


def test_table_holds_every_attester_of_the_accepted(world, answers):
    ref, got = answers
    table = ref.table()
    validators = np.frombuffer(bytes.fromhex(table["validators"]), "<u4")
    want = np.unique(np.concatenate([g["attesters"] for g in got if g["letter"] == "A"]))
    assert validators.tolist() == want.tolist()
    assert set(np.frombuffer(bytes.fromhex(table["epochs"]), "<u4").tolist()) == {SLOT // 8}
    assert [bytes.fromhex(r) for r in table["roots"]] == [world["anchor"]]


def test_altered_inputs_change_the_answer(world):
    ref, m = world["fresh"](), world["minted"][1]
    assert ref.verdict(m["ssz"], m["slot"] + 1, False)[0] == "R"  # the minter's bit
    assert not (ref.latest_epoch >= 0).any()  # a REJECT leaves no latest message
    assert ref.verdict(m["ssz"], m["slot"], True)[0] == "I"  # its own slot is not over
    assert ref.verdict(m["ssz"], m["slot"] + 33, True)[0] == "I"  # out of the range
    assert ref.verdict(m["ssz"][:-1], m["slot"] + 1, True)[0] == "R"  # not the type
    other_head = bytearray(m["ssz"])
    at = bytes(other_head).index(world["anchor"])  # data.beacon_block_root comes first
    other_head[at] ^= 1
    assert ref.verdict(bytes(other_head), m["slot"] + 1, True)[0] == "I"  # unknown head
    assert ref.verdict(m["ssz"], m["slot"] + 1, True)[0] == "A"
    assert int((ref.latest_epoch >= 0).sum()) == K - m["misses"]


def test_answers_over_the_pipe_carry_the_sums(world):
    """``answer``: the framing the generator uses, keys as 96 bytes of hex."""
    import plainref_agg

    ref = world["fresh"]()
    pick = [world["minted"][i] for i in (1, 4, 8)]
    out = plainref_agg.answer(ref, {
        "cmd": "aggregates", "sums": True, "pushed_slots": [m["slot"] + 1 for m in pick],
        "valid": [1, 1, 1], "sizes": [len(m["ssz"]) for m in pick]},
        b"".join(m["ssz"] for m in pick))
    assert out["verdicts"] == "AAR" and out["attesters"] == [15, 8, 0]
    assert out["sums"][2] == "00" * 96 and len(out["sums"][0]) == 192
    x = int(out["sums"][0][:96], 16)
    assert x == ref.aggregate_key(ref.verdict(pick[0]["ssz"], SLOT + 1, True)[1])[0]
    assert json.dumps(plainref_agg.answer(ref, {"cmd": "table"}, b""))
