"""``plainref_subnet.py`` against the mint worker's bursts at the rehearsal's
size (minimal preset, 256 validators): the plain reference has to give a
sound slot and a guard burst the minted verdicts and the table of the votes
it accepted, and has to answer differently once a signature's validity, a
vote's head root or a seen-set entry is altered.

    python3 -m pytest benchmark/tests/test_plainref_subnet.py -q

The mainnet-size agreement (2^20 validators) is a chip run's: every run of
``head.subnets-all`` compares it (PERF.md section 6).
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

SEEDS = (3, 2147483659)


def test_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "plainref_subnet.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or ".").split(".")[0])
    # plainref.py is the plain reference of block import, itself held to the same rule
    assert names <= {"__future__", "json", "sys", "time", "numpy", "plainref"}, names


def test_constants_are_the_specs():
    import plainref_subnet
    from lambda_ethereum_consensus_tpu.config import constants

    assert plainref_subnet.ATTESTATION_SUBNET_COUNT == constants.ATTESTATION_SUBNET_COUNT
    assert plainref_subnet.ATTESTATION_PROPAGATION_SLOT_RANGE == 32


@pytest.fixture(scope="module", params=SEEDS)
def world(request):
    """The seeded genesis, two minted bursts (a whole slot; a guard burst with
    its three faults) and a fresh reference holding the anchor state."""
    import hostside
    import plainref_subnet
    import subnet_mint
    from lambda_ethereum_consensus_tpu.config import constants, use_chain_spec
    from lambda_ethereum_consensus_tpu.ssz.hash import HashlibBackend, set_hash_backend
    from lambda_ethereum_consensus_tpu.state_transition import accessors

    seed = request.param
    os.environ.update(hostside.HOST_ENV)
    with open(os.path.join(HERE, "configs", "mainnet-1m-allsubnets.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", "subnets-all.json")) as f:
        mix = json.load(f)
    mix = {**mix, **mix["rehearse"]}
    set_hash_backend(HashlibBackend())
    spec, n = hostside.chain_spec(cfg, True)
    with use_chain_spec(spec):
        spec, keys, genesis = hostside.build_genesis(
            cfg, seed, int(time.time()) - 34 * int(spec.SECONDS_PER_SLOT), True)
        state_root = genesis.hash_tree_root(spec, backend=HashlibBackend())
        header = genesis.latest_block_header.copy(state_root=state_root)
        chain = hostside.Chain(spec, n, {
            "block_root": header.hash_tree_root(spec),
            "genesis_validators_root": bytes(genesis.genesis_validators_root),
            "seeds": {e: accessors.get_seed(genesis, e, constants.DOMAIN_BEACON_ATTESTER, spec)
                      for e in range(8)}})
        slot = subnet_mint.mint_burst(chain, keys, mix, seed, {
            "id": 0, "role": "window", "slot": 33, "oracle": True})
        guard = subnet_mint.mint_burst(chain, keys, mix, seed, {
            "id": 1, "role": "guard", "slot": 32, "committees": [0], "oracle": True,
            "guard": {"invalid": 1, "second_vote": 1, "wrong_subnet": 1}})
        ssz = genesis.encode(spec)

    def fresh():
        ref = plainref_subnet.SubnetReference("minimal", int(spec.SECONDS_PER_SLOT))
        assert ref.hold(ref.t.BeaconState.decode(ssz)) == chain.block_root
        return ref

    return {"fresh": fresh, "slot": slot, "guard": guard, "anchor": chain.block_root}


def verdicts(ref, burst, pushed_slot=34, **altered) -> str:
    """The reference's verdicts over a burst; ``altered``: position ->
    ``("valid", bool)`` or ``("ssz", bytes)``."""
    out = []
    for at, (raw, subnet, bad) in enumerate(
            zip(burst["ssz"], burst["subnet"].tolist(), burst["bad"].tolist())):
        valid = not bad
        what = altered.get(f"at{at}")
        if what and what[0] == "valid":
            valid = what[1]
        if what and what[0] == "ssz":
            raw = what[1]
        out.append(ref.verdict(subnet, raw, pushed_slot, valid))
    return "".join(out)


def test_a_sound_slot_gets_the_minted_verdicts_and_table(world):
    ref, burst = world["fresh"](), world["slot"]
    assert verdicts(ref, burst) == burst["expect"] == "A" * len(burst["ssz"])
    assert all(burst["oracle"])  # the native library agrees with the minting
    assert sorted(ref.latest) == sorted(burst["validator"].tolist())
    assert set(ref.latest.values()) == {(33 // 8, world["anchor"])}


def test_the_guard_burst_gets_reject_ignore_reject(world):
    ref, burst = world["fresh"](), world["guard"]
    got = verdicts(ref, burst)
    assert got == burst["expect"]
    assert got.count("R") == 2 and got.count("I") == 1 and got[-2:] == "IR"
    # the native library's verdict over the signatures alone: one invalid
    assert burst["oracle"].count(False) == 1
    assert [ok for ok, bad in zip(burst["oracle"], burst["bad"])] == [
        not bad for bad in burst["bad"]]
    # the rejected attester and the stray vote's leave no latest message
    accepted = {int(v) for v, g in zip(burst["validator"], got) if g == "A"}
    assert set(ref.latest) == accepted


def test_the_table_leaves_as_it_was_built(world):
    ref, burst = world["fresh"](), world["slot"]
    verdicts(ref, burst)
    table = ref.table()
    validators = np.frombuffer(bytes.fromhex(table["validators"]), "<u4")
    assert validators.tolist() == sorted(ref.latest)
    assert table["roots"] == [world["anchor"].hex()]


def test_an_invalid_signature_changes_the_verdict_and_the_table(world):
    ref, burst = world["fresh"](), world["slot"]
    got = verdicts(ref, burst, at3=("valid", False))
    assert got[3] == "R" and got.count("A") == len(got) - 1
    assert int(burst["validator"][3]) not in ref.latest


def test_an_altered_head_root_is_not_accepted(world):
    """The data's ``beacon_block_root`` (bytes 20..52 of the SSZ) altered: a
    block the node has not seen — IGNORE, and no latest message."""
    ref, burst = world["fresh"](), world["slot"]
    raw = bytearray(burst["ssz"][5])
    raw[4 + 16] ^= 1  # offset(4) + slot(8) + index(8): the root's first byte
    got = verdicts(ref, burst, at5=("ssz", bytes(raw)))
    assert got[5] == "I" and got.count("A") == len(got) - 1
    assert int(burst["validator"][5]) not in ref.latest


def test_a_seen_attester_is_ignored(world):
    ref, burst = world["fresh"](), world["slot"]
    ref.seen.add((33 // 8, int(burst["validator"][7])))
    got = verdicts(ref, burst)
    assert got[7] == "I" and got.count("A") == len(got) - 1


@pytest.mark.parametrize("pushed,want", [(33, "I"), (34, "A"), (47, "A"), (48, "I")])
def test_the_propagation_range_and_the_epoch_rule(world, pushed, want):
    """Slot 33 of epoch 4: not before slot 34 (fork choice counts it from the
    next slot), and the target epoch has to be the current or the previous
    one — at the minimal preset's 8-slot epochs that ends before the 32-slot
    propagation range does (slot 48 is epoch 6)."""
    ref, burst = world["fresh"](), world["slot"]
    assert ref.verdict(int(burst["subnet"][0]), burst["ssz"][0], pushed, True) == want


def test_two_bits_and_a_wrong_subnet_are_rejected(world):
    ref, burst = world["fresh"](), world["slot"]
    raw = bytearray(burst["ssz"][0])
    raw[-1] |= 0b110  # the bit list's last byte: two more member bits
    assert ref.verdict(int(burst["subnet"][0]), bytes(raw), 34, True) == "R"
    assert ref.verdict((int(burst["subnet"][0]) + 1) % 64, burst["ssz"][0], 34, True) == "R"
    assert ref.verdict(int(burst["subnet"][0]), burst["ssz"][0][:100], 34, True) == "R"


@pytest.mark.parametrize("pick_after_slots", range(12, 31))
def test_one_epoch_supplies_the_windows_slots(pick_after_slots):
    """Wherever the compiles end 12-30 slots after process start (13-14 warm,
    24-28 cold on the chip), the configuration's ``genesis_slots_back`` and the
    traffic's ``slot_lookback`` leave ``max_bursts`` unused slots of the epoch
    the warm-up burst primed, each still in the propagation range (the feeder's
    own rule) when a window opened 3 slots later, after the bank wait, has run
    its 4 slots and one of overrun."""
    from generators.subnet_votes import PROPAGATION_SLOTS, pick_slots

    with open(os.path.join(HERE, "configs", "mainnet-1m-allsubnets.json")) as f:
        back = int(json.load(f)["genesis_slots_back"])
    with open(os.path.join(HERE, "traffic", "subnets-all.json")) as f:
        mix = json.load(f)
    most, enough = int(mix["max_bursts"]), int(mix["min_epoch_slots"])
    assert 16 <= most <= 28 and enough == most == int(mix["min_banked_bursts"])
    for warm_after in range(6, min(14, pick_after_slots - 3)):
        current = back + warm_after + 1
        warm = current - 1 if (current - 1) % 32 else current - 2
        now = back + pick_after_slots
        slots = pick_slots(now, int(mix["slot_lookback"]), most, 32,
                           {warm, warm - 1}, {warm // 32}, enough)
        assert len(slots) == most and len(set(slots)) == most
        assert {s // 32 for s in slots} == {warm // 32}
        assert max(slots) < now and min(slots) + PROPAGATION_SLOTS > (now + 3 + 4 + 1) + 1
