"""``plainref.py`` against the program's host lineage at the rehearsal's size
(minimal preset, 256 validators), and with the answer broken underneath: the
plain reference has to agree with sound blocks root for root, and has to
disagree — or refuse — once a state, a block or a persisted byte is altered.

    python3 -m pytest benchmark/tests/test_plainref.py -q

The mainnet-size agreement (2^20 validators) is a chip run's: every run of
``catchup.range-blocks`` compares it (PERF.md section 6).
"""

from __future__ import annotations

import ast
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

SEEDS = (3, 2147483659, 2147483777)


def test_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "plainref.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or ".").split(".")[0])
    assert names <= {"__future__", "hashlib", "json", "struct", "sys", "time", "numpy"}, names


@pytest.mark.parametrize("preset", ["mainnet", "minimal"])
def test_presets_are_the_specs(preset):
    """Copied by hand from consensus-specs; the program's copy is a second
    witness of the copying, not a source."""
    import plainref
    from lambda_ethereum_consensus_tpu.config import mainnet_spec, minimal_spec

    spec = {"mainnet": mainnet_spec, "minimal": minimal_spec}[preset]()
    for key, value in plainref.PRESETS[preset].items():
        assert int(getattr(spec, key)) == value, key
    for key in ("MAX_ATTESTATIONS", "MAX_DEPOSITS", "MAX_EFFECTIVE_BALANCE",
                "EFFECTIVE_BALANCE_INCREMENT", "BASE_REWARD_FACTOR",
                "VALIDATOR_REGISTRY_LIMIT", "HISTORICAL_ROOTS_LIMIT",
                "MAX_VALIDATORS_PER_COMMITTEE", "MIN_ATTESTATION_INCLUSION_DELAY"):
        assert int(getattr(spec, key)) == getattr(plainref, key), key


def lineage_frames(seed: int) -> tuple[list[dict], int]:
    """The catch-up cell's blocks at the rehearsal's size, built in this
    process by the harness's lineage code (``hostside.build_blocks``)."""
    import hostside
    from lambda_ethereum_consensus_tpu.config import use_chain_spec
    from lambda_ethereum_consensus_tpu.ssz.hash import HashlibBackend, set_hash_backend

    os.environ.update(hostside.HOST_ENV)
    with open(os.path.join(HERE, "configs", "mainnet-1m-catchup.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", "range-blocks.json")) as f:
        mix = json.load(f)
    mix = {**mix, **mix["rehearse"]}
    set_hash_backend(HashlibBackend())
    spec, _n = hostside.chain_spec(cfg, True)
    with use_chain_spec(spec):
        spec, keys, genesis = hostside.build_genesis(
            cfg, seed, int(time.time()) - 36 * int(spec.SECONDS_PER_SLOT), True)
        plan = [["warmup", s] for s in mix["warmup_slots"]] + [
            ["window", mix["first_slot"] + i] for i in range(mix["blocks"])]
        cmd = {"blocks": plan, "participation": mix["participation"],
               "attestation_slots_back": mix["attestation_slots_back"],
               "prestate_after": mix["warmup_slots"][-1], "poststate": True}
        return list(hostside.build_blocks(spec, keys, genesis, cmd, seed)), int(
            spec.SECONDS_PER_SLOT)


@pytest.fixture(scope="module", params=SEEDS)
def lineage(request):
    frames, seconds_per_slot = lineage_frames(request.param)
    return {"pre": next(f for f in frames if f["kind"] == "prestate"),
            "blocks": [f for f in frames if f["kind"] == "block" and f["role"] == "window"],
            "last": frames[-1], "seconds_per_slot": seconds_per_slot}


def follow(lineage, pre_ssz=None, block_ssz=None):
    """A fresh reference over the lineage; ``block_ssz`` replaces the first block."""
    import plainref

    ref = plainref.Reference("minimal", lineage["seconds_per_slot"])
    plainref.answer(ref, {"cmd": "state"}, pre_ssz or lineage["pre"]["ssz"])
    answers = []
    for i, f in enumerate(lineage["blocks"]):
        ssz = block_ssz if (i == 0 and block_ssz is not None) else f["ssz"]
        try:
            answers.append(plainref.answer(ref, {"cmd": "block"}, ssz))
        except plainref.Refused as e:
            answers.append({"kind": "refused", "what": str(e)})
            break
    return ref, answers


def test_sound_blocks_agree_root_for_root(lineage):
    import plainref

    ref, answers = follow(lineage)
    assert len(answers) == len(lineage["blocks"]) >= 2
    for a, f in zip(answers, lineage["blocks"]):
        assert a["kind"] == "block" and a["attestations"] == f["attestations"] > 0
        assert a["block_root"] == f["root"].hex()
        assert a["post_state_root"] == a["claimed_state_root"] == f["post_state_root"].hex()
    assert answers[-1]["post_state_root"] == lineage["last"]["post_state_root"].hex()
    back = plainref.answer(ref, {"cmd": "readback"}, lineage["last"]["ssz"])
    assert back["fields"] == 28 and back["fields_differ"] == []


def test_a_persisted_byte_altered_is_named(lineage):
    import plainref

    ref, _ = follow(lineage)
    ssz = bytearray(lineage["last"]["ssz"])
    ssz[8 + 32] ^= 1  # the slot's lowest byte
    back = plainref.answer(ref, {"cmd": "readback"}, bytes(ssz))
    assert back["fields_differ"] == ["slot"]
    ssz = bytearray(lineage["last"]["ssz"])
    ssz[-1] ^= 1  # the tail of the last variable field that holds bytes
    back = plainref.answer(ref, {"cmd": "readback"}, bytes(ssz))
    assert len(back["fields_differ"]) == 1


def test_a_balance_altered_in_the_state_moves_every_root(lineage):
    import plainref

    t = plainref.Types(plainref.PRESETS["minimal"])
    state = t.BeaconState.decode(lineage["pre"]["ssz"])
    ref, answers = follow(lineage)
    # the balances' place in the encoding: after the registry's records
    fixed = lineage["pre"]["ssz"]
    at = fixed.index(state["balances"].tobytes())
    ssz = bytearray(fixed)
    ssz[at] ^= 1
    _ref, altered = follow(lineage, pre_ssz=bytes(ssz))
    assert all(a["kind"] == "refused" or a["post_state_root"] != a["claimed_state_root"]
               for a in altered)
    assert altered[0].get("post_state_root") != answers[0]["post_state_root"]


def test_a_vote_altered_in_a_block_is_refused_or_moves_the_root(lineage):
    import plainref

    t = plainref.Types(plainref.PRESETS["minimal"])
    first = lineage["blocks"][0]
    signed = t.SignedBeaconBlock.decode(first["ssz"])
    bits = signed["message"]["body"]["attestations"][0]["aggregation_bits"]
    packed = plainref.np.packbits(bits, bitorder="little").tobytes()
    ssz = bytearray(first["ssz"])
    # the first attestation's bits are the tail of its encoding; find them by
    # the signature that stands before them
    sig = signed["message"]["body"]["attestations"][0]["signature"]
    at = bytes(ssz).index(sig) + len(sig)
    assert bytes(ssz[at:at + len(packed) - 1]) == packed[:-1]
    ssz[at] ^= 1  # one member's vote flipped
    _ref, altered = follow(lineage, block_ssz=bytes(ssz))
    a = altered[0]
    assert a["kind"] == "refused" or a["post_state_root"] != a["claimed_state_root"]
