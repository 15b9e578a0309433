"""``plainref_epoch.py`` against both of the program's epoch paths, field by
field, on seeded random states (minimal preset; ``epoch_states.py``): the
host path ``_process_epoch_host`` and the resident plane's
``process_epoch_resident`` (which must not fall back).  And its imports: a
plain reference that imports the program is not one.

    python3 -m pytest benchmark/tests/test_plainref_epoch.py -q

The mainnet-size agreement (2^20 validators) is a chip run's: every run of
``catchup.epoch-boundary`` compares it (PERF.md section 6).
"""

from __future__ import annotations

import ast
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(HERE, "tests"))
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import epoch_states  # noqa: E402


def test_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "plainref_epoch.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or ".").split(".")[0])
    assert names <= {"__future__", "json", "sys", "time", "numpy", "plainref"}, names


@pytest.mark.parametrize("preset", ["mainnet", "minimal"])
def test_epoch_constants_are_the_specs(preset):
    """Copied by hand from consensus-specs; the program's copy is a second
    witness of the copying, not a source."""
    import plainref_epoch
    from lambda_ethereum_consensus_tpu.config import constants, mainnet_spec, minimal_spec

    spec = {"mainnet": mainnet_spec, "minimal": minimal_spec}[preset]()
    for key, value in plainref_epoch.EPOCH_PRESETS[preset].items():
        assert int(getattr(spec, key)) == value, key
    for key in ("MAX_SEED_LOOKAHEAD", "MIN_VALIDATOR_WITHDRAWABILITY_DELAY",
                "MIN_EPOCHS_TO_INACTIVITY_PENALTY", "EJECTION_BALANCE",
                "INACTIVITY_SCORE_BIAS", "INACTIVITY_SCORE_RECOVERY_RATE",
                "INACTIVITY_PENALTY_QUOTIENT_BELLATRIX",
                "PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX", "HYSTERESIS_QUOTIENT",
                "HYSTERESIS_DOWNWARD_MULTIPLIER", "HYSTERESIS_UPWARD_MULTIPLIER"):
        assert int(getattr(spec, key)) == getattr(plainref_epoch, key), key
    assert constants.FAR_FUTURE_EPOCH == plainref_epoch.FAR_FUTURE_EPOCH
    assert constants.DOMAIN_SYNC_COMMITTEE == plainref_epoch.DOMAIN_SYNC_COMMITTEE


@pytest.fixture(scope="module")
def spec():
    from lambda_ethereum_consensus_tpu.config import minimal_spec

    return minimal_spec()


@pytest.fixture(scope="module")
def base(spec):
    from lambda_ethereum_consensus_tpu.config import use_chain_spec

    with use_chain_spec(spec):
        return epoch_states.genesis(spec)


def reference_after_epoch(staged, spec):
    import plainref
    import plainref_epoch

    ref = plainref_epoch.Reference("minimal", int(spec.SECONDS_PER_SLOT))
    plainref.answer(ref, {"cmd": "state"}, staged.encode(spec))
    ref.process_epoch()
    return ref


@pytest.mark.parametrize("seed", epoch_states.SEEDS)
@pytest.mark.parametrize("path", ["host", "resident"])
@pytest.mark.parametrize("case", list(epoch_states.CASES))
def test_every_field_agrees_with_the_program(base, spec, case, path, seed):
    import plainref
    from lambda_ethereum_consensus_tpu.config import use_chain_spec

    with use_chain_spec(spec):
        staged = epoch_states.staged_state(base, case, seed, spec)
        post, stats = epoch_states.program_epoch(staged, spec, resident=path == "resident")
        ref = reference_after_epoch(staged, spec)
        back = plainref.answer(ref, {"cmd": "readback"}, post.encode(spec))
    assert back["fields"] == 28 and back["fields_differ"] == []
    if path == "resident":
        assert stats["sweeps"] == 1 and stats["fallbacks"] == 0, stats
    # the case did what its name says (on the reference's side)
    c, state = epoch_states.CASES[case], ref.state
    if case.startswith("finalize"):
        assert state["finalized_checkpoint"]["epoch"] > c["finalized"]
    if case in ("leak", "no_leak_none_justified"):
        assert state["finalized_checkpoint"]["epoch"] == c["finalized"]
        assert ref.is_in_inactivity_leak() is (case == "leak")
    if case == "periods_end":
        assert len(state["historical_summaries"]) == 1 and state["eth1_data_votes"] == []
        assert state["current_sync_committee"] != state["next_sync_committee"]
    if c["epoch"] > 0:
        assert not plainref.same(state["balances"],
                                 plainref.np.asarray(staged.balances, "<u8"))
        assert not plainref.same(ref.col((80, 88)), plainref.np.asarray(
            [v.effective_balance for v in staged.validators], "<u8"))
        exits = ref.col((105, 113))
        assert len(set(exits[exits != plainref.np.uint64(2 ** 64 - 1)].tolist())) >= 3


def test_a_reward_off_by_one_gwei_is_named(base, spec):
    import plainref
    from lambda_ethereum_consensus_tpu.config import use_chain_spec

    with use_chain_spec(spec):
        staged = epoch_states.staged_state(base, "no_leak_none_justified", 3, spec)
        post, _ = epoch_states.program_epoch(staged, spec, resident=False)
        ref = reference_after_epoch(staged, spec)
        balances = list(post.balances)
        balances[5] += 1
        back = plainref.answer(ref, {"cmd": "readback"},
                               post.copy(balances=balances).encode(spec))
    assert back["fields_differ"] == ["balances"]
