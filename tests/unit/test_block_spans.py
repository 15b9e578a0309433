"""Where a block import's time goes (ISSUE 36): the spans inside
``block_transition``, ``on_block``'s tail and the node's ``_on_applied`` book
once a block (the three ``block_att_*`` once an attestation), the children
never outgrow their parent, a disabled registry creates no key for them,
and the collector's hook books ``gc_collect_seconds{generation}`` without
ever taking the registry's lock."""

import asyncio
import contextlib
import gc
import threading
import time

import pytest

from lambda_ethereum_consensus_tpu import telemetry
from lambda_ethereum_consensus_tpu.config import minimal_spec, use_chain_spec
from lambda_ethereum_consensus_tpu.crypto import bls
from lambda_ethereum_consensus_tpu.fork_choice import get_forkchoice_store, on_block, on_tick
from lambda_ethereum_consensus_tpu.state_transition import accessors, process_slots
from lambda_ethereum_consensus_tpu.state_transition.genesis import build_genesis_state
from lambda_ethereum_consensus_tpu.state_transition.mutable import BeaconStateMut
from lambda_ethereum_consensus_tpu.types.beacon import BeaconBlock, BeaconBlockBody
from lambda_ethereum_consensus_tpu.validator import build_signed_block, make_attestation

from .test_stage_spans import family_totals, gained, registry_on

N = 64
SKS = [(i + 1).to_bytes(32, "big") for i in range(N)]

# the direct children of block_transition, and the stages of one attestation
CHILDREN = ("block_slots", "block_fixed_checks", "block_payload", "block_attestations",
            "block_att_verify", "block_sync_aggregate", "block_post_root")
ATT_STAGES = ("block_att_committee", "block_att_signature_inputs", "block_att_participation")
TAIL = ("on_block_store_update", "on_block_pulled_up_tip")
APPLIED = ("store_block", "state_encode", "state_kv_put", "head_observe")
ONCE = ("block_transition", "fork_choice_on_block") + TAIL + APPLIED + tuple(
    c for c in CHILDREN if c != "block_fixed_checks")


@pytest.fixture(scope="module")
def blocks():
    """Genesis 26 s back, block 1 and block 2 carrying every committee of
    slot 1."""
    with use_chain_spec(minimal_spec()) as spec:
        genesis = build_genesis_state(
            [bls.sk_to_pk(k) for k in SKS], genesis_time=int(time.time()) - 26, spec=spec)
        signed1, post1 = build_signed_block(genesis, 1, SKS, spec=spec)
        ws = BeaconStateMut(process_slots(post1, 2, spec))
        root1 = signed1.message.hash_tree_root(spec)
        atts = [
            make_attestation(
                ws, slot=1, committee_index=index, head_root=root1,
                target=post1.current_justified_checkpoint.copy(
                    epoch=0, root=accessors.get_block_root(ws, 0, spec)),
                source=post1.current_justified_checkpoint, secret_keys=SKS, spec=spec)
            for index in range(accessors.get_committee_count_per_slot(ws, 0, spec))
        ]
        signed2, _ = build_signed_block(post1, 2, SKS, attestations=atts, spec=spec)
        yield spec, genesis, signed1, signed2


@pytest.fixture(scope="module")
def imported(blocks, tmp_path_factory):
    """Block 2 through a started node's ``on_block`` and ``_on_applied``:
    the families it gained, and the node's hook count before and after."""
    from lambda_ethereum_consensus_tpu.node import BeaconNode, NodeConfig

    spec, genesis, signed1, signed2 = blocks

    async def main():
        node = BeaconNode(NodeConfig(
            db_path=str(tmp_path_factory.mktemp("spans") / "n.wal"),
            genesis_state=genesis, wire=None, enable_range_sync=False,
        ))
        users = telemetry._GC_USERS
        await node.start()
        running = (telemetry._GC_USERS, telemetry._GC_TIMER in gc.callbacks)
        try:
            for signed in (signed1, signed2):
                before = family_totals()
                root = on_block(node.store, signed, spec=spec)
                node._on_applied(root, signed)
                got = gained(before, family_totals())
        finally:
            await node.stop()
        return got, users, running, telemetry._GC_USERS

    with registry_on(), use_chain_spec(spec):
        got, users, running, after = asyncio.run(asyncio.wait_for(main(), 120))
    return {"got": got, "n_atts": len(signed2.message.body.attestations),
            "users": users, "running": running, "after": after}


def test_one_block_books_each_family_once(imported):
    got, n = imported["got"], imported["n_atts"]
    assert n >= 1
    counts = {name: got.get(name + "_seconds", (0.0, 0))[1] for name in
              ONCE + ATT_STAGES + ("block_fixed_checks",)}
    assert counts == {**{name: 1 for name in ONCE}, **{name: n for name in ATT_STAGES},
                      "block_fixed_checks": 3}


def test_children_never_outgrow_their_parent(imported):
    got = imported["got"]

    def total(*names):
        return sum(got[name + "_seconds"][0] for name in names)

    assert 0.0 < total(*CHILDREN) <= total("block_transition")
    assert 0.0 < total(*ATT_STAGES) <= total("block_attestations")
    assert total("block_transition", *TAIL) <= total("fork_choice_on_block")


def test_a_node_installs_the_collectors_hook_and_stop_removes_it(imported):
    assert imported["running"] == (imported["users"] + 1, True)
    assert imported["after"] == imported["users"]
    if imported["users"] == 0:
        assert telemetry._GC_TIMER is None
        assert not any(isinstance(cb, telemetry._GcTimer) for cb in gc.callbacks)


def test_a_disabled_registry_creates_no_key(blocks, monkeypatch):
    spec, genesis, signed1, signed2 = blocks
    off = telemetry.Metrics(enabled=False)
    monkeypatch.setattr(telemetry, "_DEFAULT", off)
    monkeypatch.setattr(telemetry, "_GC_TIMER", None)
    monkeypatch.setattr(telemetry, "_GC_USERS", 0)
    with use_chain_spec(spec):
        anchor = BeaconBlock(
            slot=0, proposer_index=0, parent_root=bytes(genesis.latest_block_header.parent_root),
            state_root=genesis.hash_tree_root(spec), body=BeaconBlockBody())
        store = get_forkchoice_store(genesis, anchor, spec)
        on_tick(store, int(time.time()), spec)
        for signed in (signed1, signed2):
            on_block(store, signed, spec=spec)
    telemetry.gc_timer_install()
    try:
        assert telemetry._GC_TIMER is None
        gc.collect(2)
    finally:
        telemetry.gc_timer_remove()
    assert off.key_count() == 0
    assert off.family_names() == set()


@contextlib.contextmanager
def gc_timed():
    with registry_on() as m:
        telemetry.gc_timer_install()
        try:
            yield m
        finally:
            telemetry.gc_timer_remove()


def gc_count(m, generation: str) -> int:
    got = m.get_histogram("gc_collect_seconds", generation=generation)
    return 0 if got is None else got[3]


def test_a_full_collection_books_one_observation_under_generation_2():
    with gc_timed() as m:
        before = gc_count(m, "2")
        gc.collect(2)
        after = gc_count(m, "2")
    assert after == before + 1
    bounds, counts, total, count = m.get_histogram("gc_collect_seconds", generation="2")
    assert total > 0.0 and sum(counts) == count


def test_a_collection_under_the_registry_lock_does_not_deadlock():
    """A collection can begin inside an allocation made while
    ``Metrics._lock`` is held: the hook must not take that lock."""
    with gc_timed() as m:
        before = gc_count(m, "0")

        def collect_holding_the_lock():
            with m._lock:
                gc.collect(0)

        worker = threading.Thread(target=collect_holding_the_lock, daemon=True)
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive(), "the collector's hook took the registry's lock"
        assert gc_count(m, "0") == before + 1
