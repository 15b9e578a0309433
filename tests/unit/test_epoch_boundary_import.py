"""A range round across an epoch boundary, imported as the benchmark's cell
``catchup.epoch-boundary`` imports it — ``SyncBlocks.run()`` ->
``PendingBlocks`` -> ``on_block`` with the resident epoch plane forced — and
held to the plain reference ``benchmark/plainref_epoch.py`` (the spec's
``process_epoch`` on numpy and hashlib, importing nothing of the program):
block roots, post-state roots and the persisted post-state field by field.
Minimal preset, 256 validators, the rehearsal's slots (17, 23 | 24, 25).

With it, the spans and the counter that PR 32 put inside the boundary
(``epoch_plane_sync`` / ``epoch_plane_sweep`` / ``epoch_writeback``,
``state_root_incremental``, ``epoch_committees_build``,
``state_root_rebuilt_chunks_total``), the side of the device floor a field of
exactly 2^18 chunks takes, and the transition warmer of a node that is given
no drain shapes.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from lambda_ethereum_consensus_tpu import telemetry  # noqa: E402
from lambda_ethereum_consensus_tpu.config import use_chain_spec  # noqa: E402

SEEDS = (3, 2147483659, 2147483777)
SPANS = ("epoch_transition", "epoch_plane_sync", "epoch_plane_sweep", "epoch_writeback",
         "state_root_incremental", "epoch_committees_build", "block_transition")
COUNTER = "state_root_rebuilt_chunks_total"


def span_counts() -> dict:
    m = telemetry.get_metrics()
    return {name: sum(count for *_rest, count in m.histogram_series(name + "_seconds"))
            for name in SPANS}


def counter_rows() -> dict:
    """``{(field, where): value}`` of the rebuilt-chunks counter."""
    out = {}
    text = telemetry.get_metrics().render_prometheus(self_scrape=False)
    for line in text.splitlines():
        if line.startswith(COUNTER + "{"):
            labels, value = line[len(COUNTER) + 1:].rsplit("} ", 1)
            pairs = dict(p.split("=") for p in labels.replace('"', "").split(","))
            out[(pairs["field"], pairs["where"])] = float(value)
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}


def lineage(seed: int, monkeypatch):
    """The cell's blocks at the rehearsal's size, on the host lineage."""
    import hostside
    from lambda_ethereum_consensus_tpu.ssz.hash import HashlibBackend, set_hash_backend

    for key, value in hostside.HOST_ENV.items():
        monkeypatch.setenv(key, value)
    with open(os.path.join(BENCH, "configs", "mainnet-1m-catchup-epochs.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "epoch-boundary.json")) as f:
        mix = json.load(f)
    mix = {**mix, **mix["rehearse"]}
    previous = set_hash_backend(HashlibBackend())
    try:
        spec, _n = hostside.chain_spec(cfg, True)
        with use_chain_spec(spec):
            genesis_time = int(time.time()) - int(cfg["genesis_slots_back"]) * int(
                spec.SECONDS_PER_SLOT)
            spec, keys, genesis = hostside.build_genesis(cfg, seed, genesis_time, True)
            plan = [["warmup", s] for s in mix["warmup_slots"]] + [
                ["window", mix["first_slot"] + i] for i in range(mix["blocks"])]
            cmd = {"blocks": plan, "participation": mix["participation"],
                   "attestation_slots_back": mix["attestation_slots_back"],
                   "prestate_after": mix["warmup_slots"][-1]}
            frames = list(hostside.build_blocks(spec, keys, genesis, cmd, seed))
    finally:
        set_hash_backend(previous)
    return spec, genesis, frames


@pytest.fixture(scope="module", params=SEEDS)
def imported(request, tmp_path_factory):
    """One import of the round per seed: what the node stored, what the
    reference answered, and what each block booked."""
    import plainref
    import plainref_epoch
    from generators.range_blocks import RangePeer
    from lambda_ethereum_consensus_tpu.fork_choice import (
        get_forkchoice_store, get_head, on_tick,
    )
    from lambda_ethereum_consensus_tpu.node.pending_blocks import PendingBlocks
    from lambda_ethereum_consensus_tpu.node.sync import SyncBlocks
    from lambda_ethereum_consensus_tpu.store import state_store
    from lambda_ethereum_consensus_tpu.store.kv import KvStore
    from lambda_ethereum_consensus_tpu.types.beacon import BeaconBlock, BeaconBlockBody

    mp = pytest.MonkeyPatch()
    try:
        spec, genesis, frames = lineage(request.param, mp)
        mp.setenv("GRAFT_RESIDENT_EPOCH", "1")  # 256 validators: forced
        blocks = [f for f in frames if f["kind"] == "block"]
        window = [f for f in blocks if f["role"] == "window"]
        pre = next(f for f in frames if f["kind"] == "prestate")
        with use_chain_spec(spec):
            anchor = BeaconBlock(
                slot=0, proposer_index=0,
                parent_root=bytes(genesis.latest_block_header.parent_root),
                state_root=genesis.hash_tree_root(spec), body=BeaconBlockBody())
            store = get_forkchoice_store(genesis, anchor, spec)
            on_tick(store, int(time.time()), spec)
            kv = KvStore(str(tmp_path_factory.mktemp("epoch") / "chain.wal"))
            states = state_store.StateStore(kv)
            booked = []  # per block applied: spans and counter rows gained

            marks = [span_counts(), counter_rows()]

            def on_applied(root, signed):
                states.store_state(root, store.block_states[root], spec)
                now = [span_counts(), counter_rows()]
                booked.append({"slot": int(signed.message.slot),
                               "spans": delta(now[0], marks[0]),
                               "rows": delta(now[1], marks[1])})
                marks[:] = now

            pending = PendingBlocks(store, spec, on_applied=on_applied)
            peer = RangePeer(spec)
            for f in blocks:
                peer.serve(f)
            fetched = asyncio.run(SyncBlocks(store, pending, peer, spec).run())
            head = get_head(store, spec)
            last = store.block_states[window[-1]["root"]]
            raw = kv.get(state_store._STATE + window[-1]["root"])

            ref = plainref_epoch.Reference("minimal", int(spec.SECONDS_PER_SLOT))
            plainref.answer(ref, {"cmd": "state"}, pre["ssz"])
            answers = [plainref_epoch.answer(ref, {"cmd": "block"}, f["ssz"]) for f in window]
            back = plainref.answer(ref, {"cmd": "readback"}, raw)
        yield {"spec": spec, "store": store, "pending": pending, "blocks": blocks,
               "window": window, "fetched": fetched, "head": head, "last": last,
               "answers": answers, "back": back, "booked": booked}
    finally:
        mp.undo()


def test_every_block_of_the_round_is_imported(imported):
    roots = [f["root"] for f in imported["blocks"]]
    assert imported["fetched"] >= len(roots)
    assert all(r in imported["store"].blocks for r in roots)
    assert not imported["pending"].invalid
    assert imported["head"] == roots[-1]
    assert [b["slot"] for b in imported["booked"]] == [f["slot"] for f in imported["blocks"]]


def test_roots_equal_the_plain_references(imported):
    for answer, frame in zip(imported["answers"], imported["window"]):
        assert answer["kind"] == "block" and answer["attestations"] == frame["attestations"] > 0
        assert answer["block_root"] == frame["root"].hex()
        assert (answer["post_state_root"] == answer["claimed_state_root"]
                == frame["post_state_root"].hex())
    # the window's first block is the one across the boundary
    assert [a["epochs_processed"] for a in imported["answers"]] == [1, 0]


def test_the_persisted_state_equals_the_plain_references(imported):
    assert imported["back"]["fields"] == 28 and imported["back"]["fields_differ"] == []


def test_every_boundary_went_through_the_resident_plane(imported):
    plane = getattr(imported["last"], "_resident_plane", None)
    assert plane is not None
    assert plane.stats["sweeps"] == 3 and plane.stats["fallbacks"] == 0  # slots 8, 16, 24
    assert plane.stats["scatter_elems"] > 0  # the third sync shipped deltas


def test_the_boundary_block_books_one_span_of_each_stage(imported):
    first, boundary, plain = (imported["booked"][i]["spans"] for i in (0, 2, 3))
    for name in ("epoch_transition", "epoch_plane_sync", "epoch_plane_sweep",
                 "epoch_writeback"):
        assert first[name] == 2, (name, first)  # slot 17 over the anchor: two boundaries
        assert boundary[name] == 1, (name, boundary)
        assert name not in plain, (name, plain)
    assert boundary["block_transition"] == plain["block_transition"] == 1


def test_the_state_root_span_once_a_slot_and_once_a_check(imported):
    first, second, boundary, plain = (b["spans"]["state_root_incremental"]
                                      for b in imported["booked"])
    assert (first, second, boundary, plain) == (17 + 1, 6 + 1, 1 + 1, 1 + 1)


def test_an_epochs_committees_book_their_build_once(imported, monkeypatch):
    """``epoch_committees_build``: the shuffle when the epoch's context is
    first asked for, the device cache when it is first used, neither again.
    (On the chip the first block that carries a vote of the new epoch books
    both; the CPU's blocks take the host RLC branch and build neither.)"""
    from lambda_ethereum_consensus_tpu.fork_choice import attestation as FA
    from lambda_ethereum_consensus_tpu.ops import bls_batch

    def parts():
        return {dict(labels).get("part"): count for labels, *_rest, count
                in telemetry.get_metrics().histogram_series("epoch_committees_build_seconds")}

    class StubCache:
        def __init__(self, store, committees, lengths, chunk):
            self.count = len(lengths)

    monkeypatch.setattr(bls_batch, "DeviceCommitteeCache", StubCache)
    monkeypatch.setattr(FA, "device_plane_store", lambda state, spec: None)
    monkeypatch.setattr(FA, "_STATE_CTX", {})
    spec, state = imported["spec"], imported["last"]
    with use_chain_spec(spec):
        before = parts()
        ctx = FA.get_state_attestation_context(state, 3, spec)
        assert delta(parts(), before) == {"shuffle": 1}
        assert FA.get_state_attestation_context(state, 3, spec) is ctx
        assert ctx.device_cache().count == ctx.count == ctx.device_cache().count
        assert delta(parts(), before) == {"shuffle": 1, "device_cache": 1}


def test_the_counter_names_field_and_where(imported):
    boundary, plain = (imported["booked"][i]["rows"] for i in (2, 3))
    # the sweep replaced every balance: one whole-field rebuild, on the host
    # at this size (256 validators x 8 bytes = 64 chunks), none on the device
    assert boundary[("balances", "host")] == 64
    assert not [k for k in boundary if k[1] == "device"]
    assert boundary[("balances", "paths")] > 0  # the block's own rewards
    # a plain block re-hashes paths only
    assert plain and all(where == "paths" for _field, where in plain)
    assert ("balances", "paths") in plain


class CountingBackend:
    """A hash backend that is not hashlib's, so the engine takes it for the
    device's, and that counts the rows it is given."""

    def __init__(self):
        from lambda_ethereum_consensus_tpu.ssz.hash import hashlib_level

        self.rows, self._level = 0, hashlib_level

    def hash_level(self, blocks):
        self.rows += blocks.shape[0]
        return self._level(blocks)


@pytest.mark.parametrize("chunks,side", [(1 << 4, "host"), ((1 << 4) + 1, "device")])
def test_a_field_of_exactly_the_floor_is_hashed_on_the_host(monkeypatch, chunks, side):
    """``_DEVICE_CHUNKS`` is compared with ``>``: a field of exactly that many
    chunks stays on the host.  ``balances`` at 2^20 validators is exactly
    2^18 chunks, the floor as it stands: no field of a steady-state boundary
    is hashed on the device at that size (PERF.md section 5, PR 32).  Who
    moves the floor, or the comparison, meets this test."""
    from lambda_ethereum_consensus_tpu import ssz
    from lambda_ethereum_consensus_tpu.ssz import incremental
    from lambda_ethereum_consensus_tpu.types.base import uint64

    assert incremental._DEVICE_CHUNKS == 1 << 18 == (1 << 20) * 8 // 32
    monkeypatch.setattr(incremental, "_DEVICE_CHUNKS", 1 << 4)

    # (this module's annotations are strings: the class is made by hand)
    Holder = type(ssz.Container)("Holder", (ssz.Container,), {
        "__annotations__": {"values": ssz.List(uint64, 1 << 20)}})

    backend = CountingBackend()
    engine = incremental.IncrementalStateRoot(Holder, backend=backend)
    before = counter_rows()
    holder = Holder(values=list(range(chunks * 4)))
    assert engine.root(holder) == holder.hash_tree_root()
    rows = delta(counter_rows(), before)
    assert rows == {("values", side): chunks}
    assert (backend.rows > 0) is (side == "device")


def test_a_node_without_drain_shapes_warms_the_delta_scatters(monkeypatch):
    """What ``catchup.epoch-boundary`` found on its first run: the plane's
    delta scatters are first dispatched by the second boundary that has a
    block behind it, and a node started without drain shapes had warmed
    nothing — so they were lowered and compiled inside that block's import.
    After the transition warmer, a sync that ships deltas lowers nothing."""
    from lambda_ethereum_consensus_tpu.config import minimal_spec
    from lambda_ethereum_consensus_tpu.crypto import bls
    from lambda_ethereum_consensus_tpu.node.warmup import start_transition_warmer
    from lambda_ethereum_consensus_tpu.ops.aot import aot_stats
    from lambda_ethereum_consensus_tpu.state_transition.genesis import build_genesis_state
    from lambda_ethereum_consensus_tpu.state_transition.mutable import BeaconStateMut
    from lambda_ethereum_consensus_tpu.state_transition.resident import ResidentEpochPlane

    n = 48  # a capacity (64) no other test of this process warms
    monkeypatch.setenv("GRAFT_RESIDENT_EPOCH", "0")
    assert start_transition_warmer(n) is None  # off for this size: nothing to warm
    monkeypatch.setenv("GRAFT_RESIDENT_EPOCH", "1")
    stats: dict = {}
    warmer = start_transition_warmer(n, stats)
    warmer.join(300)
    assert not warmer.is_alive() and "error" not in stats and "transition_s" in stats

    spec = minimal_spec()
    with use_chain_spec(spec):
        sks = [(i + 1).to_bytes(32, "big") for i in range(n)]
        ws = BeaconStateMut(build_genesis_state([bls.sk_to_pk(sk) for sk in sks], spec=spec))
        plane = ResidentEpochPlane(n)
        assert plane.sync(ws, spec)  # the full upload
        lowered = aot_stats()["retraces"]
        ws.balances[3] += 5
        ws.current_epoch_participation[7] = 7
        ws.previous_epoch_participation[9] = 1
        ws.inactivity_scores[11] = 2
        assert plane.sync(ws, spec) and plane.stats["scatter_elems"] == 3
        assert np.asarray(plane.scores)[11] == 2
        assert aot_stats()["retraces"] == lowered
