"""Causal tracing: slot-phase delay math, the flight-recorder ring,
per-item trace threading through the ingest pipeline, and the batched
verify fan-in links (ISSUE 4 tentpole + satellites)."""

import asyncio
import contextlib
import json
import time

import pytest

from lambda_ethereum_consensus_tpu import tracing
from lambda_ethereum_consensus_tpu.compression.snappy import compress
from lambda_ethereum_consensus_tpu.network.gossip import TopicSubscription
from lambda_ethereum_consensus_tpu.network.port import VERDICT_ACCEPT, VERDICT_IGNORE
from lambda_ethereum_consensus_tpu.pipeline import IngestScheduler, LaneConfig
from lambda_ethereum_consensus_tpu.telemetry import Metrics, get_metrics
from lambda_ethereum_consensus_tpu.tracing import (
    SLOT_PHASE_BUCKETS,
    FlightRecorder,
    SlotClock,
    get_recorder,
    new_trace,
    record_verify_batch,
)


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=60))


@pytest.fixture(autouse=True)
def _fresh_enabled_recorder():
    """Force the shared recorder/registry on and start from an empty
    ring — a TELEMETRY_OFF environment (or a prior test's events) must
    not null the assertions."""
    rec = get_recorder()
    m = get_metrics()
    was_rec, was_m = rec.enabled, m.enabled
    rec.set_enabled(True)
    m.set_enabled(True)
    rec.clear()
    yield
    rec.set_enabled(was_rec)
    m.set_enabled(was_m)


def _events(name=None, kind=None):
    evs = get_recorder().snapshot()
    if name is not None:
        evs = [e for e in evs if e["name"] == name]
    if kind is not None:
        evs = [e for e in evs if e["kind"] == kind]
    return evs


# ------------------------------------------------------------ slot clock


@pytest.mark.parametrize("sps", [12, 6])  # mainnet / minimal presets
def test_slot_clock_boundaries(sps):
    clock = SlotClock(genesis_time=1000, seconds_per_slot=sps)
    # exact slot boundary: offset 0.0 of the NEW slot
    assert clock.slot_at(1000) == 0
    assert clock.slot_at(1000 + sps) == 1
    assert clock.offset_into_slot(1000 + sps) == 0.0
    # one tick before the boundary still belongs to the old slot
    assert clock.slot_at(1000 + sps - 0.001) == 0
    assert clock.offset_into_slot(1000 + sps - 0.001) == pytest.approx(
        sps - 0.001
    )
    assert clock.slot_start(3) == 1000 + 3 * sps


@pytest.mark.parametrize("sps", [12, 6])
def test_slot_clock_pre_genesis(sps):
    clock = SlotClock(genesis_time=1000, seconds_per_slot=sps)
    assert clock.slot_at(999.5) == -1
    assert clock.slot_at(1000 - sps) == -1
    assert clock.slot_at(1000 - sps - 0.5) == -2
    # offset stays normalized into [0, sps) even before genesis
    off = clock.offset_into_slot(999.0)
    assert 0.0 <= off < sps
    assert clock.phase(999.0)["pre_genesis"] is True
    assert clock.phase(1000.0)["pre_genesis"] is False


@pytest.mark.parametrize("sps", [12, 6])
def test_slot_clock_intervals_per_slot(sps):
    # INTERVALS_PER_SLOT = 3 sub-phases: propose / attest / aggregate
    clock = SlotClock(genesis_time=0, seconds_per_slot=sps, intervals_per_slot=3)
    third = sps / 3
    assert clock.interval_at(0.0) == 0
    assert clock.interval_at(third - 0.01) == 0
    assert clock.interval_at(third) == 1  # boundary enters the next phase
    assert clock.interval_at(2 * third) == 2
    assert clock.interval_at(sps - 0.01) == 2  # clamped to the last phase
    assert clock.interval_at(sps) == 0  # next slot's first phase


def test_slot_clock_rejects_degenerate_config():
    with pytest.raises(ValueError):
        SlotClock(0, 0)
    with pytest.raises(ValueError):
        SlotClock(0, 12, intervals_per_slot=0)


def test_slot_phase_observe_helpers_record_histograms():
    m = get_metrics()
    clock = SlotClock(genesis_time=1000, seconds_per_slot=12)

    def count(name):
        hist = m.get_histogram(name)
        return hist[3] if hist else 0

    b0 = count("slot_block_arrival_offset_seconds")
    h0 = count("head_update_delay_seconds")
    # block for slot 2 arriving 3.5 s into it
    off = tracing.observe_block_arrival(clock, 2, now=1000 + 24 + 3.5)
    assert off == pytest.approx(3.5)
    # early arrival (clock skew) clamps to 0 instead of going negative
    assert tracing.observe_block_arrival(clock, 5, now=1000) == 0.0
    delay = tracing.observe_head_update(clock, 2, now=1000 + 24 + 4.0)
    assert delay == pytest.approx(4.0)
    assert count("slot_block_arrival_offset_seconds") == b0 + 2
    assert count("head_update_delay_seconds") == h0 + 1
    # slot-shaped buckets were pinned (not the 100us.. latency defaults)
    bounds, _, _, _ = m.get_histogram("slot_block_arrival_offset_seconds")
    assert bounds == SLOT_PHASE_BUCKETS


# -------------------------------------------------------- flight recorder


def test_recorder_ring_is_bounded():
    rec = FlightRecorder(capacity=4, enabled=True)
    for i in range(10):
        rec.record("inst", i + 1, f"e{i}")
    st = rec.stats()
    assert st["capacity"] == 4
    assert st["events"] == 4
    assert st["appended_total"] == 10
    assert st["dropped_total"] == 6
    # oldest-overwrite: only the newest 4 survive
    assert [e["name"] for e in rec.snapshot()] == ["e6", "e7", "e8", "e9"]


def test_recorder_noop_mode_records_nothing():
    rec = FlightRecorder(capacity=16, enabled=False)
    rec.record("inst", 1, "x")
    assert rec.stats()["events"] == 0
    rec.set_enabled(True)
    rec.record("inst", 1, "x")
    assert rec.stats()["events"] == 1


def test_new_trace_is_none_when_disabled():
    rec = get_recorder()
    rec.set_enabled(False)
    assert new_trace("beacon_block") is None
    assert rec.stats()["events"] == 0
    rec.set_enabled(True)
    t = new_trace("beacon_block")
    assert t is not None
    # traces buffer locally and land in the ring at TERMINATION
    assert rec.stats()["events"] == 0
    t.end("done", {"verdict": "accept"})
    assert _events(kind="begin")[0]["trace_id"] == t.trace_id


def test_trace_end_is_idempotent():
    t = new_trace("topic")
    t.end("shed", {"reason": "lane_full"})
    t.end("done", {"verdict": "accept"})  # late verdict after a shed: ignored
    t.event("late")  # post-termination events are dropped too
    ends = _events(kind="end")
    assert len(ends) == 1
    assert ends[0]["args"] == {"stage": "shed", "reason": "lane_full"}
    assert not _events(name="late")


def test_recorder_clips_oversized_args():
    rec = get_recorder()
    rec.record("inst", 0, "big", {"reason": "x" * 10_000})
    (ev,) = _events(name="big")
    assert len(ev["args"]["reason"]) == tracing._MAX_ARG_CHARS
    # buffered trace events clip too (the drop-reason path)
    t = new_trace("topic")
    t.event("drop", reason="y" * 10_000)
    t.end("done", {"verdict": "ignore"})
    (drop,) = _events(name="drop")
    assert len(drop["args"]["reason"]) == tracing._MAX_ARG_CHARS


def test_trace_event_buffer_is_capped():
    t = new_trace("topic")
    for i in range(100):
        t.event(f"e{i}")
    t.end("done", {"verdict": "accept"})
    mine = [e for e in _events() if e["trace_id"] == t.trace_id]
    # begin + capped intermediates; the terminal end still lands
    assert len(mine) <= tracing._MAX_TRACE_EVENTS + 2
    assert mine[-1]["kind"] == "end"


def test_chrome_export_shape():
    t = new_trace("beacon_aggregate_and_proof")
    t.event("enqueue", lane="aggregate")
    record_verify_batch([t], [None], "cached", time.monotonic(), 0.002)
    t.end("done", {"verdict": "accept"})
    get_recorder().record("inst", 0, "drain_restart", {"error": "RuntimeError"})
    doc = get_recorder().chrome()
    payload = json.loads(json.dumps(doc))  # must round-trip as JSON
    evs = payload["traceEvents"]
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    # nestable async begin/end share cat+id; the hex id round-trips
    (b,), (e,) = by_ph["b"], by_ph["e"]
    assert b["id"] == e["id"] == format(t.trace_id, "x")
    assert b["cat"] == e["cat"] == "item"
    # the batched verify span is a complete slice with a duration
    (x,) = by_ph["X"]
    assert x["dur"] >= 1 and x["args"]["members"] == [t.trace_id]
    # trace-less events render as global instants
    assert any(e["name"] == "drain_restart" for e in by_ph["i"])
    # every non-metadata event is timestamped
    assert all("ts" in e for e in evs if e["ph"] != "M")


# --------------------------------------------------------- verify fan-in


def test_record_verify_batch_links_members_and_outcomes():
    m = get_metrics()
    before = m.get_histogram("attestation_admit_apply_seconds")
    before_n = before[3] if before else 0
    t1, t2, t3 = (new_trace(f"s{i}") for i in range(3))
    errs = [None, RuntimeError("invalid attestation signature"), None]
    bid = record_verify_batch(
        [t1, t2, t3], errs, "cached", time.monotonic() - 0.01, 0.01
    )
    for t in (t1, t2, t3):  # buffered walks land in the ring at end
        t.end("done", {"verdict": "x"})
    (span_ev,) = _events(kind="span")
    assert span_ev["trace_id"] == bid
    assert span_ev["args"]["members"] == [t1.trace_id, t2.trace_id, t3.trace_id]
    assert span_ev["args"]["path"] == "cached"
    # every member carries the reverse link; outcomes split apply/drop
    verifies = _events(name="verify")
    assert {e["trace_id"] for e in verifies} == {t.trace_id for t in (t1, t2, t3)}
    assert all(e["args"]["batch"] == bid for e in verifies)
    assert {e["trace_id"] for e in _events(name="apply")} == {
        t1.trace_id, t3.trace_id,
    }
    (drop,) = _events(name="drop")
    assert drop["trace_id"] == t2.trace_id
    assert "invalid" in drop["args"]["reason"]
    # accepted members observed the admission->apply histogram
    assert m.get_histogram("attestation_admit_apply_seconds")[3] == before_n + 2


def test_record_verify_batch_all_none_is_noop():
    assert record_verify_batch([None, None], [None, None], "host", 0.0, 0.1) is None
    assert not _events(kind="span")


# --------------------------------------- pipeline threading (end to end)


class FakePort:
    def __init__(self):
        self.verdicts = []

    async def subscribe(self, topic, handler):
        pass

    async def unsubscribe(self, topic):
        pass

    async def validate_message(self, msg_id, verdict):
        self.verdicts.append((msg_id, verdict))

    @contextlib.asynccontextmanager
    async def verdict_batch(self):
        yield  # this double books a verdict as it is handed over


def test_end_to_end_trace_admission_through_apply_with_shed():
    """The acceptance path: a flushed batch's verify span links >= 2
    member traces end to end (admit -> enqueue -> dequeue -> verify ->
    apply -> done), and the shed item's trace terminates with the shed
    reason."""

    async def main():
        port = FakePort()
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(
            name="agg", priority=1, max_queue=2, max_batch=8,
            coalesce_target=2, deadline_s=0.02,
        ))

        async def handler(batch):
            # stand-in for the node's _attestation_drain -> fork_choice
            # on_attestation_batch(traces=...) fan-in
            record_verify_batch(
                [m.trace for m in batch], [None] * len(batch),
                "cached", time.monotonic() - 0.001, 0.001,
            )
            return [VERDICT_ACCEPT] * len(batch)

        sub = TopicSubscription(
            port, "/eth2/t1/e2e_trace/ssz_snappy", handler,
            scheduler=sched, lane="agg",
        )
        await sub.start()
        payload = compress(b"vote" * 8)
        for i in range(3):  # lane holds 2: the oldest is evicted
            await sub._on_gossip("t", b"m%d" % i, payload, b"p")
        sched.start()
        try:
            await asyncio.sleep(0)
            t0 = time.monotonic()
            while len(port.verdicts) < 3 and time.monotonic() - t0 < 10:
                await asyncio.sleep(0.01)
        finally:
            await sched.stop()
        assert len(port.verdicts) == 3

    run(main())
    evs = get_recorder().snapshot()
    ends = {e["trace_id"]: e for e in evs if e["kind"] == "end"}
    assert len(ends) == 3
    shed_ends = [e for e in ends.values() if e["args"]["stage"] == "shed"]
    done_ends = [e for e in ends.values() if e["args"]["stage"] == "done"]
    assert len(shed_ends) == 1 and len(done_ends) == 2
    assert shed_ends[0]["args"]["reason"] == "lane_full"
    assert all(e["args"]["verdict"] == "accept" for e in done_ends)
    # ONE verify span fans in to BOTH surviving member traces
    (span_ev,) = [e for e in evs if e["kind"] == "span"]
    survivors = {e["trace_id"] for e in done_ends}
    assert set(span_ev["args"]["members"]) == survivors
    # each survivor walked the full stage sequence, in timestamp order
    for tid in survivors:
        stages = [
            e["name"] for e in evs
            if e["trace_id"] == tid and e["kind"] in ("begin", "inst")
        ]
        assert stages[0] == "e2e_trace"  # admit (begin carries the label)
        assert stages[1:] == ["enqueue", "dequeue", "verify", "apply"]
        ts = [e["ts_us"] for e in evs if e["trace_id"] == tid]
        assert ts == sorted(ts)


def test_degraded_transitions_counter_counts_flips_not_sheds():
    async def main():
        m = get_metrics()
        before = m.get("ingest_degraded_transitions_total", edge="enter")
        sched = IngestScheduler(
            metrics=Metrics(enabled=True), degraded_window_s=60.0
        )
        sched.add_lane(LaneConfig(name="l", priority=0, max_queue=1))

        class Null:
            async def process(self, items): ...
            async def shed(self, item, reason="overload"): ...

        src = Null()
        sched.submit("l", "a", src)
        sched.submit("l", "b", src)  # shed -> latch flips on
        sched.submit("l", "c", src)  # shed again -> still latched
        assert m.get("ingest_degraded_transitions_total", edge="enter") == before + 1

    run(main())
    # the flip landed on the flight recorder too
    flips = _events(name="ingest_degraded")
    assert len(flips) == 1 and flips[0]["args"]["reason"] == "lane_full"


def test_drain_restart_counted_and_recorded():
    m = get_metrics()
    before = m.get("pipeline_drain_restarts_total")
    sched = IngestScheduler(metrics=Metrics(enabled=True))

    class FakeTask:
        def __init__(self, exc):
            self._exc = exc
            self.delayed = []

        def cancelled(self):
            return False

        def exception(self):
            return self._exc

        def get_loop(self):
            return self

        def call_later(self, delay, cb):
            self.delayed.append((delay, cb))

    task = FakeTask(RuntimeError("boom"))
    sched._on_task_done(task)
    assert m.get("pipeline_drain_restarts_total") == before + 1
    assert task.delayed and task.delayed[0][0] == 1.0  # restart armed
    (ev,) = _events(name="drain_restart")
    assert ev["args"] == {"error": "RuntimeError", "message": "boom"}


# ----------------------------------------------------------- API surface


def test_debug_trace_route_serves_perfetto_json():
    from lambda_ethereum_consensus_tpu.api.beacon_api import BeaconApiServer

    t = new_trace("beacon_block")
    t.end("done", {"verdict": "accept"})
    server = BeaconApiServer(store=None, spec=None)
    status, ctype, body = server._route("GET", "/debug/trace")
    assert status == "200 OK" and ctype == "application/json"
    doc = json.loads(body)
    assert any(
        e.get("ph") == "b" and e.get("id") == format(t.trace_id, "x")
        for e in doc["traceEvents"]
    )


def test_debug_lanes_route_snapshot():
    from lambda_ethereum_consensus_tpu.api.beacon_api import BeaconApiServer

    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True), max_items=100)
        sched.add_lane(LaneConfig(name="block", priority=0, max_queue=8))
        sched.add_lane(LaneConfig(name="agg", priority=1, max_queue=16))

        class Null:
            async def process(self, items): ...
            async def shed(self, item, reason="overload"): ...

        sched.submit("agg", "x", Null())

        class NodeStub:
            ingest = sched

        server = BeaconApiServer(store=None, spec=None, node=NodeStub())
        status, _, body = server._route("GET", "/debug/lanes")
        assert status == "200 OK"
        data = json.loads(body)["data"]
        assert data["depth"] == 1 and data["max_items"] == 100
        lanes = {l["name"]: l for l in data["lanes"]}
        assert lanes["agg"]["depth"] == 1 and lanes["agg"]["capacity"] == 16
        assert lanes["block"]["depth"] == 0
        assert data["recorder"]["capacity"] >= 1

    run(main())


def test_debug_lanes_404_without_scheduler():
    from lambda_ethereum_consensus_tpu.api.beacon_api import BeaconApiServer

    status, _, _ = BeaconApiServer(store=None, spec=None)._route(
        "GET", "/debug/lanes"
    )
    assert status.startswith("404")


def test_debug_slot_route_uses_node_clock():
    from lambda_ethereum_consensus_tpu.api.beacon_api import BeaconApiServer

    class NodeStub:
        slot_clock = SlotClock(
            genesis_time=int(time.time()) - 25, seconds_per_slot=12
        )

    server = BeaconApiServer(store=None, spec=None, node=NodeStub())
    status, _, body = server._route("GET", "/debug/slot")
    assert status == "200 OK"
    data = json.loads(body)["data"]
    assert data["slot"] == 2
    assert 0.0 <= data["offset_s"] < 12.0
    assert data["pre_genesis"] is False
    assert data["interval"] in (0, 1, 2)


# --------------------------------------------- /metrics self-observability


def test_render_appends_scrape_stats():
    m = Metrics()
    m.inc("reqs", result="ok")
    text = m.render_prometheus()
    assert "# TYPE telemetry_scrape_seconds gauge" in text
    assert "# TYPE telemetry_series_count gauge" in text
    # one sample series counted, excluding the stats block itself
    assert "telemetry_series_count 1" in text
    # disabled registries keep the empty-exposition no-op contract
    assert Metrics(enabled=False).render_prometheus().strip() == ""


def test_merged_metrics_route_has_single_scrape_stats_block():
    from lambda_ethereum_consensus_tpu.api.beacon_api import BeaconApiServer

    node_m = Metrics()
    node_m.set_gauge("sync_store_slot", 9)
    server = BeaconApiServer(store=None, spec=None, metrics=node_m)
    _, ctype, body = server._metrics()
    assert ctype == "text/plain; version=0.0.4"
    text = body.decode()
    assert text.count("# TYPE telemetry_scrape_seconds gauge") == 1
    assert text.count("# TYPE telemetry_series_count gauge") == 1
    type_lines = [l for l in text.splitlines() if l.startswith("# TYPE ")]
    assert len(type_lines) == len(set(type_lines))
