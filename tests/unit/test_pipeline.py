"""Priority ingest scheduler: lanes, coalescing, shedding, gossip wiring.

Covers the ISSUE 3 tentpole (pipeline/{lanes,policy,scheduler}.py) and
the gossip-layer satellites: the queue-full drop path must COUNT
(``gossip_shed_count``), shutdown must not hang on a wedged sidecar's
``unsubscribe``, and a mixed block/attestation burst must flush blocks
first.
"""

import asyncio
import contextlib
import time

import pytest

from lambda_ethereum_consensus_tpu.compression.snappy import compress
from lambda_ethereum_consensus_tpu.network import gossip as gossip_mod
from lambda_ethereum_consensus_tpu.network.gossip import TopicSubscription
from lambda_ethereum_consensus_tpu.network.port import VERDICT_ACCEPT, VERDICT_IGNORE
from lambda_ethereum_consensus_tpu.ops.aot import register_shape_bucket, shape_buckets
from lambda_ethereum_consensus_tpu.pipeline import (
    DegradedSignal,
    IngestScheduler,
    Lane,
    LaneConfig,
    choose_shed_victim,
    snap_batch,
)
from lambda_ethereum_consensus_tpu.telemetry import Metrics, get_metrics


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=60))


@pytest.fixture(autouse=True)
def _enabled_default_registry():
    """Shed/error counters land on the process default registry — force
    it on so a TELEMETRY_OFF environment can't null the assertions."""
    m = get_metrics()
    was = m.enabled
    m.set_enabled(True)
    yield
    m.set_enabled(was)


# ------------------------------------------------------------------- policy


def test_snap_batch_rounds_down_to_largest_bucket():
    assert snap_batch(5000, (1024, 4096)) == 4096
    assert snap_batch(4096, (1024, 4096)) == 4096
    assert snap_batch(1500, (1024, 4096)) == 1024


def test_snap_batch_passes_through_when_no_bucket_fits():
    # a deadline flush smaller than every warmed shape must still drain
    assert snap_batch(5, (1024, 4096)) == 5
    assert snap_batch(7, ()) == 7


def test_shape_bucket_registry():
    register_shape_bucket("t_registry", 4096)
    register_shape_bucket("t_registry", 1024)
    register_shape_bucket("t_registry", 1024)  # idempotent
    assert shape_buckets("t_registry") == (1024, 4096)
    assert shape_buckets("t_registry_unknown") == ()
    with pytest.raises(ValueError):
        register_shape_bucket("t_registry", 0)


def _lanes(*specs):
    """[(name, priority, n_items)] -> priority-ascending Lane list."""
    lanes = []
    for name, priority, n in specs:
        lane = Lane(LaneConfig(name=name, priority=priority))
        for i in range(n):
            lane.push(0.0, i, None)
        lanes.append(lane)
    return sorted(lanes, key=lambda l: l.config.priority)


def test_shed_victim_is_lowest_priority_backlogged_lane():
    lanes = _lanes(("block", 0, 2), ("aggregate", 1, 3), ("subnet", 2, 5))
    incoming_block = lanes[0]
    assert choose_shed_victim(lanes, incoming_block).config.name == "subnet"


def test_shed_victim_never_outranks_the_incoming_item():
    # only a block is queued; an incoming subnet vote must not evict it
    lanes = _lanes(("block", 0, 1), ("aggregate", 1, 0), ("subnet", 2, 0))
    incoming_subnet = lanes[2]
    assert choose_shed_victim(lanes, incoming_subnet) is None


def test_shed_victim_can_be_own_lane():
    lanes = _lanes(("block", 0, 0), ("subnet", 2, 4))
    incoming_subnet = lanes[1]
    assert choose_shed_victim(lanes, incoming_subnet).config.name == "subnet"


def test_degraded_signal_window():
    d = DegradedSignal(window_s=1.0)
    assert not d.active(10.0)
    d.mark(10.0)
    assert d.active(10.5)
    assert d.remaining(10.5) == pytest.approx(0.5)
    assert not d.active(11.5)
    assert d.remaining(11.5) is None


# ------------------------------------------------------------------- lanes


def test_lane_ready_triggers():
    lane = Lane(LaneConfig(name="l", priority=0, coalesce_target=3, deadline_s=0.5))
    assert not lane.ready(0.0)
    lane.push(0.0, "a", None)
    assert not lane.ready(0.1)  # below target, deadline not reached
    assert lane.ready(0.6)  # oldest item past its deadline
    lane.push(0.1, "b", None)
    lane.push(0.2, "c", None)
    assert lane.ready(0.25)  # coalesce target reached


# -------------------------------------------------------------- test doubles


class Recorder:
    """A lane source that records its flushes and sheds."""

    def __init__(self, delay_s: float = 0.0, fail: bool = False):
        self.batches: list[list] = []
        self.shed_items: list = []
        self.delay_s = delay_s
        self.fail = fail

    async def process(self, items):
        if self.fail:
            raise RuntimeError("boom")
        self.batches.append(list(items))
        if self.delay_s:
            await asyncio.sleep(self.delay_s)

    async def shed(self, item, reason: str = "overload"):
        self.shed_items.append((item, reason))


async def _drain_until(predicate, timeout=10.0):
    t0 = time.monotonic()
    while not predicate():
        if time.monotonic() - t0 > timeout:
            raise AssertionError("condition not reached in time")
        await asyncio.sleep(0.005)


# ---------------------------------------------------------------- admission


def test_lane_full_sheds_oldest_from_same_lane():
    sched = IngestScheduler(metrics=Metrics(enabled=True))
    sched.add_lane(LaneConfig(name="subnet", priority=2, max_queue=3))
    src = Recorder()
    before = get_metrics().get("ingest_shed_count", lane="subnet", reason="lane_full")
    for i in range(3):
        assert sched.submit("subnet", i, src) == []
    shed = sched.submit("subnet", 3, src)
    assert shed == [(src, 0, "lane_full")]  # the OLDEST item, not the newest
    assert sched.depth == 3
    after = get_metrics().get("ingest_shed_count", lane="subnet", reason="lane_full")
    assert after == before + 1
    assert sched.degraded.active(time.monotonic())


def test_global_budget_sheds_lowest_priority_lane_first():
    sched = IngestScheduler(metrics=Metrics(enabled=True), max_items=4)
    sched.add_lane(LaneConfig(name="block", priority=0, max_queue=100))
    sched.add_lane(LaneConfig(name="subnet", priority=2, max_queue=100))
    blocks, votes = Recorder(), Recorder()
    for i in range(4):
        assert sched.submit("subnet", f"v{i}", votes) == []
    # budget exhausted: admitting a block evicts the oldest subnet vote
    shed = sched.submit("block", "b0", blocks)
    assert shed == [(votes, "v0", "overload")]
    assert len(sched.lanes["block"]) == 1
    assert len(sched.lanes["subnet"]) == 3


def test_block_lane_full_drops_incoming_not_ancestor():
    """shed_newest lanes (blocks chain parent-first): a full lane keeps
    its processable prefix and drops the INCOMING item — the old
    queue-full behavior — instead of evicting a queued ancestor."""
    sched = IngestScheduler(metrics=Metrics(enabled=True))
    sched.add_lane(LaneConfig(
        name="block", priority=0, max_queue=2, shed_newest=True,
    ))
    src = Recorder()
    assert sched.submit("block", "b0", src) == []
    assert sched.submit("block", "b1", src) == []
    shed = sched.submit("block", "b2", src)
    assert shed == [(src, "b2", "lane_full")]  # incoming, not b0
    assert [e[1] for e in sched.lanes["block"]._items] == ["b0", "b1"]


def test_overload_drops_incoming_when_all_backlog_outranks_it():
    sched = IngestScheduler(metrics=Metrics(enabled=True), max_items=2)
    sched.add_lane(LaneConfig(name="block", priority=0, max_queue=100))
    sched.add_lane(LaneConfig(name="subnet", priority=2, max_queue=100))
    blocks, votes = Recorder(), Recorder()
    sched.submit("block", "b0", blocks)
    sched.submit("block", "b1", blocks)
    # every queued item is a block: the subnet vote itself is the shed
    shed = sched.submit("subnet", "v0", votes)
    assert shed == [(votes, "v0", "overload")]
    assert len(sched.lanes["block"]) == 2


def test_admission_counts_inflight_items():
    """Items dequeued into a running flush still occupy memory: the
    global budget must see them, or a flood over-admits by a whole
    round's worth of batches while the first flush is in flight."""

    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True), max_items=2)
        sched.add_lane(LaneConfig(name="l", priority=0, max_queue=10, deadline_s=0.01))
        release = asyncio.Event()
        started = asyncio.Event()

        class Held(Recorder):
            async def process(self, items):
                started.set()
                await release.wait()  # hold the batch in flight
                await super().process(items)

        src = Held()
        sched.submit("l", "a", src)
        sched.submit("l", "b", src)
        sched.start()
        try:
            await asyncio.wait_for(started.wait(), 5)
            # queues drained into the flush; a naive budget would admit
            assert sched.depth == 0
            shed = sched.submit("l", "c", src)
            assert shed == [(src, "c", "overload")]  # in-flight counted
            release.set()
            await _drain_until(lambda: sum(len(b) for b in src.batches) == 2)
            # flush done: the ledger released, admission opens again
            assert sched.submit("l", "d", src) == []
        finally:
            release.set()
            await sched.stop()

    run(main())


# ------------------------------------------------------------------ service


def test_deadline_coalescing_builds_one_batch():
    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(
            name="agg", priority=1, coalesce_target=100, max_batch=256,
            deadline_s=0.15,
        ))
        src = Recorder()
        sched.start()
        try:
            for i in range(5):
                sched.submit("agg", i, src)
            await asyncio.sleep(0.05)
            assert src.batches == []  # below target, deadline not expired
            await _drain_until(lambda: src.batches)
            assert src.batches == [[0, 1, 2, 3, 4]]  # ONE coalesced flush
        finally:
            await sched.stop()

    run(main())


def test_coalesce_target_flushes_eagerly():
    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(
            name="agg", priority=1, coalesce_target=4, max_batch=256,
            deadline_s=5.0,
        ))
        src = Recorder()
        sched.start()
        try:
            t0 = time.monotonic()
            for i in range(4):
                sched.submit("agg", i, src)
            await _drain_until(lambda: src.batches)
            # flushed on depth, far before the 5 s deadline
            assert time.monotonic() - t0 < 2.0
            assert src.batches == [[0, 1, 2, 3]]
        finally:
            await sched.stop()

    run(main())


def test_blocks_flush_before_backlogged_attestations():
    """Mixed burst: the subnet flood arrives FIRST, yet the block lane is
    served first every round — drain flush ordering under load."""

    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(
            name="block", priority=0, weight=64, max_batch=64, deadline_s=0.02,
        ))
        sched.add_lane(LaneConfig(
            name="subnet", priority=2, weight=64, max_batch=64,
            max_queue=4096, deadline_s=0.02,
        ))
        order: list[str] = []

        class Tagged(Recorder):
            def __init__(self, tag):
                super().__init__()
                self.tag = tag

            async def process(self, items):
                order.append(self.tag)
                await super().process(items)

        votes, blocks = Tagged("subnet"), Tagged("block")
        for i in range(1000):
            sched.submit("subnet", i, votes)
        for i in range(3):
            sched.submit("block", f"b{i}", blocks)
        sched.start()
        try:
            await _drain_until(lambda: blocks.batches and len(order) >= 5)
        finally:
            await sched.stop()
        assert order[0] == "block"  # blocks preempt the earlier-arrived flood
        assert [m for b in blocks.batches for m in b] == ["b0", "b1", "b2"]

    run(main())


def test_block_preempts_mid_round_between_flushes():
    """Head-of-line guard: a block arriving while a lower-priority
    flush is in flight waits ONE flush, not the rest of the round."""

    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(name="block", priority=0, deadline_s=0.01))
        sched.add_lane(LaneConfig(
            name="att1", priority=2, max_batch=64, deadline_s=0.01,
        ))
        sched.add_lane(LaneConfig(
            name="att2", priority=3, max_batch=64, deadline_s=0.01,
        ))
        order: list[str] = []
        injected = asyncio.Event()

        class Slow(Recorder):
            def __init__(self, tag, inject_block=None):
                super().__init__()
                self.tag = tag
                self.inject_block = inject_block

            async def process(self, items):
                order.append(self.tag)
                if self.inject_block is not None and not injected.is_set():
                    # a block lands while THIS flush is in flight
                    injected.set()
                    sched.submit("block", "b0", self.inject_block)
                await asyncio.sleep(0.05)

        blocks = Recorder()
        a1 = Slow("att1", inject_block=blocks)
        a2 = Slow("att2")
        for i in range(10):
            sched.submit("att1", i, a1)
            sched.submit("att2", i, a2)
        sched.start()

        # the block source records its position in `order`
        async def block_process(items):
            order.append("block")
        blocks.process = block_process
        try:
            await _drain_until(lambda: "block" in order and len(order) >= 3)
        finally:
            await sched.stop()
        # the round was planned as [att1, att2]; the block injected
        # during att1's flush is served BEFORE att2's planned flush
        assert order[:3] == ["att1", "block", "att2"], order

    run(main())


def test_drr_deficit_bounds_per_round_service():
    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(
            name="l", priority=0, weight=2, max_batch=10, deadline_s=0.01,
        ))
        src = Recorder()
        for i in range(10):
            sched.submit("l", i, src)
        sched.start()
        try:
            await _drain_until(
                lambda: sum(len(b) for b in src.batches) == 10
            )
        finally:
            await sched.stop()
        # weight=2 items/round: no single flush may exceed the deficit
        assert max(len(b) for b in src.batches) <= 2

    run(main())


def test_flush_snaps_to_warmed_shape_buckets():
    register_shape_bucket("t_snap_flush", 4)

    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(
            name="agg", priority=1, weight=16, max_batch=16,
            coalesce_target=6, deadline_s=0.05, shape_kind="t_snap_flush",
        ))
        src = Recorder()
        for i in range(6):
            sched.submit("agg", i, src)
        sched.start()
        try:
            await _drain_until(lambda: sum(len(b) for b in src.batches) == 6)
        finally:
            await sched.stop()
        # 6 queued -> snapped to the warmed 4; remainder drains on deadline
        assert [len(b) for b in src.batches] == [4, 2]

    run(main())


def test_flush_error_contained_and_counted():
    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(name="l", priority=0, deadline_s=0.01))
        bad, good = Recorder(fail=True), Recorder()
        before = get_metrics().get("ingest_flush_error_count", lane="l")
        sched.submit("l", "x", bad)
        sched.start()
        try:
            await _drain_until(
                lambda: get_metrics().get("ingest_flush_error_count", lane="l")
                == before + 1
            )
            # the scheduler survived: later flushes still run
            sched.submit("l", "y", good)
            await _drain_until(lambda: good.batches)
        finally:
            await sched.stop()
        assert good.batches == [["y"]]

    run(main())


def test_drain_loop_crash_is_supervised():
    """An exception escaping the one drain task must not silently end
    all gossip processing: it is logged, counted, and restarted."""

    async def main():
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(name="l", priority=0, deadline_s=0.01))
        src = Recorder()
        real_run = sched._run
        state = {"crashes": 0}

        async def crashing_run():
            if state["crashes"] == 0:
                state["crashes"] += 1
                raise RuntimeError("boom")
            await real_run()

        sched._run = crashing_run
        sched._inflight = 7  # a crashed round's abandoned ledger
        before = get_metrics().get("ingest_loop_crash_count")
        sched.start()
        await asyncio.sleep(0.05)  # let the first run die
        assert get_metrics().get("ingest_loop_crash_count") == before + 1
        sched.submit("l", "x", src)
        try:
            # the 1 s supervisor delay, then the restarted loop drains
            await _drain_until(lambda: src.batches, timeout=5.0)
        finally:
            await sched.stop()
        assert src.batches == [["x"]]
        # the restarted loop zeroed the leaked ledger: admission is not
        # permanently narrowed by the crash
        assert sched._inflight == 0

    run(main())


def test_degraded_gauge_sets_and_clears():
    async def main():
        node_metrics = Metrics(enabled=True)
        sched = IngestScheduler(metrics=node_metrics, degraded_window_s=0.2)
        sched.add_lane(LaneConfig(name="l", priority=0, max_queue=1, deadline_s=0.01))
        src = Recorder()
        sched.start()
        try:
            sched.submit("l", "a", src)
            sched.submit("l", "b", src)  # lane full -> shed -> latch
            assert node_metrics.get("ingest_degraded") == 1.0
            await _drain_until(
                lambda: node_metrics.get("ingest_degraded") == 0.0, timeout=5.0
            )
        finally:
            await sched.stop()

    run(main())


# ----------------------------------------------------------- gossip wiring


class FakePort:
    """Port double: records subscriptions and verdicts."""

    def __init__(self, wedge_unsubscribe: bool = False):
        self.verdicts: list[tuple[bytes, int]] = []
        self.subscribed: list[str] = []
        self.unsubscribed: list[str] = []
        self.wedge_unsubscribe = wedge_unsubscribe

    async def subscribe(self, topic, handler):
        self.subscribed.append(topic)

    async def unsubscribe(self, topic):
        if self.wedge_unsubscribe:
            await asyncio.sleep(3600)
        self.unsubscribed.append(topic)

    async def validate_message(self, msg_id, verdict):
        self.verdicts.append((msg_id, verdict))

    @contextlib.asynccontextmanager
    async def verdict_batch(self):
        yield  # this double books a verdict as it is handed over


def test_gossip_queue_full_drop_is_counted():
    """Satellite: the standalone queue-full IGNORE path must emit
    gossip_shed_count{topic,reason=queue_full} — it was silent."""

    async def main():
        port = FakePort()

        async def handler(batch):
            return [VERDICT_ACCEPT] * len(batch)

        sub = TopicSubscription(
            port, "/eth2/t1/full_drop_topic/ssz_snappy", handler, max_queue=2
        )
        # no start(): the drain loop must not race the queue-full setup
        before = get_metrics().get(
            "gossip_shed_count", topic="full_drop_topic", reason="queue_full"
        )
        for i in range(3):
            await sub._on_gossip("t", b"id%d" % i, b"payload", b"peer")
        after = get_metrics().get(
            "gossip_shed_count", topic="full_drop_topic", reason="queue_full"
        )
        assert after == before + 1
        assert port.verdicts == [(b"id2", VERDICT_IGNORE)]

    run(main())


def test_stop_bounded_on_wedged_unsubscribe(monkeypatch):
    """Satellite: a wedged sidecar's unsubscribe cannot hang shutdown."""
    monkeypatch.setattr(gossip_mod, "UNSUBSCRIBE_TIMEOUT_S", 0.2)

    async def main():
        port = FakePort(wedge_unsubscribe=True)

        async def handler(batch):
            return []

        sub = TopicSubscription(port, "/eth2/t1/wedged_topic/ssz_snappy", handler)
        await sub.start()
        t0 = time.monotonic()
        await sub.stop()
        assert time.monotonic() - t0 < 2.0

    run(main())


def test_scheduler_mode_end_to_end_mixed_burst():
    """Block + two subnet topics through the scheduler: flush ordering
    favors the block, every message gets a verdict, sheds IGNORE."""

    async def main():
        port = FakePort()
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(
            name="block", priority=0, max_batch=64, deadline_s=0.02,
        ))
        sched.add_lane(LaneConfig(
            name="subnet", priority=2, max_batch=64, max_queue=256,
            deadline_s=0.02,
        ))
        handled: list[tuple[str, int]] = []

        def make_handler(tag):
            async def handler(batch):
                handled.append((tag, len(batch)))
                return [VERDICT_ACCEPT] * len(batch)

            return handler

        block_sub = TopicSubscription(
            port, "/eth2/t1/e2e_block/ssz_snappy", make_handler("block"),
            scheduler=sched, lane="block",
        )
        sub0 = TopicSubscription(
            port, "/eth2/t1/e2e_att_0/ssz_snappy", make_handler("att0"),
            scheduler=sched, lane="subnet",
        )
        sub1 = TopicSubscription(
            port, "/eth2/t1/e2e_att_1/ssz_snappy", make_handler("att1"),
            scheduler=sched, lane="subnet",
        )
        for s in (block_sub, sub0, sub1):
            await s.start()
        assert all(s._task is None for s in (block_sub, sub0, sub1))

        payload = compress(b"x" * 32)
        # the attestation flood lands BEFORE the block
        for i in range(40):
            await sub0._on_gossip("t", b"a0-%d" % i, payload, b"p")
            await sub1._on_gossip("t", b"a1-%d" % i, payload, b"p")
        await block_sub._on_gossip("t", b"blk-0", payload, b"p")
        sched.start()
        try:
            await _drain_until(lambda: len(port.verdicts) == 81)
        finally:
            await sched.stop()
        assert handled[0][0] == "block"  # priority beats arrival order
        # each subnet topic's items flushed as ITS handler's batches
        assert sum(n for tag, n in handled if tag == "att0") == 40
        assert sum(n for tag, n in handled if tag == "att1") == 40
        assert all(v == VERDICT_ACCEPT for _, v in port.verdicts)

    run(main())


def test_scheduler_mode_shed_sends_ignore():
    async def main():
        port = FakePort()
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(name="subnet", priority=2, max_queue=2))

        async def handler(batch):
            return [VERDICT_ACCEPT] * len(batch)

        sub = TopicSubscription(
            port, "/eth2/t1/e2e_shed/ssz_snappy", handler,
            scheduler=sched, lane="subnet",
        )
        await sub.start()
        before = get_metrics().get(
            "gossip_shed_count", topic="e2e_shed", reason="lane_full"
        )
        for i in range(3):
            await sub._on_gossip("t", b"m%d" % i, b"raw", b"p")
        # the OLDEST message was evicted and IGNOREd at admission time,
        # counted under the scheduler's own reason (lane_full here)
        assert port.verdicts == [(b"m0", VERDICT_IGNORE)]
        after = get_metrics().get(
            "gossip_shed_count", topic="e2e_shed", reason="lane_full"
        )
        assert after == before + 1

    run(main())


def test_shared_sink_coalesces_topics_into_one_flush():
    """The subnet-lane shape: N topics share one SharedLaneSink, so a
    lane flush is ONE handler call across topics (one device verify),
    with verdicts routed back per message."""
    from lambda_ethereum_consensus_tpu.network.gossip import SharedLaneSink

    async def main():
        port = FakePort()
        sched = IngestScheduler(metrics=Metrics(enabled=True))
        sched.add_lane(LaneConfig(
            name="subnet", priority=2, max_batch=64, max_queue=256,
            deadline_s=0.02, coalesce_target=64,
        ))
        calls: list[list] = []

        async def handler(pairs):  # [(subscription, GossipMessage)]
            calls.append([(sub.subnet_id, msg.msg_id) for sub, msg in pairs])
            return [VERDICT_ACCEPT] * len(pairs)

        sink = SharedLaneSink(handler, label="subnet_lane")

        async def unused(batch):
            raise AssertionError("per-topic handler must not run in sink mode")

        subs = []
        for i in range(4):
            s = TopicSubscription(
                port, f"/eth2/t1/sink_att_{i}/ssz_snappy", unused,
                scheduler=sched, lane="subnet", sink=sink,
            )
            s.subnet_id = i
            await s.start()
            subs.append(s)
        payload = compress(b"vote" * 8)
        n = 0
        for i, s in enumerate(subs):
            for j in range(5):
                await s._on_gossip("t", b"%d-%d" % (i, j), payload, b"p")
                n += 1
        sched.start()
        try:
            await _drain_until(lambda: len(port.verdicts) == n)
        finally:
            await sched.stop()
        # ONE handler call carried all 4 topics' 20 messages
        assert len(calls) == 1 and len(calls[0]) == 20
        assert {sid for sid, _ in calls[0]} == {0, 1, 2, 3}
        assert all(v == VERDICT_ACCEPT for _, v in port.verdicts)

    run(main())
