"""Chaos subsystem unit pins (round 19, ISSUE 14).

The acceptance-critical one is reproducibility: the same seed MUST
reproduce the same fault schedule bit for bit, independent of how
asyncio interleaves the links — otherwise a red soak run cannot be
replayed for diagnosis.  The rest pins the ChaosPort fault semantics
(drop/dup/reorder/delay/partition, all observable in counters) and the
degraded-latch edge accounting the storm scenario asserts.
"""

import asyncio
import contextlib

import pytest

from lambda_ethereum_consensus_tpu.chaos.faults import (
    FaultDecision,
    FaultScheduler,
    FaultSpec,
)
from lambda_ethereum_consensus_tpu.chaos.inject import ChaosPort
from lambda_ethereum_consensus_tpu.network.port import VERDICT_IGNORE, PortError
from lambda_ethereum_consensus_tpu.pipeline import IngestScheduler, LaneConfig
from lambda_ethereum_consensus_tpu.telemetry import Metrics, get_metrics


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=30))


# ------------------------------------------------------------- scheduler

SPEC = FaultSpec(drop=0.2, dup=0.15, reorder=0.1, delay_s=0.001, jitter_s=0.002)


def test_same_seed_reproduces_schedule_bit_for_bit():
    """The ISSUE-14 acceptance pin."""
    a = FaultScheduler(1234, SPEC)
    b = FaultScheduler(1234, SPEC)
    assert a.schedule("n0<-n1", 500) == b.schedule("n0<-n1", 500)
    # and a different seed is a different schedule
    c = FaultScheduler(1235, SPEC)
    assert a.schedule("n0<-n1", 500) != c.schedule("n0<-n1", 500)


def test_links_are_independent_of_interleaving():
    """Message n on link X gets the same verdict regardless of what other
    links consumed in between — asyncio ordering cannot desync a replay."""
    solo = FaultScheduler(7, SPEC)
    expected = solo.schedule("a->b", 50)
    mixed = FaultScheduler(7, SPEC)
    got = []
    for i in range(50):
        # interleave draws on other links between every a->b decision
        mixed.decide("b->a")
        if i % 3 == 0:
            mixed.decide("c->a")
        got.append(mixed.decide("a->b"))
    assert got == expected


def test_inert_spec_never_faults_and_skips_draws():
    sched = FaultScheduler(42, FaultSpec())
    assert sched.schedule("x", 100) == [
        FaultDecision(False, False, False, 0.0)
    ] * 100


def test_fault_spec_validates_parameters():
    with pytest.raises(ValueError):
        FaultSpec(drop=1.5)
    with pytest.raises(ValueError):
        FaultSpec(delay_s=-0.1)


def test_fault_rates_approach_probabilities():
    sched = FaultScheduler(99, FaultSpec(drop=0.3))
    n = 2000
    drops = sum(1 for d in sched.schedule("l", n) if d.drop)
    assert 0.25 < drops / n < 0.35


# ------------------------------------------------------------- chaos port

class _FakePort:
    """The Port surface ChaosPort wraps, with full call capture."""

    def __init__(self):
        self.handlers = {}
        self.verdicts = []
        self.published = []
        self.requests = []
        self.on_new_peer = None
        self.on_peer_gone = None
        self.on_exit = None

    async def subscribe(self, topic, handler):
        self.handlers[topic] = handler

    async def validate_message(self, msg_id, verdict):
        self.verdicts.append((msg_id, verdict))

    @contextlib.asynccontextmanager
    async def verdict_batch(self):
        yield  # this double books a verdict as it is handed over

    async def publish(self, topic, payload):
        self.published.append((topic, payload))

    async def send_request(self, peer_id, protocol_id, payload, timeout_ms=0):
        self.requests.append((peer_id, protocol_id))
        return b"resp"

    async def set_request_handler(self, protocol_id, handler):
        self.handlers[protocol_id] = handler


def _chaos_pair(spec: FaultSpec, seed=0):
    fake = _FakePort()
    chaos = ChaosPort(fake, FaultScheduler(seed, spec), name="n0")
    return fake, chaos


def _first_faulting(spec_kind: str, seed=0, spec=None) -> int:
    """Index of the first message the seeded stream faults with KIND on
    the inbound link — so the tests assert exact behavior, not luck."""
    probe = FaultScheduler(seed, spec)
    for i in range(10_000):
        decision = probe.decide("n0<-peer")
        if getattr(decision, spec_kind):
            return i
    raise AssertionError(f"seed never produced a {spec_kind}")


def test_chaos_port_drop_ignores_and_counts():
    spec = FaultSpec(drop=0.3)
    target = _first_faulting("drop", spec=spec)

    async def main():
        fake, chaos = _chaos_pair(spec)
        got = []

        async def handler(topic, msg_id, payload, peer_id):
            got.append(msg_id)

        await chaos.subscribe("t", handler)
        wrapped = fake.handlers["t"]
        for i in range(target + 1):
            await wrapped("t", b"m%d" % i, b"x", b"peer")
        assert b"m%d" % target not in got  # the scheduled drop
        assert len(got) == target  # everything before it delivered
        # the dropped id got an IGNORE verdict (not a score-bearing REJECT)
        assert (b"m%d" % target, VERDICT_IGNORE) in fake.verdicts
        assert chaos.fault_counts["drop"] == 1

    run(main())


def test_chaos_port_dup_delivers_twice():
    spec = FaultSpec(dup=0.3)
    target = _first_faulting("dup", spec=spec)

    async def main():
        fake, chaos = _chaos_pair(spec)
        got = []

        async def handler(topic, msg_id, payload, peer_id):
            got.append(msg_id)

        await chaos.subscribe("t", handler)
        wrapped = fake.handlers["t"]
        for i in range(target + 1):
            await wrapped("t", b"m%d" % i, b"x", b"peer")
        assert got.count(b"m%d" % target) == 2
        assert chaos.fault_counts["dup"] == 1

    run(main())


def test_chaos_port_reorder_holds_one_message():
    spec = FaultSpec(reorder=0.9)

    async def main():
        fake, chaos = _chaos_pair(spec)
        got = []

        async def handler(topic, msg_id, payload, peer_id):
            got.append(msg_id)

        await chaos.subscribe("t", handler)
        wrapped = fake.handlers["t"]
        await wrapped("t", b"m0", b"x", b"peer")  # held (reorder ~0.9)
        await wrapped("t", b"m1", b"x", b"peer")  # delivers, releases m0
        assert got[:2] == [b"m1", b"m0"]
        assert chaos.fault_counts["reorder"] >= 1

    run(main())


def test_chaos_port_reorder_flush_timer_releases_tail():
    """The last message of a burst must not hang in the hold slot."""
    spec = FaultSpec(reorder=0.9)

    async def main():
        fake, chaos = _chaos_pair(spec)
        got = []

        async def handler(topic, msg_id, payload, peer_id):
            got.append(msg_id)

        await chaos.subscribe("t", handler)
        await fake.handlers["t"]("t", b"tail", b"x", b"peer")
        assert got == []  # held
        for _ in range(100):
            if got:
                break
            await asyncio.sleep(0.01)
        assert got == [b"tail"]  # force-flushed

    run(main())


def test_chaos_port_partition_blocks_both_planes():
    async def main():
        fake, chaos = _chaos_pair(FaultSpec())
        got = []

        async def handler(topic, msg_id, payload, peer_id):
            got.append(peer_id)

        await chaos.subscribe("t", handler)
        await chaos.set_request_handler("/proto/1", handler)
        chaos.set_partition({b"evil"})
        assert chaos.partitioned
        # inbound gossip from the blocked peer: dropped + IGNOREd
        await fake.handlers["t"]("t", b"m0", b"x", b"evil")
        assert got == []
        assert fake.verdicts[-1][0] == b"m0"
        # outbound req/resp to the blocked peer: unreachable
        with pytest.raises(PortError):
            await chaos.send_request(b"evil", "/proto/1", b"q")
        # inbound req/resp from the blocked peer: silently unanswered
        await fake.handlers["/proto/1"]("/proto/1", b"r1", b"q", b"evil")
        assert got == []
        assert chaos.fault_counts["partition_drop"] == 1
        assert chaos.fault_counts["partition_req_block"] == 2
        # heal: traffic flows again, both planes
        chaos.heal()
        await fake.handlers["t"]("t", b"m1", b"x", b"evil")
        assert await chaos.send_request(b"evil", "/proto/1", b"q") == b"resp"
        await fake.handlers["/proto/1"]("/proto/1", b"r2", b"q", b"evil")
        assert got == [b"evil", b"evil"]

    run(main())


def test_chaos_port_forwards_node_handlers_to_inner_port():
    fake, chaos = _chaos_pair(FaultSpec())
    marker = lambda *a: None  # noqa: E731
    chaos.on_new_peer = marker
    chaos.on_exit = marker
    assert fake.on_new_peer is marker  # the inner port dispatches these
    assert fake.on_exit is marker
    fake.listen_port = 1234
    assert chaos.listen_port == 1234  # __getattr__ delegation


# --------------------------------------------------------- degraded edges

class _SlowSource:
    def __init__(self, busy_s=0.05):
        self.busy_s = busy_s
        self.sheds = 0

    async def process(self, items):
        await asyncio.sleep(self.busy_s)

    async def shed(self, item, reason="overload"):
        self.sheds += 1


def test_degraded_latch_edges_exactly_once_per_storm():
    """The ISSUE-14 satellite pin: one enter and one exit increment per
    storm window — across TWO storms, so the release provably re-arms."""

    async def one_storm(sched, src, m, n=40):
        enter0 = m.get("ingest_degraded_transitions_total", edge="enter")
        exit0 = m.get("ingest_degraded_transitions_total", edge="exit")
        for i in range(n):  # flood a queue of 4: sheds flip the latch
            for shed_src, item, reason in sched.submit("l", i, src):
                await shed_src.shed(item, reason)
        assert src.sheds > 0
        # the latch holds for the window, then the drain loop observes
        # the release edge (its idle sleep is capped by the expiry)
        for _ in range(200):
            ex = m.get("ingest_degraded_transitions_total", edge="exit")
            if ex == exit0 + 1:
                break
            await asyncio.sleep(0.05)
        enter_d = (
            m.get("ingest_degraded_transitions_total", edge="enter") - enter0
        )
        exit_d = (
            m.get("ingest_degraded_transitions_total", edge="exit") - exit0
        )
        assert (enter_d, exit_d) == (1, 1), (
            f"edges enter={enter_d} exit={exit_d}; want exactly one each"
        )

    async def main():
        m = get_metrics()
        sched = IngestScheduler(
            metrics=Metrics(enabled=True), degraded_window_s=0.3
        )
        sched.add_lane(LaneConfig(
            name="l", priority=0, weight=1, max_batch=4, max_queue=4,
            deadline_s=0.01, coalesce_target=1,
        ))
        sched.start()
        try:
            src = _SlowSource()
            await one_storm(sched, src, m)
            await one_storm(sched, src, m)  # the latch re-armed
        finally:
            await sched.stop()

    run(main())


# ------------------------------------------------------- crash injection


def test_crash_kill_offsets_are_seeded_and_deterministic():
    """The round-20 storage-fault pin: the SIGKILL byte offsets are a
    pure function of (seed, trial) through the same hash stream as the
    transport fault layer — same seed, same crash schedule."""
    from lambda_ethereum_consensus_tpu.chaos.crash import kill_offset

    a = [kill_offset(7, t, window_bytes=50_000) for t in range(16)]
    b = [kill_offset(7, t, window_bytes=50_000) for t in range(16)]
    assert a == b
    assert a != [kill_offset(8, t, window_bytes=50_000) for t in range(16)]
    # offsets spread over the configured window span, never inside the
    # file header
    assert min(a) > 8
    assert max(a) <= 8 + 50_000 * 30 + 1
    assert len(set(a)) > 8  # genuinely spread, not clustered


def test_crash_filler_recipe_is_deterministic_and_sized():
    from lambda_ethereum_consensus_tpu.chaos.crash import (
        filler_key,
        filler_value,
    )

    assert filler_value(7, 3, 2, 256) == filler_value(7, 3, 2, 256)
    assert filler_value(7, 3, 2, 256) != filler_value(7, 3, 3, 256)
    assert len(filler_value(7, 0, 0, 100)) == 100
    assert filler_key(1, 2) != filler_key(2, 1)


def test_crash_writer_and_recovery_round_trip(tmp_path):
    """One in-process window set + verify_recovered: the verifier
    accepts an undamaged log and flags a damaged finalized record."""
    from lambda_ethereum_consensus_tpu.chaos import crash as crash_mod

    workload = crash_mod.build_workload(
        11, str(tmp_path), n_keys=8, chain_len=2
    )
    base, finalized_end = crash_mod.build_fuzz_db(
        workload, str(tmp_path), windows=2
    )
    clean = crash_mod.verify_recovered(
        base, workload, acked=[0, 1]
    )
    assert clean["ok"], clean["problems"]
    red = crash_mod.red_self_check(
        workload, base, finalized_end, str(tmp_path)
    )
    assert red["detected"] is True
