"""Stage spans of the served path (ISSUE 25): the gossip drain and the
batched verify record one span per stage and per flush — never one per
message — the stages nest inside the spans that were there, the harness's
``annotate_spans()`` patch leaves them working, a disabled registry
creates no key for them, and the two new benchmark readers read a window
and the recorded tiny trace."""

import asyncio
import contextlib
import os
import re
import sys
import time

import pytest

from lambda_ethereum_consensus_tpu import telemetry
from lambda_ethereum_consensus_tpu.compression.snappy import compress
from lambda_ethereum_consensus_tpu.config import constants, use_chain_spec
from lambda_ethereum_consensus_tpu.crypto import bls
from lambda_ethereum_consensus_tpu.fork_choice import (
    on_attestation_batch,
    on_block,
    on_tick,
)
from lambda_ethereum_consensus_tpu.network.gossip import TopicSubscription
from lambda_ethereum_consensus_tpu.network.port import (
    VERDICT_ACCEPT,
    VERDICT_IGNORE,
    VERDICT_REJECT,
    Port,
)
from lambda_ethereum_consensus_tpu.network.proto import port_pb2
from lambda_ethereum_consensus_tpu.pipeline import IngestScheduler, LaneConfig
from lambda_ethereum_consensus_tpu.state_transition import accessors, misc
from lambda_ethereum_consensus_tpu.telemetry import Metrics, get_metrics, span
from lambda_ethereum_consensus_tpu.types.beacon import (
    Attestation,
    AttestationData,
    Checkpoint,
)

from .test_fork_choice import SKS, build_block, chain, make_store  # noqa: F401

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
BENCH = os.path.join(REPO_ROOT, "benchmark")

# table A of ISSUE 25, by where one flush records them
DRAIN_STAGES = ("gossip_decode", "gossip_verdicts")
VERIFY_STAGES = (
    "attestation_prepare", "signature_decompress", "bls_host_pack",
    "bls_dispatch", "bls_device_wait", "votes_apply",
)
ALL_NEW = DRAIN_STAGES + VERIFY_STAGES + (
    "ingest_wait", "node_tick", "fork_choice_on_block",
)
TOPIC = "/eth2/t1/beacon_aggregate_and_proof/ssz_snappy"


def family_totals(m=None) -> dict:
    """``{family: (sum, count)}`` over every label set — what the
    benchmark's window snapshots (``common.histogram_totals``)."""
    m = m if m is not None else get_metrics()
    out = {}
    for name in m.family_names():
        rows = m.histogram_series(name)
        if rows:
            out[name] = (sum(r[3] for r in rows), sum(r[4] for r in rows))
    return out


def gained(before: dict, after: dict) -> dict:
    return {
        k: (v[0] - before.get(k, (0.0, 0))[0], v[1] - before.get(k, (0.0, 0))[1])
        for k, v in after.items()
        if v[1] - before.get(k, (0.0, 0))[1]
    }


@contextlib.contextmanager
def registry_on():
    """The default registry recording, whatever ``TELEMETRY_OFF`` says."""
    m = get_metrics()
    was = m.enabled
    m.set_enabled(True)
    try:
        yield m
    finally:
        m.set_enabled(was)


@contextlib.contextmanager
def bench_path():
    """``benchmark/`` importable, as ``benchmark/run.py`` makes it."""
    sys.path.insert(0, BENCH)
    try:
        yield
    finally:
        sys.path[:] = [p for p in sys.path if p != BENCH]


@contextlib.contextmanager
def harness_annotations():
    """The harness's ``annotate_spans()`` patch of ``telemetry._Span``,
    taken off again: other test files share this process."""
    span_cls = telemetry._Span
    enter, leave = span_cls.__enter__, span_cls.__exit__
    with bench_path():
        import session

        session.annotate_spans()
        try:
            yield
        finally:
            span_cls.__enter__, span_cls.__exit__ = enter, leave
            session._ANNOTATED = False


class _LiveProc:
    returncode = None


class SidecarPort(Port):
    """A real ``Port`` whose round trip ends at a recorder instead of a
    child process: staging, bracket and send path are the port's own; a
    frame is one ``sidecar_roundtrip`` span, open across an await as the
    real one is, and its verdicts land when it is acknowledged."""

    def __init__(self):
        super().__init__()
        self._proc = _LiveProc()
        self.verdicts = []
        self.frames = []  # verdicts per frame, in the order sent

    async def subscribe(self, topic, handler):
        pass

    async def _roundtrip(self, cmd, timeout):
        with span("sidecar_roundtrip", command=cmd.WhichOneof("c")):
            await asyncio.sleep(0)
        batch = [(v.msg_id, v.verdict) for v in cmd.validate_messages.verdicts]
        self.frames.append(len(batch))
        self.verdicts.extend(batch)
        return port_pb2.Result(ok=True)


async def flush_through_scheduler(
    payloads, handler, spec, ssz_type=Attestation, port=None
):
    """``payloads`` into a lane of a real ``IngestScheduler`` through a real
    ``TopicSubscription``: one full flush, every verdict awaited."""
    port = port if port is not None else SidecarPort()
    sched = IngestScheduler(metrics=Metrics(enabled=True))
    sched.add_lane(LaneConfig(
        name="agg", priority=1, max_queue=64, max_batch=64,
        coalesce_target=len(payloads), deadline_s=5.0,
    ))
    sub = TopicSubscription(
        port, TOPIC, handler, ssz_type=ssz_type, spec=spec,
        scheduler=sched, lane="agg",
    )
    await sub.start()
    sched.start()
    try:
        await asyncio.sleep(0.01)  # the scheduler finds no ready lane and sleeps
        for i, payload in enumerate(payloads):
            await sub._on_gossip(TOPIC, b"m%d" % i, payload, b"peer")
        t0 = time.monotonic()
        while len(port.verdicts) < len(payloads) and time.monotonic() - t0 < 600:
            await asyncio.sleep(0.005)
    finally:
        await sched.stop()
    return port.verdicts


@pytest.fixture(scope="module")
def drains(chain):  # noqa: F811
    """Two cached device drains (interpret mode, minimal preset) of 3 and
    of 4 aggregates through scheduler -> ``_drain_decode_verify`` ->
    ``on_attestation_batch``, a third of 4 under the harness's
    ``annotate_spans()`` patch; what each made the default registry gain."""
    genesis, anchor_block, spec = chain
    out = {}
    with pytest.MonkeyPatch.context() as mp, registry_on(), use_chain_spec(spec):
        mp.setenv("BLS_DEVICE_CHAIN", "1")
        mp.setenv("BLS_DEVICE_CHAIN_MIN", "1")
        store, anchor_root = make_store(genesis, anchor_block, spec)
        on_tick(store, store.genesis_time + 2 * spec.SECONDS_PER_SLOT, spec)
        signed1, _ = build_block(genesis, spec, 1)
        before = family_totals()
        root1 = on_block(store, signed1, spec=spec)
        out["on_block"] = gained(before, family_totals())
        state = store.block_states[root1]

        def payload(committee_index):
            committee = accessors.get_beacon_committee(
                state, 1, committee_index, spec
            )
            data = AttestationData(
                slot=1, index=committee_index, beacon_block_root=root1,
                source=store.justified_checkpoint,
                target=Checkpoint(epoch=0, root=anchor_root),
            )
            domain = accessors.get_domain(
                state, constants.DOMAIN_BEACON_ATTESTER, 0, spec
            )
            root = misc.compute_signing_root(data, domain)
            att = Attestation(
                aggregation_bits=[True] * len(committee), data=data,
                signature=bls.aggregate([bls.sign(SKS[i], root) for i in committee]),
            )
            return compress(att.encode(spec))

        wire = [payload(0), payload(1)]

        async def handler(batch):
            results = on_attestation_batch(
                store, [msg.value for msg in batch], spec=spec
            )
            return [
                VERDICT_ACCEPT if r is None else VERDICT_REJECT for r in results
            ]

        def drain(n):
            payloads = [wire[i % 2] for i in range(n)]
            before = family_totals()
            verdicts = asyncio.run(asyncio.wait_for(
                flush_through_scheduler(payloads, handler, spec), 900
            ))
            assert [v for _, v in verdicts] == [VERDICT_ACCEPT] * n
            return gained(before, family_totals())

        out[3] = drain(3)
        out[4] = drain(4)
        with harness_annotations():
            out["annotated"] = drain(4)
    return out


# ------------------------------------------------- (a) once per flush


@pytest.mark.parametrize("n", [3, 4])
def test_every_stage_records_once_per_flush_not_per_message(drains, n):
    """One entry per stage whether the flush holds 3 messages or 4: the
    count does not grow with the batch, and neither do the round trips."""
    got = drains[n]
    for name in DRAIN_STAGES + VERIFY_STAGES:
        assert got[name + "_seconds"][1] == 1, (name, got.get(name + "_seconds"))
    # the spans that were there: one drain, one batched verify
    assert got["gossip_drain_seconds"][1] == 1
    assert got["attestation_batch_verify_seconds"][1] == 1
    # the drain's verdicts are one frame: one round trip, n verdicts in it
    assert got["sidecar_roundtrip_seconds"][1] == 1
    assert got["port_verdict_batch_size"] == (n, 1)
    # the scheduler slept for want of a ready lane, a handful of times
    # however many messages the flush held
    assert 1 <= got["ingest_wait_seconds"][1] <= 4
    assert got["ingest_flush_wait_seconds"][1] == 1


def test_on_block_records_the_span_the_docstring_names(drains):
    assert drains["on_block"]["fork_choice_on_block_seconds"][1] == 1
    # the state transition nests inside it
    on_block_s = drains["on_block"]["fork_choice_on_block_seconds"][0]
    assert drains["on_block"]["block_transition_seconds"][0] <= on_block_s
    assert "span(\"fork_choice_on_block\")" in telemetry.__doc__


# ------------------------------------------------------ (b) stages nest


@pytest.mark.parametrize("n", [3, 4, "annotated"])
def test_stage_sums_nest(drains, n):
    got = drains[n]
    secs = lambda name: got[name + "_seconds"][0]  # noqa: E731
    verify = secs("attestation_batch_verify")
    stages = sum(secs(name) for name in VERIFY_STAGES)
    assert stages <= verify
    # what is left outside the stages: the grouping loop between the
    # decompression and the chain — which, in the first drain of a target
    # epoch (n == 3 here) and only there, builds the device committee cache
    if n != 3:
        assert stages >= 0.9 * verify, (stages, verify)
    drain = secs("gossip_drain")
    assert secs("gossip_decode") + secs("gossip_verdicts") + verify <= drain
    # the round trip sits inside the verdict hand-over
    assert got["sidecar_roundtrip_seconds"][0] <= secs("gossip_verdicts")


# ----------------------------- (c) under the harness's annotate_spans()


def test_annotate_spans_patch_keeps_every_new_histogram(drains):
    got = drains["annotated"]
    for name in DRAIN_STAGES + VERIFY_STAGES:
        assert got[name + "_seconds"][1] == 1, name
    assert got["ingest_wait_seconds"][1] >= 1
    assert got["sidecar_roundtrip_seconds"][1] == 1


def test_annotated_spans_survive_interleaved_tasks():
    """Two drains on one loop, their ``gossip_verdicts`` and
    ``sidecar_roundtrip`` spans open across awaits and interleaved, under
    the harness's patch (whose annotations nest by thread, not by task):
    nothing raises and every span still lands in its histogram."""
    async def handler(batch):
        await asyncio.sleep(0)
        return [VERDICT_ACCEPT] * len(batch)

    async def main():
        payloads = [compress(b"vote" * 8)] * 5
        return await asyncio.gather(
            flush_through_scheduler(payloads, handler, None, ssz_type=None),
            flush_through_scheduler(payloads, handler, None, ssz_type=None),
        )

    with registry_on(), harness_annotations():
        before = family_totals()
        a, b = asyncio.run(asyncio.wait_for(main(), 60))
        got = gained(before, family_totals())
    assert len(a) == len(b) == 5
    assert got["gossip_decode_seconds"][1] == 2
    assert got["gossip_verdicts_seconds"][1] == 2
    assert got["sidecar_roundtrip_seconds"][1] == 2
    assert got["port_verdict_batch_size"] == (10, 2)


def test_undecodable_message_is_rejected_before_the_handler():
    """The decode loop never awaits (its span is left synchronously): a
    message whose bytes do not decode gets its REJECT right after the
    loop, under a ``gossip_verdicts`` span of its own, and the rest of
    the batch goes on to the handler."""
    seen = []

    async def handler(batch):
        seen.extend(msg.msg_id for msg in batch)
        return [VERDICT_ACCEPT] * len(batch)

    with registry_on():
        before = family_totals()
        verdicts = asyncio.run(asyncio.wait_for(flush_through_scheduler(
            [compress(b"ok"), b"\xff\xff\xff not snappy", compress(b"ok")],
            handler, None, ssz_type=None,
        ), 60))
        got = gained(before, family_totals())
    assert verdicts == [
        (b"m1", VERDICT_REJECT), (b"m0", VERDICT_ACCEPT), (b"m2", VERDICT_ACCEPT),
    ]
    assert seen == [b"m0", b"m2"]
    assert got["gossip_decode_seconds"][1] == 1
    assert got["gossip_verdicts_seconds"][1] == 2


def test_drain_decodes_by_the_plan_and_counts_a_flush_once():
    """A flush of snappy+SSZ ``Attestation``s (ISSUE 31): a corrupt SSZ
    body and a corrupt snappy body are REJECTed before the handler, the
    rest reach it equal to what was encoded, decoded by the type's plan."""
    from lambda_ethereum_consensus_tpu.config import minimal_spec

    spec = minimal_spec()
    votes = [
        Attestation(
            aggregation_bits=[j == i for j in range(9 + i)],
            data=AttestationData(
                slot=i, index=i % 4, beacon_block_root=bytes([i]) * 32,
                source=Checkpoint(epoch=i, root=b"\x01" * 32),
                target=Checkpoint(epoch=i + 1, root=b"\x02" * 32),
            ),
            signature=bytes([0xA0 + i]) * 96,
        )
        for i in range(6)
    ]
    wire = [compress(v.encode(spec)) for v in votes]
    no_sentinel = bytearray(votes[0].encode(spec))
    no_sentinel[228:] = bytes(len(no_sentinel) - 228)  # the bitlist, zeroed
    wire.insert(2, compress(bytes(no_sentinel)))  # m2: snappy fine, SSZ not
    wire.insert(5, b"\xff\xff\xff not snappy")  # m5
    seen = []

    async def handler(batch):
        seen.extend(msg.value for msg in batch)
        return [VERDICT_ACCEPT] * len(batch)

    verdicts = asyncio.run(asyncio.wait_for(
        flush_through_scheduler(wire, handler, spec), 60
    ))
    assert verdicts[:2] == [(b"m2", VERDICT_REJECT), (b"m5", VERDICT_REJECT)]
    assert [v for _, v in verdicts[2:]] == [VERDICT_ACCEPT] * 6
    assert seen == votes
    assert [type(v.data.source) for v in seen] == [Checkpoint] * 6
    assert Attestation.decode_plan_kind(spec) == "mixed"


# ------------------------------- one frame for a drain's verdicts (ISSUE 26)

BAD = b"\xff\xff\xff not snappy"


async def drain_once(port, payloads, handler):
    """One drain of ``payloads`` (ids ``m0..``) through a real
    ``TopicSubscription``; returns when the drain does."""
    sub = TopicSubscription(port, TOPIC, handler, ssz_type=None)
    await sub._process_batch(
        [(b"m%d" % i, payload, b"peer", None) for i, payload in enumerate(payloads)]
    )


async def _mixed(batch):
    return [(VERDICT_ACCEPT, VERDICT_REJECT, VERDICT_IGNORE)[i % 3]
            for i in range(len(batch))]


async def _short(batch):
    return [VERDICT_ACCEPT]


async def _raising(batch):
    raise RuntimeError("handler bug")


def _ok(n):
    return [compress(b"ok%d" % i) for i in range(n)]


@pytest.mark.parametrize(
    "payloads, handler, expected, frames",
    [
        # undecodable ones first (their REJECTs do not wait for the
        # handler), then the rest in arrival order with the handler's own
        (
            [BAD] + _ok(2) + [BAD] + _ok(3),
            _mixed,
            [(b"m0", VERDICT_REJECT), (b"m3", VERDICT_REJECT),
             (b"m1", VERDICT_ACCEPT), (b"m2", VERDICT_REJECT),
             (b"m4", VERDICT_IGNORE), (b"m5", VERDICT_ACCEPT),
             (b"m6", VERDICT_REJECT)],
            [2, 5],
        ),
        # a short handler output: the rest are IGNOREd
        (
            _ok(4),
            _short,
            [(b"m0", VERDICT_ACCEPT), (b"m1", VERDICT_IGNORE),
             (b"m2", VERDICT_IGNORE), (b"m3", VERDICT_IGNORE)],
            [4],
        ),
        # a raising handler: every decoded message drops to IGNORE
        (
            _ok(2) + [BAD],
            _raising,
            [(b"m2", VERDICT_REJECT), (b"m0", VERDICT_IGNORE),
             (b"m1", VERDICT_IGNORE)],
            [1, 2],
        ),
        # nothing decodes: one frame of REJECTs, the handler never runs
        ([BAD, BAD], _raising,
         [(b"m0", VERDICT_REJECT), (b"m1", VERDICT_REJECT)], [2]),
        # a drain of one (a block topic) is a batch of one
        (_ok(1), _mixed, [(b"m0", VERDICT_ACCEPT)], [1]),
    ],
    ids=["undecodable+mixed", "short", "raising", "all-undecodable", "single"],
)
def test_drain_verdicts_same_sequence_in_two_round_trips_at_most(
    payloads, handler, expected, frames
):
    """Every message gets exactly one verdict, the same one and in the
    same order as when each was its own round trip; now the drain's
    REJECTs are one frame and everything after the handler another."""
    port = SidecarPort()
    handed = []
    port_validate = port.validate_message

    async def record(msg_id, verdict):
        handed.append((msg_id, verdict))
        await port_validate(msg_id, verdict)

    port.validate_message = record
    with registry_on():
        before = family_totals()
        asyncio.run(asyncio.wait_for(drain_once(port, payloads, handler), 60))
        got = gained(before, family_totals())
    assert handed == expected  # through validate_message, once per message
    assert port.verdicts == expected  # and what the sidecar was sent
    assert port.frames == frames
    assert got["sidecar_roundtrip_seconds"][1] == len(frames) <= 2
    assert got["port_verdict_batch_size"] == (len(expected), len(frames))
    assert port._staged_verdicts == {}


def test_hooked_validate_message_sees_every_verdict_before_the_drain_returns():
    """The harness's contract (``benchmark/session.py`` ``hook_verdicts``,
    ``chip_smoke.py``): a coroutine assigned to ``port.validate_message``
    on the INSTANCE, which records and then awaits the original, is
    called once per message — and by the time the drain returns, every
    verdict was seen by it and acknowledged by the sidecar."""
    port = SidecarPort()
    seen = {}
    port_validate = port.validate_message

    async def record_verdict(msg_id, verdict):
        seen[msg_id] = (verdict, len(port.verdicts))
        await port_validate(msg_id, verdict)

    port.validate_message = record_verdict

    async def main():
        await drain_once(port, _ok(6) + [BAD], _mixed)
        # the drain has returned: nothing is still to be sent
        return dict(seen), list(port.verdicts)

    at_return, acked = asyncio.run(asyncio.wait_for(main(), 60))
    assert set(at_return) == {b"m%d" % i for i in range(7)}
    assert sorted(acked) == sorted((m, v) for m, (v, _) in at_return.items())
    # staged, not sent one by one: the six after the handler were all
    # handed over before their frame (the REJECT's one verdict) went out
    assert [at_return[b"m%d" % i][1] for i in range(6)] == [1] * 6
    assert port.frames == [1, 6]


def test_verdict_outside_a_bracket_and_from_another_task_is_a_batch_of_one():
    """``validate_message`` outside a bracket (queue-full IGNORE, a shed,
    gossip on a topic nobody handles) is a batch of one through the same
    send path — also when another task's bracket is open meanwhile."""
    port = SidecarPort()

    async def main():
        gate = asyncio.Event()

        async def other():
            await gate.wait()
            await port.validate_message(b"other", VERDICT_IGNORE)

        task = asyncio.ensure_future(other())
        async with port.verdict_batch():
            await port.validate_message(b"a", VERDICT_ACCEPT)
            gate.set()
            await asyncio.sleep(0.01)  # a bracket body that suspends
            assert port.verdicts == [(b"other", VERDICT_IGNORE)]
            await port.validate_message(b"b", VERDICT_REJECT)
            assert len(port.verdicts) == 1
        await task
        async with port.verdict_batch():
            pass  # an empty batch writes nothing

    asyncio.run(asyncio.wait_for(main(), 60))
    assert port.frames == [1, 2]
    assert port.verdicts[1:] == [(b"a", VERDICT_ACCEPT), (b"b", VERDICT_REJECT)]


# ------------------------------------------------------------- node tick


def test_node_tick_span_once_per_tick(tmp_path):
    from lambda_ethereum_consensus_tpu.config import minimal_spec
    from lambda_ethereum_consensus_tpu.node import BeaconNode, NodeConfig
    from lambda_ethereum_consensus_tpu.state_transition.genesis import (
        build_genesis_state,
    )

    async def main(spec):
        genesis = build_genesis_state(
            [bls.sk_to_pk(k) for k in SKS], genesis_time=int(time.time()) - 26,
            spec=spec,
        )
        node = BeaconNode(NodeConfig(
            db_path=str(tmp_path / "n.wal"), genesis_state=genesis, wire=None,
            enable_range_sync=False,
        ))
        before = family_totals()
        await node.start()
        try:
            await asyncio.sleep(2.2)  # two second boundaries
        finally:
            await node.stop()
        return gained(before, family_totals())

    with registry_on(), use_chain_spec(minimal_spec()) as spec:
        got = asyncio.run(asyncio.wait_for(main(spec), 120))
    total, count = got["node_tick_seconds"]
    assert 2 <= count <= 3
    assert 0.0 < total < 2.2


def test_node_holds_a_flush_in_the_young_generation(tmp_path):
    """While a node runs, a flush's working set (tens of thousands of live
    message objects) meets no collection, so none of it is promoted to the
    generation whose collection walks the registry; stop() restores."""
    import gc

    from lambda_ethereum_consensus_tpu import types as T
    from lambda_ethereum_consensus_tpu.config import minimal_spec
    from lambda_ethereum_consensus_tpu.node import BeaconNode, NodeConfig
    from lambda_ethereum_consensus_tpu.node.node import GC_YOUNG_OBJECTS
    from lambda_ethereum_consensus_tpu.state_transition.genesis import (
        build_genesis_state,
    )

    async def main(spec):
        genesis = build_genesis_state(
            [bls.sk_to_pk(k) for k in SKS], genesis_time=int(time.time()) - 26,
            spec=spec,
        )
        node = BeaconNode(NodeConfig(
            db_path=str(tmp_path / "n.wal"), genesis_state=genesis, wire=None,
            enable_range_sync=False,
        ))
        await node.start()
        collections = []
        watch = lambda phase, info: collections.append(info["generation"])  # noqa: E731
        try:
            running = gc.get_threshold()
            gc.callbacks.append(watch)
            flush = [T.Checkpoint(epoch=i, root=bytes(32)) for i in range(40_000)]
            del flush
        finally:
            gc.callbacks.remove(watch)
            await node.stop()
        return running, collections

    before = gc.get_threshold()
    with use_chain_spec(minimal_spec()) as spec:
        running, collections = asyncio.run(asyncio.wait_for(main(spec), 120))
    assert running == (GC_YOUNG_OBJECTS, *before[1:]) and GC_YOUNG_OBJECTS >= 100_000
    assert collections == []
    assert gc.get_threshold() == before


# ------------------------------------------------------- (e) no-op mode


def test_disabled_registry_creates_no_key_for_any_new_family(monkeypatch):
    monkeypatch.setenv("TELEMETRY_OFF", "1")
    assert telemetry.telemetry_enabled() is False
    off = Metrics(enabled=telemetry.telemetry_enabled())
    for name in ALL_NEW:
        with off.span(name, topic="t"):
            pass
        with off.bound_span(name).time():
            pass
        off.observe(name + "_seconds", 0.5)
    assert off.key_count() == 0
    assert off.family_names() == set()
    # every new family is in the inventory the exposition documents
    for name in ALL_NEW:
        assert name + "_seconds" in telemetry._HELP


# ------------------------------------------- (f) the two new readers


class FakeWindow:
    def __init__(self, spans0, spans1, trace=None, traced=None):
        self.spans0, self.spans1 = spans0, spans1
        self.trace, self.traced = trace, traced

    def span_delta(self, family):
        a = self.spans0.get(family, (0.0, 0))
        b = self.spans1.get(family, (0.0, 0))
        return b[0] - a[0], b[1] - a[1]


@pytest.fixture
def readers():
    with bench_path():
        from readers import span_count, trace_module_time

        import tracered

        yield span_count, trace_module_time, tracered


def test_span_count_reader_divides_a_count_by_a_fact(readers):
    span_count, _, _ = readers
    w = FakeWindow(
        {"sidecar_roundtrip_seconds": (1.0, 100)},
        {"sidecar_roundtrip_seconds": (9.0, 100 + 6 * 1024 + 3)},
    )
    facts = {"bursts": 6, "aggregates": 6144}
    got = span_count.read(w, facts, family="sidecar_roundtrip_seconds", per="bursts")
    assert got == pytest.approx((6 * 1024 + 3) / 6)
    # nothing to read: the family never recorded, or the fact is missing
    assert span_count.read(w, facts, family="nope_seconds", per="bursts") is None
    assert span_count.read(
        w, {}, family="sidecar_roundtrip_seconds", per="bursts"
    ) is None


def test_trace_module_time_reader_on_the_tiny_trace(readers):
    _, trace_module_time, tracered = readers
    from fixtures.make_tiny_xplane import EXPECTED

    trace = tracered.reduce_trace(os.path.join(BENCH, "fixtures", "tiny.xplane.pb"))
    w = FakeWindow({}, {}, trace=trace, traced={"t0": 0.0, "t1": 1.0, "items": 4})
    total_us = sum(EXPECTED["modules_us"].values())
    assert trace_module_time.read(w, {}, scale=1e6) == pytest.approx(total_us / 4)
    name, us = next(iter(EXPECTED["modules_us"].items()))
    assert trace_module_time.read(
        w, {}, scale=1e6, select=re.escape(name)
    ) == pytest.approx(us / 4)
    # no module matches, no trace, no whole item: nothing, never zero
    assert trace_module_time.read(w, {}, select="no_such_module") is None
    assert trace_module_time.read(FakeWindow({}, {}), {}) is None
    w.traced["items"] = 0
    assert trace_module_time.read(w, {}, scale=1e6) is None
