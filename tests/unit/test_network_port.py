"""Two sidecar processes over loopback TCP: req/resp + gossip round-trips
(mirror of the reference's test/unit/libp2p_port_test.exs:30-50)."""

import asyncio
import importlib.util

import pytest

from lambda_ethereum_consensus_tpu.network import Port
from lambda_ethereum_consensus_tpu.network.port import (
    VERDICT_ACCEPT,
    VERDICT_IGNORE,
    VERDICT_REJECT,
    PortCommandError,
    PortError,
)
from lambda_ethereum_consensus_tpu.network.proto import port_pb2
from lambda_ethereum_consensus_tpu.telemetry import get_metrics


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=60))


async def start_pair(fork_digest=b"\xba\xa4\xda\x96"):
    recver = await Port.start(fork_digest=fork_digest)
    sender = await Port.start(fork_digest=fork_digest)
    new_peer = asyncio.get_running_loop().create_future()

    def on_new_peer(peer_id, addr):
        if not new_peer.done():
            new_peer.set_result(peer_id)

    sender.on_new_peer = on_new_peer
    await sender.add_peer(f"127.0.0.1:{recver.listen_port}")
    peer_id = await asyncio.wait_for(new_peer, 10)
    assert peer_id == recver.node_id
    return sender, recver, peer_id


def test_identity_and_connect():
    async def main():
        sender, recver, peer_id = await start_pair()
        assert len(sender.node_id) == 32
        assert sender.node_id != recver.node_id
        await sender.close()
        await recver.close()

    run(main())


def test_request_response_roundtrip():
    async def main():
        sender, recver, peer_id = await start_pair()

        async def handle(protocol_id, request_id, payload, from_peer):
            assert payload == b"ping payload"
            assert from_peer == sender.node_id
            await recver.send_response(request_id, b"pong:" + payload)

        await recver.set_request_handler("/eth2/beacon_chain/req/ping/1/", handle)
        reply = await sender.send_request(
            peer_id, "/eth2/beacon_chain/req/ping/1/", b"ping payload"
        )
        assert reply == b"pong:ping payload"
        await sender.close()
        await recver.close()

    run(main())


def test_request_unsupported_protocol_errors():
    async def main():
        sender, recver, peer_id = await start_pair()
        with pytest.raises(Exception, match="unsupported protocol"):
            await sender.send_request(peer_id, "/nope/1/", b"x")
        await sender.close()
        await recver.close()

    run(main())


def test_gossip_roundtrip_with_validation():
    async def main():
        sender, recver, peer_id = await start_pair()
        got = asyncio.get_running_loop().create_future()

        async def on_gossip(topic, msg_id, payload, from_peer):
            await recver.validate_message(msg_id, VERDICT_ACCEPT)
            if not got.done():
                got.set_result((topic, payload))

        await recver.subscribe("/eth2/test/topic/ssz_snappy", on_gossip)
        await asyncio.sleep(0.2)  # let subscription settle
        await sender.publish("/eth2/test/topic/ssz_snappy", b"gossip body")
        topic, payload = await asyncio.wait_for(got, 10)
        assert topic == "/eth2/test/topic/ssz_snappy"
        assert payload == b"gossip body"
        await sender.close()
        await recver.close()

    run(main())


def test_gossip_propagates_through_middle_node():
    """A -> B -> C flood: C must receive a message published by A only if B
    accepts it (validation gates forwarding)."""

    async def main():
        digest = b"\x01\x02\x03\x04"
        a = await Port.start(fork_digest=digest)
        b = await Port.start(fork_digest=digest, enable_peer_exchange=False)
        c = await Port.start(fork_digest=digest, enable_peer_exchange=False)
        await a.add_peer(f"127.0.0.1:{b.listen_port}")
        await c.add_peer(f"127.0.0.1:{b.listen_port}")
        await asyncio.sleep(0.3)

        got_c = asyncio.get_running_loop().create_future()

        async def on_b(topic, msg_id, payload, from_peer):
            verdict = VERDICT_ACCEPT if payload != b"bad" else VERDICT_REJECT
            await b.validate_message(msg_id, verdict)

        async def on_c(topic, msg_id, payload, from_peer):
            await c.validate_message(msg_id, VERDICT_ACCEPT)
            if not got_c.done():
                got_c.set_result(payload)

        await b.subscribe("/t", on_b)
        await c.subscribe("/t", on_c)
        await asyncio.sleep(0.2)
        await a.publish("/t", b"bad")  # rejected at B, must not reach C
        await a.publish("/t", b"good")
        payload = await asyncio.wait_for(got_c, 10)
        assert payload == b"good"
        for port in (a, b, c):
            await port.close()

    run(main())


def test_fork_digest_mismatch_filters_peer():
    async def main():
        x = await Port.start(fork_digest=b"\xaa\xaa\xaa\xaa")
        y = await Port.start(fork_digest=b"\xbb\xbb\xbb\xbb")
        connected = asyncio.get_running_loop().create_future()
        x.on_new_peer = lambda *a: connected.done() or connected.set_result(a)
        await x.add_peer(f"127.0.0.1:{y.listen_port}")
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(asyncio.shield(connected), 1.5)
        await x.close()
        await y.close()

    run(main())


def test_sidecar_crash_detected():
    async def main():
        port = await Port.start()
        exited = asyncio.get_running_loop().create_future()
        port.on_exit = lambda: exited.done() or exited.set_result(True)
        port._proc.kill()
        assert await asyncio.wait_for(exited, 10)
        assert not port.alive
        await port.close()

    run(main())


def test_rejecting_peer_gets_downscored_and_disconnected():
    """Sustained REJECT verdicts must prune and finally disconnect the
    misbehaving sender (VERDICT r1: rejects never penalized anyone)."""

    async def main():
        digest = b"\x05\x06\x07\x08"
        bad = await Port.start(fork_digest=digest)
        honest = await Port.start(fork_digest=digest)
        gone = asyncio.get_running_loop().create_future()
        honest.on_peer_gone = (
            lambda peer_id: gone.done() or gone.set_result(peer_id)
        )
        new_peer = asyncio.get_running_loop().create_future()
        honest.on_new_peer = (
            lambda peer_id, addr: new_peer.done() or new_peer.set_result(peer_id)
        )
        await bad.add_peer(f"127.0.0.1:{honest.listen_port}")
        assert await asyncio.wait_for(new_peer, 10) == bad.node_id

        seen = asyncio.Queue()

        async def on_gossip(topic, msg_id, payload, from_peer):
            # every message from the bad peer is a protocol violation
            await honest.validate_message(msg_id, VERDICT_REJECT)
            await seen.put(payload)

        await honest.subscribe("/junk", on_gossip)
        await asyncio.sleep(0.2)
        # -40 (pruned), -80, -120: the third REJECT crosses the graylist
        for i in range(3):
            await bad.publish("/junk", b"junk-%d" % i)
            await asyncio.wait_for(seen.get(), 10)
        assert await asyncio.wait_for(gone, 10) == bad.node_id
        await bad.close()
        await honest.close()

    run(main())


def test_mesh_grafts_between_subscribers():
    """Two subscribers of one topic graft each other within a heartbeat;
    a published message then flows along the mesh link."""

    async def main():
        digest = b"\x09\x0a\x0b\x0c"
        a = await Port.start(fork_digest=digest)
        b = await Port.start(fork_digest=digest)
        await a.add_peer(f"127.0.0.1:{b.listen_port}")
        await asyncio.sleep(0.2)

        got = asyncio.get_running_loop().create_future()

        async def on_a(topic, msg_id, payload, from_peer):
            await a.validate_message(msg_id, VERDICT_ACCEPT)

        async def on_b(topic, msg_id, payload, from_peer):
            await b.validate_message(msg_id, VERDICT_ACCEPT)
            if not got.done():
                got.set_result(payload)

        await a.subscribe("/mesh", on_a)
        await b.subscribe("/mesh", on_b)
        # a full heartbeat so GRAFT control frames settle the mesh
        await asyncio.sleep(1.0)
        await a.publish("/mesh", b"over the mesh")
        assert await asyncio.wait_for(got, 10) == b"over the mesh"
        await a.close()
        await b.close()

    run(main())


# ------------------------------------------------- round 19: robustness

class _FakeProc:
    returncode = None


def _stub_port():
    """A Port with a live-looking process and no subprocess behind it —
    _roundtrip is replaced per test, so the retry policy is exercised
    in isolation from the wire."""
    port = Port()
    port._proc = _FakeProc()
    return port


def test_command_absorbs_one_transient_error():
    """The ISSUE-14 satellite pin: a single injected transient failure is
    retried away (and counted on port_retry_total{command}); the caller
    never sees it."""
    from lambda_ethereum_consensus_tpu.network.port import PortError
    from lambda_ethereum_consensus_tpu.network.proto import port_pb2
    from lambda_ethereum_consensus_tpu.telemetry import get_metrics

    async def main():
        m = get_metrics()
        m.set_enabled(True)
        before = m.get("port_retry_total", command="publish")
        port = _stub_port()
        attempts = []

        async def flaky(cmd, timeout):
            attempts.append(cmd.WhichOneof("c"))
            if len(attempts) == 1:
                raise PortError("transient sidecar hiccup")
            result = port_pb2.Result()
            result.ok = True
            return result

        port._roundtrip = flaky
        cmd = port_pb2.Command()
        cmd.publish.topic = "t"
        cmd.publish.payload = b"x"
        result = await port._command(cmd)
        assert result.ok
        assert attempts == ["publish", "publish"]
        assert m.get("port_retry_total", command="publish") == before + 1

    run(main())


def test_command_persistent_error_still_raises():
    """Bounded: a failure on every attempt surfaces after the retry
    budget — the supervisor must see real outages."""
    from lambda_ethereum_consensus_tpu.network.port import (
        PortError,
        _retry_max,
    )
    from lambda_ethereum_consensus_tpu.network.proto import port_pb2

    async def main():
        port = _stub_port()
        attempts = []

        async def broken(cmd, timeout):
            attempts.append(1)
            raise PortError("sidecar is wedged")

        port._roundtrip = broken
        cmd = port_pb2.Command()
        cmd.publish.topic = "t"
        cmd.publish.payload = b"x"
        with pytest.raises(PortError):
            await port._command(cmd)
        assert len(attempts) == 1 + _retry_max()

    run(main())


def test_command_dead_sidecar_skips_retries():
    """Once the sidecar is gone the failure is terminal for this Port:
    re-sending into a corpse would just burn the backoff schedule."""
    from lambda_ethereum_consensus_tpu.network.port import PortError
    from lambda_ethereum_consensus_tpu.network.proto import port_pb2

    async def main():
        port = _stub_port()
        attempts = []

        async def dies(cmd, timeout):
            attempts.append(1)
            port._dead = True  # the read loop noticed the exit
            raise PortError("sidecar exited")

        port._roundtrip = dies
        cmd = port_pb2.Command()
        cmd.publish.topic = "t"
        cmd.publish.payload = b"x"
        with pytest.raises(PortError):
            await port._command(cmd)
        assert len(attempts) == 1  # no retry against a dead sidecar

    run(main())


def test_early_peer_events_replay_on_handler_assignment():
    """new_peer/peer_gone notifications that arrive before the node wires
    its handlers (the sidecar dials bootnodes during init — on loopback
    the handshake can win that race) must replay on assignment, not drop:
    a dropped new_peer left the host-side peerbook empty and range sync
    idle while the sidecar was happily connected (found by the ISSUE-14
    chaos fleet)."""
    from lambda_ethereum_consensus_tpu.network.proto import port_pb2

    async def main():
        port = _stub_port()
        n = port_pb2.Notification()
        n.new_peer.peer_id = b"p1"
        n.new_peer.addr = "127.0.0.1:9"
        await port._dispatch(n)
        gone = port_pb2.Notification()
        gone.peer_gone.peer_id = b"p2"
        await port._dispatch(gone)

        seen = []
        port.on_new_peer = lambda pid, addr: seen.append(("new", pid, addr))
        port.on_peer_gone = lambda pid: seen.append(("gone", pid))
        assert seen == [("new", b"p1", "127.0.0.1:9"), ("gone", b"p2")]

        # live path unchanged: the next notification dispatches directly
        n2 = port_pb2.Notification()
        n2.new_peer.peer_id = b"p3"
        n2.new_peer.addr = "127.0.0.1:10"
        await port._dispatch(n2)
        assert seen[-1] == ("new", b"p3", "127.0.0.1:10")
        assert port._early_peer_events == []

    run(main())


def test_early_peer_events_replay_preserves_cross_kind_order():
    """A connect/disconnect/reconnect burst buffered during init must
    replay in ARRIVAL order once both handlers attach — per-kind replay
    would deliver the disconnect last and ghost a live peer."""
    from lambda_ethereum_consensus_tpu.network.proto import port_pb2

    async def main():
        port = _stub_port()
        for kind in ("new", "gone", "new"):
            n = port_pb2.Notification()
            if kind == "new":
                n.new_peer.peer_id = b"p"
                n.new_peer.addr = "127.0.0.1:9"
            else:
                n.peer_gone.peer_id = b"p"
            await port._dispatch(n)

        seen = []
        port.on_new_peer = lambda pid, addr: seen.append("new")
        # only the ordered prefix drains until the gone handler exists
        assert seen == ["new"]
        port.on_peer_gone = lambda pid: seen.append("gone")
        assert seen == ["new", "gone", "new"]  # the peer ends CONNECTED

    run(main())


# ------------------- ISSUE 26: one sidecar frame for a drain's verdicts

needs_libp2p_wire = pytest.mark.skipif(
    importlib.util.find_spec("cryptography") is None,
    reason="libp2p-wire sidecar needs the optional 'cryptography' module",
)
WIRES = [None, pytest.param("libp2p", marks=needs_libp2p_wire)]
BATCH_TOPIC = "/eth2/bba4da96/beacon_aggregate_and_proof/ssz_snappy"


def _roundtrips(command: str) -> int:
    return sum(
        row[4]
        for row in get_metrics().histogram_series("sidecar_roundtrip_seconds")
        if dict(row[0]).get("command") == command
    )


def _batch_sizes() -> tuple[float, int]:
    rows = get_metrics().histogram_series("port_verdict_batch_size")
    return sum(r[3] for r in rows), sum(r[4] for r in rows)


async def _star(wire, n_senders):
    """Hub B with ``n_senders`` peers publishing into it and one peer C
    downstream of it; nobody else is connected to anybody (no peer
    exchange), so what reaches C went through B's verdict.  On the
    libp2p wire a peer's next message waits for the verdict of its last
    (the validator blocks the peer's read loop), hence one message per
    sender for several validations pending at once."""
    kw = dict(wire=wire, fork_digest=b"\xba\xa4\xda\x96", enable_peer_exchange=False)
    b = await Port.start(**kw)
    c = await Port.start(**kw)
    senders = [await Port.start(**kw) for _ in range(n_senders)]
    for port in [c, *senders]:
        await port.add_peer(f"127.0.0.1:{b.listen_port}")
    return b, c, senders


@pytest.mark.parametrize("wire", WIRES)
def test_verdict_batch_is_one_frame_and_resolves_each_pending_validation(wire):
    """A batch of N verdicts against a real sidecar child: one
    ``sidecar_roundtrip``, one frame on the pipe; every pending
    validation resolves with its OWN verdict (ACCEPT forwards and
    rewards, REJECT downscores and never forwards, IGNORE drops);
    unknown and duplicate ids are harmless; the same batch re-sent (a
    retry whose first Result was lost) changes nothing; an empty batch
    writes nothing."""

    async def main():
        m = get_metrics()
        was = m.enabled
        m.set_enabled(True)
        b, c, senders = await _star(wire, 3)
        try:
            pending: dict[bytes, bytes] = {}  # payload -> msg_id, at B
            all_pending = asyncio.Event()
            at_c: list[bytes] = []

            async def on_b(topic, msg_id, payload, from_peer):
                pending[payload] = msg_id  # no verdict yet: the batch gives it
                if len(pending) == 3:
                    all_pending.set()

            async def on_c(topic, msg_id, payload, from_peer):
                # no verdict from C: the process-wide histograms read
                # below must hold B's one frame and nothing else
                at_c.append(payload)

            await b.subscribe(BATCH_TOPIC, on_b)
            await c.subscribe(BATCH_TOPIC, on_c)
            for s in senders:
                await s.subscribe(BATCH_TOPIC, lambda *a: None)
            await asyncio.sleep(1.5)  # heartbeats: subscriptions spread, meshes graft
            bodies = [b"accept-me", b"reject-me", b"ignore-me"]
            for s, body in zip(senders, bodies):
                await s.publish(BATCH_TOPIC, body)
            await asyncio.wait_for(all_pending.wait(), 10)

            async def scores():
                peers = (await b.get_gossip_stats())["peers"]
                return [peers[s.node_id.hex()]["score"] for s in senders]

            before = await scores()
            frames = []
            write = b._proc.stdin.write

            def counting_write(data):
                frames.append(len(data))
                write(data)

            b._proc.stdin.write = counting_write
            trips, sizes, minted = (
                _roundtrips("validate_messages"), _batch_sizes(), b._counter
            )
            async with b.verdict_batch():
                await b.validate_message(b"never-seen", VERDICT_ACCEPT)
                await b.validate_message(pending[b"accept-me"], VERDICT_ACCEPT)
                await b.validate_message(pending[b"reject-me"], VERDICT_REJECT)
                await b.validate_message(pending[b"ignore-me"], VERDICT_IGNORE)
                # a second verdict for an id the batch already answered
                await b.validate_message(pending[b"accept-me"], VERDICT_REJECT)
                assert frames == []  # staged: nothing written inside the bracket
            assert len(frames) == 1
            assert b._counter == minted + 1
            assert _roundtrips("validate_messages") == trips + 1
            got = _batch_sizes()
            assert (got[0] - sizes[0], got[1] - sizes[1]) == (5, 1)
            t0 = asyncio.get_running_loop().time()
            while not at_c and asyncio.get_running_loop().time() - t0 < 10:
                await asyncio.sleep(0.05)
            await asyncio.sleep(0.5)  # anything else B forwarded would be here by now
            assert at_c == [b"accept-me"]
            after = await scores()
            assert after[0] > before[0]  # ACCEPT rewards its sender
            # REJECT downscores its own sender, once: the duplicate REJECT
            # of the accepted id found nothing pending
            assert before[1] - 41 < after[1] < before[1] - 39
            assert abs(after[2] - before[2]) < 0.5  # IGNORE: neither

            # the same batch again, as _command's retry would re-send it
            await b._send_verdicts([
                (b"never-seen", VERDICT_ACCEPT),
                (pending[b"accept-me"], VERDICT_ACCEPT),
                (pending[b"reject-me"], VERDICT_REJECT),
                (pending[b"ignore-me"], VERDICT_IGNORE),
                (pending[b"accept-me"], VERDICT_REJECT),
            ])
            await asyncio.sleep(0.5)
            assert at_c == [b"accept-me"]
            again = await scores()
            assert abs(again[1] - after[1]) < 0.5 and again[0] >= before[0]

            # an empty batch writes nothing
            n_frames, minted = len(frames), b._counter
            async with b.verdict_batch():
                pass
            await b._send_verdicts([])
            assert (len(frames), b._counter) == (n_frames, minted)
        finally:
            m.set_enabled(was)
            for port in [b, c, *senders]:
                await port.close()

    run(main())


def test_verdict_batch_retry_resends_the_same_frame():
    """``_command``'s retry rules hold for the batch: one transient
    failure is retried away with the same verdicts in the same order
    (the sidecar pops a pending entry the first time, so the second
    application changes nothing), counted on
    ``port_retry_total{command="validate_messages"}``."""

    async def main():
        m = get_metrics()
        m.set_enabled(True)
        before = m.get("port_retry_total", command="validate_messages")
        port = _stub_port()
        attempts = []

        async def flaky(cmd, timeout):
            attempts.append([
                (v.msg_id, v.verdict) for v in cmd.validate_messages.verdicts
            ])
            if len(attempts) == 1:
                raise PortError("transient sidecar hiccup")
            return port_pb2.Result(ok=True)

        port._roundtrip = flaky
        batch = [(b"a", VERDICT_ACCEPT), (b"b", VERDICT_REJECT), (b"c", VERDICT_IGNORE)]
        async with port.verdict_batch():
            for msg_id, verdict in batch:
                await port.validate_message(msg_id, verdict)
        assert attempts == [batch, batch]
        assert m.get("port_retry_total", command="validate_messages") == before + 1
        # a verdict outside a bracket: a batch of one through the same path
        await port.validate_message(b"solo", VERDICT_IGNORE)
        assert attempts[-1] == [(b"solo", VERDICT_IGNORE)]

    run(main())


def test_verdict_batch_refused_by_the_sidecar_raises_out_of_the_bracket():
    """``ok = false`` on the batch's Result is a ``PortCommandError`` at
    the bracket's exit — never retried, and the drain that staged the
    batch sees it."""

    async def main():
        port = _stub_port()
        attempts = []

        async def refused(cmd, timeout):
            attempts.append(1)
            raise PortCommandError("ValueError: first error of the batch")

        port._roundtrip = refused
        with pytest.raises(PortCommandError, match="first error"):
            async with port.verdict_batch():
                await port.validate_message(b"a", VERDICT_ACCEPT)
        assert attempts == [1]
        assert port._staged_verdicts == {}

    run(main())


class _Captured(list):
    async def __call__(self, cmd_id, ok, payload=b"", error=""):
        self.append((cmd_id, ok, error))


def _batch_command(entries) -> "port_pb2.Command":
    cmd = port_pb2.Command(id=b"\x00" * 7 + b"\x07")
    for msg_id, verdict in entries:
        cmd.validate_messages.verdicts.add(msg_id=msg_id, verdict=verdict)
    return cmd


def test_one_raising_verdict_does_not_stop_the_rest_bespoke_sidecar(monkeypatch):
    """The bespoke sidecar's own command handler, in process: a verdict
    whose application raises leaves the others applied, in order, and
    the ONE Result says ``ok = false`` with the first error."""
    from lambda_ethereum_consensus_tpu.network.sidecar import Sidecar

    monkeypatch.setenv("SIDECAR_PLAINTEXT", "1")
    monkeypatch.delenv("SIDECAR_KEY_FILE", raising=False)

    async def main():
        sc = Sidecar()
        sc.result = results = _Captured()
        gone = b"\x11" * 32  # a sender that already disconnected
        sc.pending_validation[b"m1"] = ("/t", b"p1", gone, None)
        sc.pending_validation[b"m2"] = ("not", "a pending entry")  # raises
        sc.pending_validation[b"m3"] = ("/t", b"p3", gone, None)
        sc.pending_validation[b"m4"] = ("also", "broken")  # raises too
        await sc.handle_command(_batch_command([
            (b"m1", VERDICT_REJECT), (b"m2", VERDICT_ACCEPT),
            (b"m3", VERDICT_REJECT), (b"m4", VERDICT_ACCEPT),
        ]))
        assert not sc.pending_validation  # every entry was taken up
        # both REJECTs after-and-before the raising ones were applied
        assert sc.ban_scores[gone] == -80.0
        assert len(results) == 1
        cmd_id, ok, error = results[0]
        assert cmd_id == b"\x00" * 7 + b"\x07" and ok is False
        assert error.startswith("ValueError")
        # the single-verdict command is still served
        single = port_pb2.Command(id=b"\x00" * 7 + b"\x08")
        single.validate_message.msg_id = b"unknown"
        await sc.handle_command(single)
        assert results[1] == (b"\x00" * 7 + b"\x08", True, "")

    run(main())


@needs_libp2p_wire
def test_one_raising_verdict_does_not_stop_the_rest_libp2p_sidecar(monkeypatch):
    """The libp2p sidecar's twin of the test above."""
    from lambda_ethereum_consensus_tpu.network.libp2p import gossipsub
    from lambda_ethereum_consensus_tpu.network.sidecar_libp2p import Libp2pSidecar

    monkeypatch.delenv("SIDECAR_KEY_FILE", raising=False)

    async def main():
        sc = Libp2pSidecar()
        sc.result = results = _Captured()
        loop = asyncio.get_running_loop()
        futs = {m: loop.create_future() for m in (b"m1", b"m3", b"m4")}
        sc.pending_validation[b"m1"] = futs[b"m1"]
        sc.pending_validation[b"m2"] = object()  # not a future: raises
        sc.pending_validation[b"m3"] = futs[b"m3"]
        sc.pending_validation[b"m4"] = futs[b"m4"]
        await sc.handle_command(_batch_command([
            (b"m1", VERDICT_ACCEPT), (b"m2", VERDICT_ACCEPT),
            (b"m3", VERDICT_REJECT), (b"unknown", VERDICT_ACCEPT),
            (b"m4", VERDICT_IGNORE), (b"m1", VERDICT_REJECT),
        ]))
        assert not sc.pending_validation
        assert [futs[m].result() for m in (b"m1", b"m3", b"m4")] == [
            gossipsub.ACCEPT, gossipsub.REJECT, gossipsub.IGNORE,
        ]
        assert len(results) == 1
        _, ok, error = results[0]
        assert ok is False and error.startswith("AttributeError")
        single = port_pb2.Command(id=b"\x00" * 7 + b"\x08")
        single.validate_message.msg_id = b"unknown"
        await sc.handle_command(single)
        assert results[1] == (b"\x00" * 7 + b"\x08", True, "")

    run(main())
