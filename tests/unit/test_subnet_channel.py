"""The attestation subnet channel of a real node against the plain reference.

A ``BeaconNode`` (minimal preset, 64 validators, the bespoke sidecar) is put
on all 64 subnets by its own run-time call; one slot's unaggregated votes and
a flush of faulty ones go in through the topics' own
``TopicSubscription._on_gossip`` -> ``IngestScheduler`` -> ``SharedLaneSink``
-> ``_subnet_attestation_drain`` -> the cached device chain (interpret mode)
in the single-signer shape.  Verdicts and ``store.latest_messages`` are held
to ``benchmark/plainref_subnet.py`` (a child that imports nothing of the
program) — the comparison the benchmark's ``head.subnets-all`` makes at
2^20 validators.  One scenario, made once (an interpret-mode chain call
costs ~10 s); the tests read what it left.
"""

import asyncio
import json
import os
import struct
import subprocess
import sys
import time

import pytest

from lambda_ethereum_consensus_tpu import telemetry
from lambda_ethereum_consensus_tpu.compression.snappy import compress
from lambda_ethereum_consensus_tpu.config import constants, minimal_spec, use_chain_spec
from lambda_ethereum_consensus_tpu.crypto import bls
from lambda_ethereum_consensus_tpu.crypto.bls import batch as batch_mod
from lambda_ethereum_consensus_tpu.node import BeaconNode, NodeConfig
from lambda_ethereum_consensus_tpu.node import ingest as ingest_mod
from lambda_ethereum_consensus_tpu.state_transition import accessors, misc
from lambda_ethereum_consensus_tpu.state_transition.genesis import build_genesis_state
from lambda_ethereum_consensus_tpu.types.beacon import Attestation, AttestationData, Checkpoint

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PLAINREF = os.path.join(ROOT, "benchmark", "plainref_subnet.py")
N = 64
SKS = [(i + 1).to_bytes(32, "big") for i in range(N)]
SECONDS_PER_SLOT = 120  # interpret-mode drains take many seconds
LETTER = {0: "A", 1: "R", 2: "I"}  # network.port VERDICT_ACCEPT / REJECT / IGNORE
ALL = tuple(range(64))


def counter(name: str, registries, **labels) -> float:
    want = [f'{k}="{v}"' for k, v in labels.items()]
    total = 0.0
    for reg in registries:
        for line in reg.render_prometheus(self_scrape=False).splitlines():
            if line.startswith(name + "{") and all(w in line for w in want):
                total += float(line.rsplit(" ", 1)[1])
    return total


def span_count(family: str) -> int:
    reg = telemetry.get_metrics()
    if family not in reg.family_names():
        return 0
    return sum(count for *_rest, count in reg.histogram_series(family))


class PlainReference:
    """``benchmark/plainref_subnet.py`` as a child without ``PYTHONPATH``."""

    def __init__(self):
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        self.proc = subprocess.Popen(
            [sys.executable, PLAINREF, "minimal", str(SECONDS_PER_SLOT)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            cwd=os.path.dirname(PLAINREF))

    def ask(self, header: dict, payload: bytes = b"") -> dict:
        head = json.dumps({**header, "bytes": len(payload)}).encode()
        self.proc.stdin.write(struct.pack("<Q", len(head)) + head + payload)
        self.proc.stdin.flush()
        return json.loads(self.proc.stdout.readline())

    def votes(self, messages, pushed_slot: int) -> str:
        answer = self.ask(
            {"cmd": "votes", "subnets": [m["subnet"] for m in messages],
             "pushed_slots": [pushed_slot] * len(messages),
             "valid": [int(m["valid"]) for m in messages],
             "sizes": [len(m["ssz"]) for m in messages]},
            b"".join(m["ssz"] for m in messages))
        assert answer["kind"] == "votes", answer
        return answer["verdicts"]

    def close(self):
        self.proc.stdin.close()
        self.proc.wait(timeout=10)


async def scenario(tmp, mp) -> dict:
    spec = minimal_spec().replace(SECONDS_PER_SLOT=SECONDS_PER_SLOT)
    out = {}
    with use_chain_spec(spec):
        genesis = build_genesis_state(
            [bls.sk_to_pk(k) for k in SKS],
            genesis_time=int(time.time()) - 20 * SECONDS_PER_SLOT - 5, spec=spec)
        # what --attnets 0,1,...,63 at start ends in
        at_start = BeaconNode(NodeConfig(
            db_path=os.path.join(tmp, "a.wal"), genesis_state=genesis, wire=None,
            enable_range_sync=False, attnet_subnets=ALL))
        await at_start.start()
        out["subs_at_start"] = sorted(s.topic_label for s in at_start._subs)
        await at_start.stop()

        # tiny lanes, so that the capacity rule has to act: one subnet's
        # committee (4) fits the floor, a slot of every subnet (8) does not
        mp.setattr(ingest_mod, "ATT_QUEUE", 4)
        node = BeaconNode(NodeConfig(
            db_path=os.path.join(tmp, "b.wal"), genesis_state=genesis, wire=None,
            enable_range_sync=False, attnet_subnets=(0,), ingest_max_items=6))
        await node.start()
        try:
            lane = lambda: next(x for x in node.ingest.snapshot()["lanes"]  # noqa: E731
                                if x["name"] == "subnet")
            out["lane_one_subnet"] = (lane()["capacity"], node.ingest.max_items)
            await node.set_attestation_subnets(ALL)
            out["lane_all_subnets"] = (lane()["capacity"], node.ingest.max_items)
            out["subs_at_run_time"] = sorted(s.topic_label for s in node._subs)
            out["attnets"] = node._attnets_bitfield()
            with pytest.raises(ValueError):
                await node.set_attestation_subnets((0, 64))
            out["subs_after_refused"] = sorted(s.topic_label for s in node._subs)
            await node._start_network()  # a sidecar restart
            out["subs_after_restart"] = sorted(s.topic_label for s in node._subs)
            out["lane_after_restart"] = (lane()["capacity"], node.ingest.max_items)

            verdicts: dict[bytes, int] = {}
            port_validate = node.port.validate_message

            async def record(msg_id, verdict):
                verdicts[msg_id] = verdict
                await port_validate(msg_id, verdict)

            node.port.validate_message = record
            store = node.store
            anchor_root = next(iter(store.blocks))
            state = genesis
            cps = accessors.get_committee_count_per_slot(state, 2, spec)
            domain = accessors.get_domain(state, constants.DOMAIN_BEACON_ATTESTER, 2, spec)

            def vote(slot, index, position, *, subnet=None, head=None, sk=None,
                     signature=None, extra_bit=None):
                committee = accessors.get_beacon_committee(state, slot, index, spec)
                data = AttestationData(
                    slot=slot, index=index, beacon_block_root=head or anchor_root,
                    source=Checkpoint(epoch=0, root=b"\x00" * 32),
                    target=Checkpoint(epoch=2, root=anchor_root))
                v = int(committee[position])
                if signature is None:
                    root = misc.compute_signing_root(data, domain)
                    signature = bls.sign(SKS[v] if sk is None else sk, root)
                bits = [i in (position, extra_bit) for i in range(len(committee))]
                ssz = Attestation(aggregation_bits=bits, data=data,
                                  signature=signature).encode(spec)
                own = misc.compute_subnet_for_attestation(cps, slot, index, spec)
                return {"ssz": ssz, "subnet": own if subnet is None else subnet,
                        "valid": sk is None and signature is not None and len(ssz) > 0,
                        "validator": v}

            async def flush(name, messages):
                shed0 = counter("ingest_shed_count", node_registries(node), lane="subnet")
                want = len(verdicts) + len(messages)
                for j, m in enumerate(messages):
                    sub = next(s for s in node._subs
                               if s.topic_label == f"beacon_attestation_{m['subnet']}")
                    m["id"] = b"%s:%d" % (name.encode(), j)
                    await sub._on_gossip(sub.topic, m["id"], compress(m["ssz"]), b"peer")
                t0 = time.monotonic()
                # every verdict back and the flush over: until its frame is
                # acknowledged its items still count against the budget
                while (len(verdicts) < want or node.ingest.snapshot()["inflight"]
                       ) and time.monotonic() - t0 < 600:
                    await asyncio.sleep(0.01)
                return {
                    "got": "".join(LETTER.get(verdicts.get(m["id"]), "?") for m in messages),
                    "shed": counter("ingest_shed_count", node_registries(node),
                                    lane="subnet") - shed0,
                }

            single0 = counter("bls_chain_entries_total", [telemetry.get_metrics()],
                              shape="single")
            points0 = counter("bls_chain_entries_total", [telemetry.get_metrics()],
                              shape="points")
            validate0 = span_count("subnet_validate_seconds")
            # flush A: one whole slot of every subscribed subnet, all sound
            slot_votes = [vote(18, index, p) for p in range(4) for index in range(cps)]
            out["slot_votes"] = len(slot_votes)
            out["A"] = await flush("a", slot_votes)
            # flush B: the faults, and one sound vote of another slot
            infinity = b"\xc0" + b"\x00" * 95
            faults = [
                vote(17, 0, 0),                                  # sound
                vote(17, 0, 1, sk=SKS[0]),                       # another key's signature
                vote(17, 1, 0, subnet=(misc.compute_subnet_for_attestation(
                    cps, 17, 1, spec) + 1) % 64),               # on the wrong topic
                vote(17, 1, 1, extra_bit=2),                     # two bits
                vote(18, 0, 0, head=b"\x42" * 32),               # a second vote: another head
                vote(17, 1, 2, signature=infinity),              # the point at infinity
                vote(17, 1, 3, signature=b"\xff" * 96),          # not a point at all
            ]
            for m, valid in zip(faults, (True, False, True, True, True, False, False)):
                m["valid"] = valid
            out["B"] = await flush("b", faults)
            out["B_want"] = "ARRRIRR"
            out["single_gained"] = counter(
                "bls_chain_entries_total", [telemetry.get_metrics()], shape="single") - single0
            out["points_gained"] = counter(
                "bls_chain_entries_total", [telemetry.get_metrics()], shape="points") - points0
            out["validate_spans"] = span_count("subnet_validate_seconds") - validate0
            out["seen_gauge"] = counter_gauge(node, "subnet_seen_votes")
            out["double_votes"] = node.forensics.evidence_count("double_vote")
            out["latest"] = {int(v): (int(m.epoch), bytes(m.root))
                             for v, m in store.latest_messages.items()}
            out["accepted_validators"] = sorted(
                m["validator"] for m in slot_votes + faults[:1])

            # the plain reference over the same messages, in push order
            plain = PlainReference()
            try:
                held = plain.ask({"cmd": "state"}, genesis.encode(spec))
                out["plain_anchor"] = bytes.fromhex(held["anchor_root"]) == anchor_root
                out["plain_A"] = plain.votes(slot_votes, 20)
                out["plain_B"] = plain.votes(faults, 20)
                table = plain.ask({"cmd": "table"})
            finally:
                plain.close()
            import numpy as np

            vals = np.frombuffer(bytes.fromhex(table["validators"]), "<u4").tolist()
            eps = np.frombuffer(bytes.fromhex(table["epochs"]), "<u4").tolist()
            ids = np.frombuffer(bytes.fromhex(table["root_ids"]), "<u4").tolist()
            out["plain_latest"] = {
                v: (e, bytes.fromhex(table["roots"][r])) for v, e, r in zip(vals, eps, ids)}
        finally:
            await node.stop()
    return out


def node_registries(node):
    return (telemetry.get_metrics(), node.metrics)


def counter_gauge(node, name: str) -> float:
    for line in node.metrics.render_prometheus(self_scrape=False).splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            return float(line.rsplit(" ", 1)[1])
    return -1.0


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("subnet_channel"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("SIDECAR_PLAINTEXT", "1")
        mp.setenv("BLS_DEVICE_CHAIN", "1")
        mp.setenv("BLS_DEVICE_CHAIN_MIN", "1")
        # 16-bit RLC coefficients and ladder, as the benchmark's rehearsal
        mp.setattr(batch_mod, "_COEFF_BITS", 16)
        m = telemetry.get_metrics()
        was = m.enabled
        m.set_enabled(True)  # the default registry recording, whatever TELEMETRY_OFF says
        try:
            return asyncio.run(asyncio.wait_for(scenario(tmp, mp), 1200))
        finally:
            m.set_enabled(was)


@pytest.mark.device
def test_run_time_subscription_equals_attnets_at_start(ran):
    assert len(ran["subs_at_start"]) == 66  # block, aggregate, 64 subnets
    assert ran["subs_at_run_time"] == ran["subs_at_start"]
    assert ran["attnets"] == b"\xff" * 8


@pytest.mark.device
def test_subscription_survives_a_network_restart(ran):
    assert ran["subs_after_restart"] == ran["subs_at_start"]
    assert ran["lane_after_restart"] == ran["lane_all_subnets"]


@pytest.mark.device
def test_out_of_range_subnet_is_refused_and_changes_nothing(ran):
    assert ran["subs_after_refused"] == ran["subs_at_start"]


@pytest.mark.device
def test_subnet_lane_capacity_follows_the_subscription(ran):
    # one subnet: one committee of 4 fits the floor (4); the budget as configured
    assert ran["lane_one_subnet"] == (4, 6)
    # all subnets: a slot's 2 committees x 4; the budget grows by what the lane grew
    assert ran["lane_all_subnets"] == (8, 10)


@pytest.mark.device
def test_a_whole_slot_of_every_subscribed_subnet_is_never_shed(ran):
    assert ran["slot_votes"] == 8 == ran["lane_all_subnets"][0]
    assert ran["A"]["shed"] == 0 and ran["B"]["shed"] == 0
    assert ran["A"]["got"] == "A" * 8


@pytest.mark.device
@pytest.mark.parametrize("at,what", list(enumerate([
    "sound vote", "another key's signature", "wrong subnet's topic", "two bits",
    "second vote of one attester", "infinity signature", "undecodable signature"])))
def test_fault_verdicts(ran, at, what):
    assert ran["B"]["got"][at] == ran["B_want"][at], what


@pytest.mark.device
def test_verdicts_equal_the_plain_reference(ran):
    assert ran["plain_anchor"]
    assert ran["plain_A"] == ran["A"]["got"]
    assert ran["plain_B"] == ran["B"]["got"]


@pytest.mark.device
def test_latest_messages_equal_the_plain_reference(ran):
    assert ran["latest"] == ran["plain_latest"]
    assert sorted(ran["latest"]) == ran["accepted_validators"]


@pytest.mark.device
def test_votes_were_verified_in_the_single_signer_shape(ran):
    # 8 + the 2 of flush B that reach the chain (bisection re-checks them)
    assert ran["single_gained"] >= 9
    assert ran["points_gained"] == 0


@pytest.mark.device
def test_subnet_validate_is_booked_once_per_flush(ran):
    assert ran["validate_spans"] == 2


@pytest.mark.device
def test_seen_votes_gauge_and_double_vote_evidence(ran):
    assert ran["seen_gauge"] == 9  # one cell an accepted vote
    assert ran["double_votes"] == 1
