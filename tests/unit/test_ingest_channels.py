"""The gossip channels of ``node/ingest.py`` without a node.

A channel takes an ``IngestContext`` and nothing else, so its rules run here
against a stub store over one real genesis state (minimal preset, 64
validators), a stubbed ``on_attestation_batch`` and recording forensics — no
device chain, no sidecar.  The last tests drive the real
``TopicSubscription`` -> ``IngestScheduler`` -> ``SharedLaneSink`` -> channel
path over a port double, across what a sidecar restart rebuilds.
"""

import asyncio
import contextlib
from types import SimpleNamespace

import pytest

from lambda_ethereum_consensus_tpu.compression.snappy import compress
from lambda_ethereum_consensus_tpu.config import constants, minimal_spec, use_chain_spec
from lambda_ethereum_consensus_tpu.crypto import bls
from lambda_ethereum_consensus_tpu.network.port import (
    VERDICT_ACCEPT as A,
    VERDICT_IGNORE as I,
    VERDICT_REJECT as R,
)
from lambda_ethereum_consensus_tpu.node import NodeConfig
from lambda_ethereum_consensus_tpu.node import ingest as ingest_mod
from lambda_ethereum_consensus_tpu.node.ingest import GossipIngest, IngestContext
from lambda_ethereum_consensus_tpu.state_transition import accessors, misc
from lambda_ethereum_consensus_tpu.state_transition.genesis import build_genesis_state
from lambda_ethereum_consensus_tpu.telemetry import Metrics
from lambda_ethereum_consensus_tpu.types.beacon import (
    Attestation,
    AttestationData,
    BeaconBlock,
    BeaconBlockBody,
    Checkpoint,
    SignedBeaconBlock,
)

ROOT_A, ROOT_B = b"\xaa" * 32, b"\xbb" * 32
SIGNATURE = b"\xc0" + b"\x00" * 95  # never checked: the batched verify is stubbed
EPOCH = 2  # the target epoch of every vote


@pytest.fixture(scope="module")
def chain():
    with use_chain_spec(minimal_spec()) as spec:
        keys = [bls.sk_to_pk((i + 1).to_bytes(32, "big")) for i in range(64)]
        yield spec, build_genesis_state(keys, spec=spec)


class Forensics:
    def __init__(self):
        self.votes = []

    def note_vote(self, cell, root):
        self.votes.append((cell, root))


class Harness:
    """The channels over a stub store whose three sources of a committee
    count (checkpoint state, target block state, justified state) a test
    fills in as it needs them."""

    def __init__(self, chain, monkeypatch, *, spec=None, **config):
        self.spec, self.state = spec or chain[0], chain[1]
        spe = int(self.spec.SLOTS_PER_EPOCH)
        self.store = SimpleNamespace(
            checkpoint_states={}, block_states={}, slot=EPOCH * spe + 1,
            justified_checkpoint=Checkpoint(epoch=0, root=b"\x11" * 32),
        )
        self.store.current_slot = lambda spec=None: self.store.slot
        self.forensics, self.pending = Forensics(), []
        self.head_moves, self.batches = 0, []
        self.errors = lambda att: None  # the stubbed verify's answer per vote
        self.metrics = Metrics(enabled=True)
        self.ingest = GossipIngest(IngestContext(
            store=self.store, spec=self.spec, config=NodeConfig(**config),
            metrics=self.metrics, forensics=self.forensics,
            pending=SimpleNamespace(add_block=self.pending.append),
            da=None, slot_clock=None, head_moved=self._head_moved,
        ))
        monkeypatch.setattr(ingest_mod, "on_attestation_batch", self._verify)
        self.cps = accessors.get_committee_count_per_slot(self.state, EPOCH, self.spec)

    def _head_moved(self):
        self.head_moves += 1

    def _verify(self, store, atts, is_from_block, spec, traces):
        assert store is self.store and not is_from_block and len(traces) == len(atts)
        self.batches.append(len(atts))
        return [self.errors(att) for att in atts]

    def authoritative(self, root=ROOT_A):
        self.store.checkpoint_states[(EPOCH, root)] = self.state

    def approximate(self, root=ROOT_A):
        self.store.block_states[root] = self.state

    def vote(self, slot, index, bit=0, *, bits=None, target=ROOT_A, head=ROOT_A, subnet=None):
        flags = [i in ((bit,) if bits is None else bits) for i in range(4)]
        att = Attestation(
            aggregation_bits=flags, signature=SIGNATURE,
            data=AttestationData(
                slot=slot, index=index, beacon_block_root=head,
                source=Checkpoint(epoch=0, root=b"\x00" * 32),
                target=Checkpoint(epoch=EPOCH, root=target)))
        att = Attestation.decode(att.encode(self.spec), self.spec)  # as gossip hands it over
        own = misc.compute_subnet_for_attestation(self.cps, slot, index, self.spec)
        return own if subnet is None else subnet, SimpleNamespace(value=att, trace=None)

    def drain(self, *votes):
        return self.ingest.subnet.drain(list(votes))


SLOT = EPOCH * 8 + 1  # minimal preset: 8 slots an epoch


# ------------------------------------------------------------ subnet channel


@pytest.mark.parametrize("bits", [(0, 1), ()], ids=["two_bits", "zero_bits"])
def test_subnet_vote_without_exactly_one_bit_is_rejected(chain, monkeypatch, bits):
    h = Harness(chain, monkeypatch)
    h.authoritative()
    assert h.drain(h.vote(SLOT, 0, bits=bits)) == [R]
    assert h.batches == []  # nothing reached the batched verify


@pytest.mark.parametrize("authoritative,verdict", [(True, R), (False, I)],
                         ids=["authoritative", "approximate"])
@pytest.mark.parametrize("fault", ["wrong_subnet", "index_beyond_committees"])
def test_subnet_misrouted_vote_penalised_only_on_authoritative_count(
        chain, monkeypatch, fault, authoritative, verdict):
    h = Harness(chain, monkeypatch)
    h.authoritative() if authoritative else h.approximate()
    if fault == "wrong_subnet":
        own = misc.compute_subnet_for_attestation(h.cps, SLOT, 0, h.spec)
        vote = h.vote(SLOT, 0, subnet=(own + 1) % constants.ATTESTATION_SUBNET_COUNT)
    else:
        vote = h.vote(SLOT, h.cps)
    assert h.drain(vote, h.vote(SLOT, 0, 1)) == [verdict, A]
    assert h.batches == [1]


def test_subnet_duplicate_cell_in_one_flush_is_ignored_and_both_roots_noted(chain, monkeypatch):
    h = Harness(chain, monkeypatch)
    h.authoritative()
    assert h.drain(h.vote(SLOT, 0, head=ROOT_A), h.vote(SLOT, 0, head=ROOT_B)) == [A, I]
    assert h.batches == [1]
    (cell_a, root_a), (cell_b, root_b) = h.forensics.votes
    assert cell_a == cell_b and cell_a[:4] == (EPOCH, SLOT, 0, 0)
    assert (root_a, root_b) == (ROOT_A, ROOT_B)  # a double vote stays evidence


@pytest.mark.parametrize("first,second", [(None, I), ("ignore", A), ("reject", A)],
                         ids=["after_accept", "after_ignore", "after_reject"])
def test_subnet_duplicate_across_flushes_is_ignored_only_after_an_accept(
        chain, monkeypatch, first, second):
    h = Harness(chain, monkeypatch)
    h.authoritative()
    error = None if first is None else SimpleNamespace(reject=first == "reject")
    h.errors = lambda att: error
    assert h.drain(h.vote(SLOT, 0)) == [{None: A, "ignore": I, "reject": R}[first]]
    h.errors = lambda att: None
    assert h.drain(h.vote(SLOT, 0)) == [second]


def test_subnet_same_cell_under_another_shuffling_is_not_ignored(chain, monkeypatch):
    h = Harness(chain, monkeypatch)
    h.authoritative(ROOT_A)
    other = h.state.copy(randao_mixes=[b"\x07" * 32] * len(h.state.randao_mixes))
    h.store.checkpoint_states[(EPOCH, ROOT_B)] = other  # a fork with another seed
    assert h.drain(h.vote(SLOT, 0, target=ROOT_A)) == [A]
    assert h.drain(h.vote(SLOT, 0, target=ROOT_B)) == [A]
    assert h.drain(h.vote(SLOT, 0, target=ROOT_B)) == [I]


def test_subnet_provisional_discriminator_upgrades_to_the_seed_and_stays(chain, monkeypatch):
    h = Harness(chain, monkeypatch)
    subnet = h.ingest.subnet
    h.errors = lambda att: SimpleNamespace(reject=False)  # target unknown: not accepted
    assert h.drain(h.vote(SLOT, 0)) == [I]
    assert subnet._vote_cell_disc[(EPOCH, ROOT_A)] == (ROOT_A, False)
    assert subnet._seen_subnet_votes == {}  # no cell under the stand-in
    h.errors = lambda att: None
    h.approximate()  # the target block arrived
    seed = accessors.get_seed(h.state, EPOCH, constants.DOMAIN_BEACON_ATTESTER, h.spec)
    assert h.drain(h.vote(SLOT, 0)) == [A]
    assert subnet._vote_cell_disc[(EPOCH, ROOT_A)] == (seed, True)
    # sticky: another state under the same target never reflows recorded keys
    h.store.block_states[ROOT_A] = h.state.copy(
        randao_mixes=[b"\x09" * 32] * len(h.state.randao_mixes))
    subnet._cps_memo.clear()
    assert h.drain(h.vote(SLOT, 0)) == [I]
    assert subnet._vote_cell_disc[(EPOCH, ROOT_A)] == (seed, True)


def test_subnet_cells_pruned_two_epochs_back_and_gauge_follows(chain, monkeypatch):
    h = Harness(chain, monkeypatch)
    h.authoritative()
    subnet, gauge = h.ingest.subnet, lambda: h.metrics.get("subnet_seen_votes")
    assert h.drain(h.vote(SLOT, 0, 0), h.vote(SLOT, 0, 1), h.vote(SLOT, 1, 0)) == [A, A, A]
    assert gauge() == 3 and set(subnet._seen_subnet_votes) == {EPOCH}
    h.store.slot += 8  # one epoch on: still on gossip
    assert h.drain(h.vote(SLOT + 1, 0, 2)) == [A]
    assert gauge() == 4 and (EPOCH, ROOT_A) in subnet._vote_cell_disc
    h.store.slot += 8  # two epochs on: the next verified flush prunes
    later = h.vote(SLOT, 0, 3)
    later[1].value = later[1].value.copy(data=later[1].value.data.copy(
        target=Checkpoint(epoch=EPOCH + 2, root=ROOT_B)))
    h.store.checkpoint_states[(EPOCH + 2, ROOT_B)] = h.state
    assert h.drain(later) == [A]
    assert set(subnet._seen_subnet_votes) == {EPOCH + 2} and gauge() == 1
    assert set(subnet._vote_cell_disc) == {(EPOCH + 2, ROOT_B)}


def test_aggregate_channel_maps_the_verify_three_ways_and_reports_the_head(chain, monkeypatch):
    h = Harness(chain, monkeypatch)
    answers = {0: None, 1: SimpleNamespace(reject=True), 2: SimpleNamespace(reject=False)}
    h.errors = lambda att: answers[int(att.data.index)]
    batch = [SimpleNamespace(trace=None, value=SimpleNamespace(message=SimpleNamespace(
        aggregate=h.vote(SLOT, i)[1].value))) for i in range(3)]
    assert asyncio.run(h.ingest.aggregate.drain(batch)) == [A, R, I]
    assert h.head_moves == 1 and h.batches == [3]
    assert h.metrics.get("network_gossip_count", type="aggregate_and_proof") == 3


# ------------------------------------------------------- block, blob, tables


@pytest.mark.parametrize("offset,verdict", [(-9, I), (-8, A), (0, A), (8, A), (9, I)])
def test_block_outside_one_epoch_of_the_clock_is_ignored(chain, monkeypatch, offset, verdict):
    h = Harness(chain, monkeypatch)
    block = SignedBeaconBlock(message=BeaconBlock(
        slot=h.store.slot + offset, proposer_index=0, parent_root=ROOT_A,
        state_root=ROOT_B, body=BeaconBlockBody()))
    msg = SimpleNamespace(value=block, trace=None)
    assert asyncio.run(h.ingest.block.drain([msg])) == [verdict]
    assert h.pending == ([block] if verdict == A else [])
    assert h.metrics.get("network_gossip_count", type="beacon_block") == 1


def test_blob_sidecar_misrouted_is_rejected_before_any_pairing(chain, monkeypatch):
    h = Harness(chain, monkeypatch)
    beyond = SimpleNamespace(value=SimpleNamespace(index=6), trace=None)  # MAX_BLOBS_PER_BLOCK
    wrong = SimpleNamespace(value=SimpleNamespace(index=1), trace=None)
    assert h.ingest.blob.drain([(0, beyond), (2, wrong)]) == [R, R]


@pytest.mark.parametrize("fork,blobs", [("capella", 0), ("deneb", 2)])
def test_topic_table_follows_the_fork_and_the_subscription(chain, monkeypatch, fork, blobs):
    spec = chain[0].replace(DENEB_FORK_EPOCH=0) if fork == "deneb" else chain[0]
    h = Harness(chain, monkeypatch, spec=spec, attnet_subnets=(5, 3, 3), blob_subnets=(1, 4))
    rows = h.ingest.topic_table()
    assert [r.name for r in rows if r.since_fork != "deneb"] == [
        "beacon_block", "beacon_aggregate_and_proof",
        "beacon_attestation_3", "beacon_attestation_5"]
    assert [r.name for r in rows if r.since_fork == "deneb"] == [
        "blob_sidecar_1", "blob_sidecar_4"][:blobs]
    for row in rows:  # a row flushes to its topic's handler or to its lane's one sink
        assert (row.handler is None) != (row.sink is None)
    assert {r.sink.label for r in rows if r.sink} == {
        "subnet_lane", "blob_lane"} - ({"blob_lane"} if not blobs else set())
    with pytest.raises(ValueError):
        Harness(chain, monkeypatch, attnet_subnets=(64,)).ingest.topic_table()


class FakePort:
    def __init__(self):
        self.verdicts: dict[bytes, int] = {}

    async def subscribe(self, topic, handler):
        pass

    async def validate_message(self, msg_id, verdict):
        self.verdicts[msg_id] = verdict

    @contextlib.asynccontextmanager
    async def verdict_batch(self):
        yield


def test_every_channels_lane_in_priority_order_sized_to_the_subscription(chain, monkeypatch):
    async def main():
        h = Harness(chain, monkeypatch, ingest_max_items=100)
        monkeypatch.setattr(ingest_mod, "ATT_QUEUE", 4)
        sched = h.ingest.build_scheduler()
        lanes = sched.snapshot()["lanes"]
        assert [(lane["name"], lane["priority"]) for lane in lanes] == [
            ("block", 0), ("blob", 1), ("aggregate", 2), ("subnet", 3), ("other", 4)]
        floor = {lane["name"]: lane["capacity"] for lane in lanes}
        assert floor["subnet"] == floor["aggregate"] == 4 and sched.max_items == 100
        # a justified state answers: 64 subnets x 2 committees x 4 members
        h.store.block_states[bytes(h.store.justified_checkpoint.root)] = h.state
        h.ingest.ctx.config.attnet_subnets = tuple(range(64))
        sched = h.ingest.build_scheduler()
        subnet = next(x for x in sched.snapshot()["lanes"] if x["name"] == "subnet")
        assert subnet["capacity"] == 8 and sched.max_items == 104

    asyncio.run(main())


def test_first_seen_cells_survive_what_a_sidecar_restart_rebuilds(chain, monkeypatch):
    """``BeaconNode._start_network`` on a restart: a new port, a new scheduler,
    new subscriptions — and the same channels.  The duplicate of a vote
    accepted before the restart is IGNOREd after it."""

    async def main():
        h = Harness(chain, monkeypatch, ingest_attestation_deadline_ms=5,
                    attnet_subnets=tuple(range(64)))
        h.authoritative()
        subnet, vote = h.vote(SLOT, 0)
        payload = compress(vote.value.encode(h.spec))
        verdicts = []
        for msg_id in (b"before", b"after"):
            port, sched = FakePort(), h.ingest.build_scheduler()
            sched.start()
            subs = [await h.ingest.subscribe(row, port, b"\x00" * 4, sched)
                    for row in h.ingest.topic_table()]
            sub = next(s for s in subs if s.topic_label == f"beacon_attestation_{subnet}")
            await sub._on_gossip(sub.topic, msg_id, payload, b"peer")
            for _ in range(400):
                if msg_id in port.verdicts:
                    break
                await asyncio.sleep(0.005)
            verdicts.append(port.verdicts.get(msg_id))
            await sched.stop()
        assert verdicts == [A, I]
        assert h.batches == [1]  # the second never reached the verify

    asyncio.run(main())
