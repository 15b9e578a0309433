"""Gossip ingest: the channels a node drains gossip through.

``node/node.py`` (lifecycle) builds ``GossipIngest`` once per node from an
``IngestContext``; below it are ``network.gossip``, ``pipeline``,
``fork_choice``, ``node.pending_blocks`` and ``da``.  A channel is ONE
object: its topic rows, the ``LaneConfig`` of its lane, its drain and the
state its validation rules keep.  The channels live as long as the node: a
network (re)start rebuilds the scheduler and the subscriptions from them
(``BeaconNode._start_network``) and keeps their state — first-seen vote
cells, discriminators and memos survive a sidecar restart.  A new channel
costs one class here and one entry in ``GossipIngest.channels``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Awaitable, Callable

from ..config import ChainSpec, constants
from ..config.presets import FORK_ORDER
from ..da import DataAvailability, trusted_setup, verify_blob_batch, verify_blob_proof
from ..fork_choice import ConsensusForensics, Store, attestation_batch_target, on_attestation_batch
from ..fork_choice.store import checkpoint_key
from ..network.gossip import SharedLaneSink, TopicSubscription, topic_name
from ..network.port import VERDICT_ACCEPT, VERDICT_IGNORE, VERDICT_REJECT
from ..pipeline import IngestScheduler, LaneConfig
from ..state_transition import accessors, misc
from ..state_transition.mutable import BeaconStateMut
from ..telemetry import Metrics, inc, span
from ..tracing import SlotClock, observe_block_arrival
from ..types.beacon import Attestation, SignedBeaconBlock
from ..types.deneb import BlobSidecar
from ..types.validator import SignedAggregateAndProof
from .pending_blocks import PendingBlocks

# attestation channels take deep batches: the device drain's fixed
# dispatch cost amortizes across thousands of signatures, and one mainnet
# slot already carries ~1k aggregates
ATT_BATCH, ATT_QUEUE = 8192, 16384


@dataclass
class IngestContext:
    """What the channels need of the node, handed over once.  No channel
    holds the ``BeaconNode``."""

    store: Store
    spec: ChainSpec
    config: object  # NodeConfig: subscriptions, lane deadlines, the budget
    metrics: Metrics  # the node's own registry (per-node gauges, counters)
    forensics: ConsensusForensics
    pending: PendingBlocks | None
    da: DataAvailability | None
    slot_clock: SlotClock | None
    # votes or a block may have moved the head
    head_moved: Callable[[], None]


@dataclass(frozen=True)
class TopicSpec:
    """One row of the fork-aware gossip topic table: forks only ADD rows,
    and a row joins the mesh when the chain's current fork
    (``spec.fork_at_epoch``) has reached ``since_fork``.  A row has a
    ``handler`` (its topic's own flushes) or a ``sink`` (one flush
    spanning every topic of its lane), both bound to its channel."""

    name: str  # short topic name (topic_name() adds digest + ssz_snappy)
    ssz_type: object
    lane: str  # ingest-scheduler lane
    handler: Callable[[list], Awaitable[list[int]]] | None = None
    sink: SharedLaneSink | None = None
    since_fork: str = "phase0"


def _subnet_ids(wanted, count: int, family: str) -> tuple[int, ...]:
    """Subscribed subnet ids, deduped (``Port.subscribe`` is keyed by
    topic: a duplicated id would orphan one drain loop and double-subscribe
    the sidecar) and range-checked — at startup, not inside the
    sidecar-restart loop."""
    ids = tuple(sorted({int(i) for i in wanted}))
    for i in ids:
        if not 0 <= i < count:
            raise ValueError(f"{family} subnet id out of range: {i}")
    return ids


def attestation_subnet_ids(config) -> tuple[int, ...]:
    return _subnet_ids(config.attnet_subnets, constants.ATTESTATION_SUBNET_COUNT, "attestation")


def _subnet_of(sub: TopicSubscription) -> int:
    """A subnet-family topic's id, from its name (``beacon_attestation_{i}``,
    ``blob_sidecar_{i}``): a subscription needs no side-channel attribute
    to join its lane's sink."""
    return int(sub.topic_label.rsplit("_", 1)[1])


def attestation_drain(ctx: IngestContext, batch, extract, metric_type: str) -> list[int]:
    """Shared drain of both attestation channels: one batched RLC
    signature check (fork_choice.on_attestation_batch) and the three-way
    verdict mapping — invalid signatures REJECT (the sidecar downscores
    and eventually disconnects the sender)."""
    ctx.metrics.inc("network_gossip_count", value=len(batch), type=metric_type)
    results = on_attestation_batch(
        ctx.store,
        [extract(msg) for msg in batch],
        is_from_block=False,
        spec=ctx.spec,
        # fan-in link: the ONE batched verify span records its member
        # item traces (and each accepted member observes the
        # admission->apply slot-phase histogram)
        traces=[msg.trace for msg in batch],
    )
    # an attestation batch can reorg the head onto an already-applied
    # block with no block apply involved — observe that too
    ctx.head_moved()
    return [
        VERDICT_ACCEPT
        if err is None
        else (VERDICT_REJECT if getattr(err, "reject", False) else VERDICT_IGNORE)
        for err in results
    ]


def _attestation_lane(ctx: IngestContext, name: str, priority: int) -> LaneConfig:
    """The attestation lanes coalesce to the device path's minimum
    worthwhile batch (fork_choice.attestation_batch_target) and snap
    flush sizes to the AOT-warmed shape buckets; their deficit weights
    keep them from starving each other."""
    return LaneConfig(
        name=name, priority=priority, weight=4096, max_batch=ATT_BATCH, max_queue=ATT_QUEUE,
        deadline_s=ctx.config.ingest_attestation_deadline_ms / 1000.0,
        coalesce_target=min(attestation_batch_target(), ATT_BATCH),
        shape_kind="attestation_entries",
    )


class BlockChannel:
    """``beacon_block``: gossip blocks -> the pending set (signature
    verification happens in on_block).  Strict priority keeps block
    import latency bounded under any attestation flood."""

    def __init__(self, ctx: IngestContext):
        self.ctx = ctx

    def lane_config(self) -> LaneConfig:
        return LaneConfig(
            name="block", priority=0, weight=64, max_batch=64, max_queue=1024,
            deadline_s=self.ctx.config.ingest_block_deadline_ms / 1000.0,
            coalesce_target=1,
            # blocks chain parent-first: a full lane drops the incoming
            # message rather than evicting a queued ancestor and
            # orphaning its descendants
            shed_newest=True,
        )

    def rows(self) -> list[TopicSpec]:
        return [TopicSpec(
            name="beacon_block", ssz_type=SignedBeaconBlock, lane="block", handler=self.drain
        )]

    async def drain(self, batch) -> list[int]:
        ctx = self.ctx
        verdicts = []
        head_slot = ctx.store.current_slot(ctx.spec)
        for msg in batch:
            block = msg.value
            ctx.metrics.inc("network_gossip_count", type="beacon_block")
            if ctx.slot_clock is not None:
                # arrival offset into the block's OWN slot: the slot-
                # phase histogram that says whether blocks reach us in
                # time to attest (decode follows admission within the
                # flush deadline, so this is admission-accurate)
                offset = observe_block_arrival(ctx.slot_clock, int(block.message.slot))
                # weight-event log: a late block that later flips the
                # head is named (with this offset) in the ReorgRecord's
                # attribution.  No root here — merkleizing on the gossip
                # admission path would break the O(1)-per-event budget;
                # the forensic join keys on (slot, arrival offset).
                ctx.forensics.note_block_arrival(None, int(block.message.slot), offset)
                if msg.trace is not None:
                    msg.trace.event(
                        "slot_phase", slot=int(block.message.slot), offset_s=round(offset, 4)
                    )
            # within-one-epoch window check (ref: gossip_handler.ex:21)
            if abs(block.message.slot - head_slot) <= ctx.spec.SLOTS_PER_EPOCH:
                ctx.pending.add_block(block)
                if msg.trace is not None:
                    msg.trace.event("apply", kind="pending_queue")
                verdicts.append(VERDICT_ACCEPT)
            else:
                verdicts.append(VERDICT_IGNORE)
        return verdicts


class BlobChannel:
    """``blob_sidecar_{i}`` (since deneb): one topic per sampled column,
    one shared lane — a flush verifies in a single RLC-folded pairing
    check and feeds the DA gate."""

    def __init__(self, ctx: IngestContext):
        self.ctx = ctx
        self.sink = SharedLaneSink(self._on_flush, label="blob_lane")

    def lane_config(self) -> LaneConfig:
        # between blocks and attestations: a block cannot apply until its
        # sampled columns verify, so sidecars must not starve behind an
        # attestation flood — but they coalesce to a block's worth so a
        # flush is ONE pairing check, under a deadline that does not hold
        # block import hostage.  A full lane sheds the incoming message
        # (withholding adversaries must not evict queued honest sidecars).
        return LaneConfig(
            name="blob", priority=1, weight=64, max_batch=64, max_queue=1024,
            deadline_s=self.ctx.config.ingest_blob_deadline_ms / 1000.0,
            coalesce_target=int(self.ctx.spec.get("MAX_BLOBS_PER_BLOCK", 6)),
            shed_newest=True,
        )

    def subnet_ids(self) -> tuple[int, ...]:
        count = int(self.ctx.spec.get("BLOB_SIDECAR_SUBNET_COUNT", 6))
        wanted = self.ctx.config.blob_subnets  # None: every subnet (a full-DA node)
        return _subnet_ids(range(count) if wanted is None else wanted, count, "blob")

    def rows(self) -> list[TopicSpec]:
        return [
            TopicSpec(f"blob_sidecar_{i}", BlobSidecar, "blob", sink=self.sink, since_fork="deneb")
            for i in self.subnet_ids()
        ]

    async def _on_flush(self, pairs) -> list[int]:
        return self.drain([(_subnet_of(sub), msg) for sub, msg in pairs])

    def drain(self, tagged) -> list[int]:
        """blob_sidecar_{i} gossip validation (p2p spec deneb) of
        ``(subnet, message)`` pairs:

        - REJECT structurally misrouted sidecars (index beyond
          MAX_BLOBS_PER_BLOCK, or on the wrong subnet for its index) —
          compliant peers penalize a node that re-propagates these
        - REJECT commitment-linkage mismatches against a block's
          advertised commitment list (the DA gate's expectation)
        - the whole flush's KZG proofs fold into ONE pairing check
          (da.kzg.verify_blob_batch); only a failing fold pays the
          per-item bisect, so the all-valid common case is one pairing
        - verified sidecars feed the DA gate: the sidecar that completes
          a block's sampled column set unparks it in pending-blocks
        """
        ctx = self.ctx
        spec, da = ctx.spec, ctx.da
        max_blobs = int(spec.get("MAX_BLOBS_PER_BLOCK", 6))
        subnet_count = int(spec.get("BLOB_SIDECAR_SUBNET_COUNT", 6))
        verdicts: list[int | None] = [None] * len(tagged)
        items = []  # (pos, root, sidecar, msg)
        for pos, (subnet, msg) in enumerate(tagged):
            sc = msg.value
            ctx.metrics.inc("network_gossip_count", type="blob_sidecar")
            index = int(sc.index)
            if index >= max_blobs or index % subnet_count != subnet:
                verdicts[pos] = VERDICT_REJECT
                continue
            root = sc.signed_block_header.message.hash_tree_root(spec)
            # linkage pre-check against an already-registered block
            # expectation: an advertised-commitment mismatch REJECTs
            # before paying for the pairing check
            expected = da.expected_commitment(root, index)
            if expected is not None and expected != bytes(sc.kzg_commitment):
                inc("da_sidecars_total", 1, result="mismatch")
                verdicts[pos] = VERDICT_REJECT
                continue
            items.append((pos, root, sc, msg))
        if items:
            setup = trusted_setup(spec)  # built once per width (da.kzg.dev_setup)
            blobs = [bytes(sc.blob) for _, _, sc, _ in items]
            comms = [bytes(sc.kzg_commitment) for _, _, sc, _ in items]
            proofs = [bytes(sc.kzg_proof) for _, _, sc, _ in items]
            if verify_blob_batch(blobs, comms, proofs, setup=setup):
                ok = [True] * len(items)
            else:
                # one bad sidecar must not take honest flush-mates down
                # with it: re-check each item on its own
                ok = [
                    verify_blob_proof(b, c, p, setup=setup) for b, c, p in zip(blobs, comms, proofs)
                ]
            for (pos, root, sc, msg), valid in zip(items, ok):
                if not valid:
                    verdicts[pos] = VERDICT_REJECT
                    continue
                linkage = da.on_sidecar(root, int(sc.index), bytes(sc.kzg_commitment))
                if linkage == "mismatch":
                    verdicts[pos] = VERDICT_REJECT
                elif linkage == "duplicate":
                    verdicts[pos] = VERDICT_IGNORE
                else:  # accept | complete | orphan (block not seen yet)
                    verdicts[pos] = VERDICT_ACCEPT
                if msg.trace is not None and linkage == "complete":
                    msg.trace.event("apply", kind="da_complete")
        return [VERDICT_IGNORE if v is None else v for v in verdicts]


class AggregateChannel:
    """``beacon_aggregate_and_proof``: the inner aggregates of a flush in
    one batched verify."""

    def __init__(self, ctx: IngestContext):
        self.ctx = ctx

    def lane_config(self) -> LaneConfig:
        return _attestation_lane(self.ctx, "aggregate", 2)

    def rows(self) -> list[TopicSpec]:
        return [TopicSpec(
            name="beacon_aggregate_and_proof", ssz_type=SignedAggregateAndProof,
            lane="aggregate", handler=self.drain,
        )]

    async def drain(self, batch) -> list[int]:
        return attestation_drain(
            self.ctx, batch, lambda msg: msg.value.message.aggregate, "aggregate_and_proof"
        )


class SubnetChannel:
    """``beacon_attestation_{i}``: unaggregated votes of every subscribed
    subnet on ONE shared lane (a flood on any subnet competes with the
    other subnets, never with blocks) and in ONE flush: all votes land in
    a single batched RLC verify instead of per-topic fragments."""

    def __init__(self, ctx: IngestContext):
        self.ctx = ctx
        self.sink = SharedLaneSink(self._on_flush, label="subnet_lane")
        # committees-per-slot + shuffling seed memo per target
        self._cps_memo: dict[tuple[int, bytes], tuple[int, bool, bytes]] = {}
        self._cps_fallback_memo: dict[tuple[int, bytes], tuple[int, bytes]] = {}
        # per-target vote-cell discriminator, (value, is_seed): see drain()
        self._vote_cell_disc: dict[tuple[int, bytes], tuple[bytes, bool]] = {}
        # the one-vote-per-validator-per-epoch IGNORE cache: epoch -> cells
        self._seen_subnet_votes: dict[int, set] = {}

    def lane_config(self) -> LaneConfig:
        # its capacity here is the floor: lane_bounds() sizes the live lane
        return _attestation_lane(self.ctx, "subnet", 3)

    def lane_bounds(self) -> tuple[int, int]:
        """``(subnet lane capacity, scheduler budget)`` for the current
        subscription: one slot's unaggregated votes of every subscribed
        subnet must fit — the committees a slot lands on the subscribed
        subnets times the committee size the justified checkpoint state
        gives — so that a valid first-seen vote is never shed while the
        lane is the only one loaded.  Never under the aggregate lane's
        depth; the budget keeps its distance below the sum of the lane
        caps (``NodeConfig.ingest_max_items``) and grows by what the lane
        grew, so the cross-lane shed policy engages as before."""
        ctx = self.ctx
        spec, store = ctx.spec, ctx.store
        queue = ATT_QUEUE
        state = store.block_states.get(bytes(store.justified_checkpoint.root))
        if state is not None:
            epoch = misc.compute_epoch_at_slot(store.current_slot(spec), spec)
            ws = BeaconStateMut(state)
            cps = accessors.get_committee_count_per_slot(ws, epoch, spec)
            slots = int(spec.SLOTS_PER_EPOCH)
            committee = -(-len(ws.active_indices(epoch)) // (cps * slots))
            per_subnet = -(-cps // constants.ATTESTATION_SUBNET_COUNT)
            committees = min(cps, len(attestation_subnet_ids(ctx.config)) * per_subnet)
            queue = max(queue, committees * committee)
        return queue, ctx.config.ingest_max_items + queue - ATT_QUEUE

    @staticmethod
    def topic(subnet: int) -> str:
        return f"beacon_attestation_{subnet}"

    def row(self, subnet: int) -> TopicSpec:
        return TopicSpec(
            name=self.topic(subnet), ssz_type=Attestation, lane="subnet", sink=self.sink
        )

    def rows(self) -> list[TopicSpec]:
        return [self.row(i) for i in attestation_subnet_ids(self.ctx.config)]

    def publish_topic(self, att, committees_per_slot: int) -> str | None:
        """The topic an own vote goes out on; None when its subnet is not
        subscribed (a publish to a mesh we are not part of would be dropped)."""
        subnet = misc.compute_subnet_for_attestation(
            committees_per_slot, int(att.data.slot), int(att.data.index), self.ctx.spec
        )
        return self.topic(subnet) if subnet in self.ctx.config.attnet_subnets else None

    async def _on_flush(self, pairs) -> list[int]:
        return self.drain([(_subnet_of(sub), msg) for sub, msg in pairs])

    def _committees_per_slot_at(self, target) -> tuple[int, bool, bytes] | None:
        """``(committees_per_slot, authoritative, shuffling_seed)`` for the
        target epoch.

        ``authoritative`` is True only when the materialized checkpoint
        state answered — approximations (target block's post-state, the
        justified state during sync) can cross a committee-count boundary,
        and a REJECT issued from one would penalize honest peers, so the
        caller must downgrade mismatches to IGNORE for those.  A
        non-authoritative memo entry upgrades itself once the checkpoint
        state materializes.  The attester shuffling seed rides along (from
        the same resolved state) as the one-vote-cell discriminator."""
        store, spec = self.ctx.store, self.ctx.spec
        key = checkpoint_key(target)
        hit = self._cps_memo.get(key)
        if hit is not None and (hit[1] or key not in store.checkpoint_states):
            return hit
        epoch = int(target.epoch)
        state = store.checkpoint_states.get(key)
        authoritative = state is not None
        if state is None:
            state = store.block_states.get(bytes(target.root))
        if state is None:
            # sync-time fallback: the justified state, memoized under its
            # own key so gossip doesn't pay an O(registry) active-set scan
            # per message while targets are still being fetched
            jroot = bytes(store.justified_checkpoint.root)
            fhit = self._cps_fallback_memo.get((epoch, jroot))
            if fhit is not None:
                return fhit[0], False, fhit[1]
            jstate = store.block_states.get(jroot)
            if jstate is None:
                return None
            cps = accessors.get_committee_count_per_slot(jstate, epoch, spec)
            seed = accessors.get_seed(jstate, epoch, constants.DOMAIN_BEACON_ATTESTER, spec)
            if len(self._cps_fallback_memo) > 64:
                self._cps_fallback_memo.clear()
            self._cps_fallback_memo[(epoch, jroot)] = (cps, seed)
            return cps, False, seed
        cps = accessors.get_committee_count_per_slot(state, epoch, spec)
        seed = accessors.get_seed(state, epoch, constants.DOMAIN_BEACON_ATTESTER, spec)
        if len(self._cps_memo) > 64:
            self._cps_memo.clear()
        self._cps_memo[key] = (cps, authoritative, seed)
        return cps, authoritative, seed

    def drain(self, tagged) -> list[int]:
        """Subnet gossip validation (p2p spec beacon_attestation_{i}: without
        these REJECTs the node re-propagates misrouted messages compliant
        peers penalize) of ``(subnet, message)`` pairs, then the shared
        batched drain:

        - REJECT unless exactly one aggregation bit is set
        - REJECT when the committee maps to a different subnet
        - IGNORE duplicate (validator, epoch) votes — keyed by the
          (epoch, slot, index, bit, shuffling-seed) cell.  The cell only
          pins one validator per epoch UNDER ONE SHUFFLING: the seed
          discriminates competing forks whose different shufflings put a
          DIFFERENT validator in the same (slot, index, bit) cell (an
          honest first-seen vote on the other fork is not IGNOREd), while
          forks that share the shuffling (divergence after the seed's
          randao mix) still collide — the same validator's second vote at
          one epoch stays IGNOREd, as the p2p spec requires.  The
          discriminator is sticky once seed-derived (recorded cell keys
          must never reflow); a provisional target-root stand-in (no
          state can answer yet) upgrades to the seed, which is safe
          because only ACCEPTed votes record cells and acceptance
          requires the target block — hence a seed source — to be known
        """
        ctx = self.ctx
        verdicts: list[int | None] = [None] * len(tagged)
        passed, passed_pos, passed_keys = [], [], []
        batch_keys: set = set()  # dedupe same-validator cells WITHIN the batch
        # the p2p rules, once per flush (the batched verify below has its
        # own spans)
        with span("subnet_validate"):
            for pos, (subnet, msg) in enumerate(tagged):
                att = msg.value
                bits = att.aggregation_bits
                if bits.count() != 1:
                    verdicts[pos] = VERDICT_REJECT
                    continue
                cps_auth = self._committees_per_slot_at(att.data.target)
                seed = None
                if cps_auth is not None:
                    cps, authoritative, seed = cps_auth
                    if int(att.data.index) >= cps or misc.compute_subnet_for_attestation(
                        cps, int(att.data.slot), int(att.data.index), ctx.spec
                    ) != subnet:
                        # approximate committee counts can mis-map honest
                        # messages across a count boundary — only the real
                        # checkpoint state justifies penalizing the sender
                        verdicts[pos] = VERDICT_REJECT if authoritative else VERDICT_IGNORE
                        continue
                epoch = int(att.data.target.epoch)
                tkey = (epoch, bytes(att.data.target.root))
                hit = self._vote_cell_disc.get(tkey)
                if hit is not None and hit[1]:
                    disc = hit[0]  # seed-derived: sticky, keys never reflow
                elif seed is not None:
                    # first seed-based resolution (or an upgrade from the
                    # provisional stand-in — no cells were recorded under it:
                    # ACCEPT requires the target block, hence a seed source)
                    disc = seed
                    self._vote_cell_disc[tkey] = (seed, True)
                else:
                    # no state to derive the seed from yet: the target root is
                    # the coarser stand-in (never merges distinct shufflings)
                    disc = bytes(att.data.target.root)
                    self._vote_cell_disc[tkey] = (disc, False)
                key = (int(att.data.slot), int(att.data.index), bits.indices()[0], disc)
                if key in self._seen_subnet_votes.get(epoch, ()) or (epoch, key) in batch_keys:
                    verdicts[pos] = VERDICT_IGNORE
                    # the IGNORE is correct for fork choice, but a duplicate
                    # cell carrying a DIFFERENT head root is a double vote —
                    # retained as ledger evidence instead of vanishing here
                    ctx.forensics.note_vote((epoch,) + key, bytes(att.data.beacon_block_root))
                    continue
                batch_keys.add((epoch, key))
                # first-seen root for the cell, recorded BEFORE the verify
                # verdict lands so a same-batch twin still compares roots
                ctx.forensics.note_vote((epoch,) + key, bytes(att.data.beacon_block_root))
                passed.append(msg)
                passed_pos.append(pos)
                passed_keys.append((epoch, key))
        if passed:
            inner = attestation_drain(ctx, passed, lambda msg: msg.value, "beacon_attestation")
            current_epoch = misc.compute_epoch_at_slot(ctx.store.current_slot(ctx.spec), ctx.spec)
            for pos, verdict, (epoch, key) in zip(passed_pos, inner, passed_keys):
                verdicts[pos] = verdict
                if verdict == VERDICT_ACCEPT:
                    self._seen_subnet_votes.setdefault(epoch, set()).add(key)
            # prune epochs that can no longer appear on gossip
            for epoch in [e for e in self._seen_subnet_votes if e < current_epoch - 1]:
                del self._seen_subnet_votes[epoch]
            for tkey in [k for k in self._vote_cell_disc if k[0] < current_epoch - 1]:
                del self._vote_cell_disc[tkey]
            ctx.metrics.set_gauge(
                "subnet_seen_votes",
                sum(len(cells) for cells in self._seen_subnet_votes.values()),
            )
        return verdicts


class GossipIngest:
    """A node's gossip channels, in lane-priority order (blocks > blob
    sidecars > aggregates > subnet attestations), and what a network
    (re)start builds from them: the scheduler, the topic table and the
    subscriptions."""

    def __init__(self, ctx: IngestContext):
        self.ctx = ctx
        self.block = BlockChannel(ctx)
        self.blob = BlobChannel(ctx)
        self.aggregate = AggregateChannel(ctx)
        self.subnet = SubnetChannel(ctx)
        self.channels = (self.block, self.blob, self.aggregate, self.subnet)

    def build_scheduler(self) -> IngestScheduler:
        """One priority drain over every topic (pipeline/): strict
        priority order between the channels' lanes, deficit weights
        within it."""
        sched = IngestScheduler(metrics=self.ctx.metrics)
        for channel in self.channels:
            sched.add_lane(channel.lane_config())
        # catch-all for non-core topics (sync committees, slashings, BLS
        # changes — future subscriptions); empty until one is wired, and
        # kept out of the budget picture by the explicit budget below
        sched.add_lane(LaneConfig(
            name="other", priority=4, weight=64, max_batch=64, max_queue=1024,
            deadline_s=0.2, coalesce_target=16,
        ))
        # the subnet lane and the budget follow the subscription, here as
        # at a run-time change (BeaconNode.set_attestation_subnets)
        sched.resize_lane("subnet", *self.subnet.lane_bounds())
        return sched

    def topic_table(self) -> list[TopicSpec]:
        """The gossip surface at the chain's CURRENT fork: rows gated
        behind a later fork (deneb blob sidecars) join at the first
        network (re)start after the chain reaches it."""
        spec = self.ctx.spec
        epoch = int(self.ctx.store.current_slot(spec)) // int(spec.SLOTS_PER_EPOCH)
        active_fork = FORK_ORDER.index(spec.fork_at_epoch(epoch))
        return [
            row for channel in self.channels for row in channel.rows()
            if FORK_ORDER.index(row.since_fork) <= active_fork
        ]

    async def subscribe(
        self, row: TopicSpec, port, fork_digest: bytes, scheduler: IngestScheduler
    ) -> TopicSubscription:
        """Join one row: its subscription on ``port``, producing into its
        lane of ``scheduler`` (a lane-shared row's flushes go to its
        channel's sink; its own handler is never called)."""
        sub = TopicSubscription(
            port, topic_name(fork_digest, row.name), row.handler,
            ssz_type=row.ssz_type, spec=self.ctx.spec, metrics=self.ctx.metrics,
            scheduler=scheduler, lane=row.lane, sink=row.sink,
            node=self.ctx.config.node_label,
        )
        await sub.start()
        return sub
