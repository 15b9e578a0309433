#!/usr/bin/env python3
"""chip_smoke.py — the beacon node's served path, once, on one TPU chip.

Boots a real ``BeaconNode`` (the objects ``node/__main__.py`` builds) on an
unmodified mainnet preset with 2^20 validators, pushes three slots of
gossip aggregates and two signed capella blocks through the node's own
entry points, queries it over HTTP, and holds every device verdict and
root to a plain host oracle (native BLS library, ``hashlib`` Merkleization,
the non-resident transition).  Each phase prints one JSON line; any failure
exits non-zero and the last line is not printed.

    python chip_smoke.py               # one chip; refuses to start off-chip
    python chip_smoke.py --chips 4     # the sharded programs against their
                                       # single-device twins, nothing else
    python chip_smoke.py --rehearse    # CPU rehearsal at a tiny size
                                       # (minimal preset): control flow only

The last line of standard output is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
One process touches JAX; the network sidecar child stays on the CPU.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import faulthandler
import functools
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
TIME_LIMIT_S = 1200


class SmokeFailure(Exception):
    pass


def expect(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0, help="keys, draws, tampering")
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4 = only the sharded programs and what they are "
                        "compared with")
    p.add_argument("--rehearse", action="store_true",
                   help="run on the CPU at a tiny size (minimal preset, "
                        "interpret-mode kernels): a rehearsal, never a result")
    return p.parse_args(argv)


def steer_rehearsal(chips: int) -> None:
    """The CPU rehearsal steers the package from here, through knobs it
    already has — never through a fallback inside the package.  Must run
    before the first import of jax or the package."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={chips}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ.update({
        "BLS_RLC_BITS": "16",  # a quarter of the ladder steps
        "BLS_DEVICE_CHAIN_MIN": "4",  # tiny drains still take the device chain
        "BLS_BLOCK_BATCH_MIN_MEMBERS": "1",
        "GRAFT_RESIDENT_EPOCH": "1",  # on by itself only above 16,384 validators
    })
    if chips > 1:  # a 128-block tree is under the sharded tree's own floor
        os.environ["SSZ_SHARD"] = "1"


def build_native() -> dict:
    """``make -C native`` from the committed sources; both libraries must
    load afterwards — this path has no pure-Python BLS or KV."""
    t0 = time.perf_counter()
    subprocess.run(["make", "-C", os.path.join(HERE, "native")], check=True,
                   stdout=subprocess.DEVNULL)
    from lambda_ethereum_consensus_tpu.crypto.bls import native
    from lambda_ethereum_consensus_tpu.store import kv

    expect(native.available() and native.rlc_available()
           and native.decompress_available() and native.final_exp_available(),
           "native/build/libbls381.so missing or refused")
    expect(kv._NATIVE is not None, "native/build/libkvstore.so missing or refused")
    return {"native_build_s": round(time.perf_counter() - t0, 2)}


class CompileClock:
    """Where a phase's seconds went: JAX's own trace/lower/compile events
    (every jit, AOT-wrapped or not), persistent-cache hits, and the AOT
    tier's loads — so each phase reports compile/load apart from run."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __init__(self):
        import jax.monitoring as mon

        self.t = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0}
        self.n = {"jax_cache_hits": 0, "jax_cache_misses": 0}
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._evt)

    def _dur(self, name, secs, **_kw):
        key = self.EVENTS.get(name)
        if key:
            self.t[key] += secs

    def _evt(self, name, **_kw):
        if name.endswith("/cache_hits"):
            self.n["jax_cache_hits"] += 1
        elif name.endswith("/cache_misses"):
            self.n["jax_cache_misses"] += 1

    def snapshot(self) -> dict:
        from lambda_ethereum_consensus_tpu.ops.aot import aot_stats, compile_profile

        stats = aot_stats()
        return {
            **self.t, **self.n,
            "aot_loads": stats["loads"],
            "aot_lowers": stats["retraces"],
            "aot_saves": stats["saves"],
            "aot_load_s": sum(r["load_seconds"] for r in compile_profile()),
        }

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: round(b[k] - a[k], 2) if isinstance(b[k], float) else b[k] - a[k]
                for k in b}


@contextlib.contextmanager
def phase(n: int, name: str, clock: CompileClock):
    """Time one phase and print its line; the body fills ``out``."""
    out: dict = {}
    before, t0 = clock.snapshot(), time.perf_counter()
    yield out
    secs = time.perf_counter() - t0
    d = CompileClock.delta(before, clock.snapshot())
    # compile-side seconds overlap when the warmer thread compiles beside
    # the main thread, so "run" is a floor at zero, not a residue
    compile_side = d["trace_s"] + d["lower_s"] + d["compile_s"] + d["aot_load_s"]
    emit({"phase": n, "name": name, "seconds": round(secs, 2),
          "compile_load_s": round(compile_side, 2),
          "run_s": round(max(secs - compile_side, 0.0), 2), **d, **out})


@functools.cache
def pkg() -> types.SimpleNamespace:
    """The package names the oracle helpers share — imported on first use,
    after ``main`` has steered the environment and found the repository."""
    import numpy as np

    from lambda_ethereum_consensus_tpu.config import constants
    from lambda_ethereum_consensus_tpu.crypto.bls import curve
    from lambda_ethereum_consensus_tpu.crypto.bls.batch import batch_verify_each_points
    from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (
        DST_POP, hash_to_g2,
    )
    from lambda_ethereum_consensus_tpu.ssz.hash import HashlibBackend, set_hash_backend
    from lambda_ethereum_consensus_tpu.state_transition import accessors, misc
    from lambda_ethereum_consensus_tpu.state_transition.mutable import BeaconStateMut
    from lambda_ethereum_consensus_tpu.types.beacon import (
        Attestation, AttestationData, Checkpoint,
    )

    return types.SimpleNamespace(**locals())


@contextlib.contextmanager
def host_routing():
    """The plain reference's routing: hashlib Merkleization, the
    non-resident transition, BLS on the native host library.  Steered from
    here, for the oracle's stretch of this thread only."""
    keys = {"BLS_NO_DEVICE": "1", "GRAFT_RESIDENT_EPOCH": "0"}
    saved = {k: os.environ.get(k) for k in keys}
    prev = pkg().set_hash_backend(pkg().HashlibBackend())
    os.environ.update(keys)
    try:
        yield
    finally:
        pkg().set_hash_backend(prev)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class Keys:
    """A registry of ``n`` validators cycling 64 seeded keys (minting 2^20
    distinct keys on the host would dominate set-up); aggregate secrets and
    public keys come from the cycle counts."""

    def __init__(self, seed: int, n: int):
        rng = random.Random(seed)
        self.C = C = pkg().curve
        self.sks = [rng.randrange(1, C.R) for _ in range(64)]
        self.pks = [C.g1_to_bytes(C.g1.multiply_raw(C.G1_GENERATOR, sk))
                    for sk in self.sks]
        self.n = n

    def pubkeys(self) -> list[bytes]:
        return [self.pks[i % 64] for i in range(self.n)]

    def __getitem__(self, i: int) -> bytes:  # secret_keys[i] for block signing
        return self.sks[i % 64].to_bytes(32, "big")

    def sync_keys(self) -> dict:
        return {pk: sk.to_bytes(32, "big") for pk, sk in zip(self.pks, self.sks)}

    def agg_sk(self, members) -> int:
        np = pkg().np
        counts = np.bincount(np.asarray(members, np.int64) % 64, minlength=64)
        return sum(int(c) * sk for c, sk in zip(counts, self.sks)) % self.C.R


class Minter:
    """Aggregates as the wire carries them, signed as H(m)^(sum sk), with
    the plain host route's view of each one kept beside it."""

    def __init__(self, keys: Keys, spec, rng):
        self.keys, self.spec, self.rng = keys, spec, rng
        self._h: dict[bytes, tuple] = {}
        self._shuffled: dict[tuple, tuple] = {}

    def committee(self, view, slot: int, index: int):
        """``get_beacon_committee`` from the spec's own pieces, with the
        epoch's shuffled active set computed once (the accessor rescans a
        2^20-validator registry on every call)."""
        P, spec = pkg(), self.spec
        accessors, misc = P.accessors, P.misc
        epoch = misc.compute_epoch_at_slot(slot, spec)
        seed = accessors.get_seed(
            view, epoch, P.constants.DOMAIN_BEACON_ATTESTER, spec)
        hit = self._shuffled.get((epoch, seed))
        if hit is None:
            ws = P.BeaconStateMut(view)
            active = P.np.asarray(ws.active_indices(epoch), P.np.int64)
            perm = misc.compute_shuffled_indices(
                len(active), seed, spec.SHUFFLE_ROUND_COUNT)
            cps = accessors.get_committee_count_per_slot(ws, epoch, spec)
            hit = self._shuffled[(epoch, seed)] = (active[perm], cps)
        shuffled, cps = hit
        count = cps * int(spec.SLOTS_PER_EPOCH)
        i = (slot % int(spec.SLOTS_PER_EPOCH)) * cps + index
        total = len(shuffled)
        return shuffled[total * i // count: total * (i + 1) // count]

    def h_point(self, signing_root: bytes):
        h = self._h.get(signing_root)
        if h is None:
            h = self._h[signing_root] = pkg().hash_to_g2(signing_root, pkg().DST_POP)
        return h

    def attestation_data(self, view, slot: int, index: int):
        """What an honest attester of ``slot`` signs on ``view``'s chain."""
        P, spec = pkg(), self.spec
        accessors, misc = P.accessors, P.misc
        epoch = misc.compute_epoch_at_slot(slot, spec)
        src = (view.current_justified_checkpoint
               if epoch == accessors.get_current_epoch(view, spec)
               else view.previous_justified_checkpoint)
        start = misc.compute_start_slot_at_epoch(epoch, spec)
        block_root = (accessors.get_block_root_at_slot(view, slot, spec)
                      if slot < view.slot
                      else view.latest_block_header.hash_tree_root(spec))
        target_root = (accessors.get_block_root_at_slot(view, start, spec)
                       if start < view.slot else block_root)
        return P.AttestationData(
            slot=slot, index=index, beacon_block_root=block_root,
            source=P.Checkpoint(epoch=src.epoch, root=bytes(src.root)),
            target=P.Checkpoint(epoch=epoch, root=target_root),
        )

    def aggregate(self, view, slot: int, index: int, corrupt: bool = False):
        """``(Attestation, attesting members, host entry)`` with
        participation drawn from [90 %, 100 %].  ``corrupt`` signs with the
        wrong secret: a valid curve point that is not this signature."""
        P, C, spec = pkg(), self.keys.C, self.spec
        committee = self.committee(view, slot, index)
        k = len(committee)
        bits = P.np.ones(k, bool)
        miss = self.rng.randrange(0, k // 10 + 1)
        if miss:
            bits[self.rng.sample(range(k), miss)] = False
        data = self.attestation_data(view, slot, index)
        domain = P.accessors.get_domain(
            view, P.constants.DOMAIN_BEACON_ATTESTER, int(data.target.epoch), spec)
        sroot = P.misc.compute_signing_root(data, domain)
        sk = self.keys.agg_sk(committee[bits])
        sig = C.g2.multiply_raw(self.h_point(sroot), sk + 1 if corrupt else sk)
        att = P.Attestation(aggregation_bits=bits.tolist(), data=data,
                          signature=C.g2_to_bytes(sig))
        # the host route's entry: the aggregate key from the secrets, never
        # from the registry planes the device sums
        entry = (C.g1.multiply_raw(C.G1_GENERATOR, sk), sroot, sig)
        return att, committee[bits], entry


def host_verdicts(entries) -> list[bool]:
    """``batch_verify_each_points`` on the native library."""
    with host_routing():
        return pkg().batch_verify_each_points(entries)


def counter_total(name: str, *registries) -> float:
    """One counter family summed over its series, read off the Prometheus
    exposition the node serves."""
    total = 0.0
    for reg in registries:
        for line in reg.render_prometheus(self_scrape=False).splitlines():
            if line.startswith(name) and line[len(name)] in " {":
                total += float(line.rsplit(" ", 1)[1])
    return total


async def http(port: int, method: str, path: str, body: dict | None = None):
    """One request from an executor thread (never the loop thread)."""
    import urllib.request

    def go():
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=600) as resp:
            return json.loads(resp.read().decode())

    return await asyncio.get_running_loop().run_in_executor(None, go)


# ------------------------------------------------------------- one chip


async def run_node_path(args, clock: CompileClock, workdir: str) -> None:
    nodes: list = []
    try:
        await node_phases(args, clock, workdir, nodes)
        await nodes.pop().stop()
    finally:
        for node in nodes:  # a failed phase: still stop what was started
            with contextlib.suppress(Exception):
                await asyncio.wait_for(node.stop(), 60)


async def node_phases(args, clock: CompileClock, workdir: str, nodes: list) -> None:
    from lambda_ethereum_consensus_tpu import telemetry
    from lambda_ethereum_consensus_tpu.compression.snappy import compress
    from lambda_ethereum_consensus_tpu.config import (
        mainnet_spec, minimal_spec, use_chain_spec,
    )
    from lambda_ethereum_consensus_tpu.fork_choice import get_head
    from lambda_ethereum_consensus_tpu.fork_choice import attestation as FA
    from lambda_ethereum_consensus_tpu.network.port import (
        VERDICT_ACCEPT, VERDICT_REJECT,
    )
    from lambda_ethereum_consensus_tpu.node import BeaconNode, NodeConfig
    from lambda_ethereum_consensus_tpu.node.warmup import DrainShapes
    from lambda_ethereum_consensus_tpu.ops import aot
    from lambda_ethereum_consensus_tpu.ssz.hash import HashlibBackend
    from lambda_ethereum_consensus_tpu.state_transition import (
        accessors, misc, process_slots,
    )
    from lambda_ethereum_consensus_tpu.state_transition.core import state_root
    from lambda_ethereum_consensus_tpu.state_transition.genesis import (
        build_genesis_state,
    )
    from lambda_ethereum_consensus_tpu.types.validator import (
        AggregateAndProof, SignedAggregateAndProof,
    )
    from lambda_ethereum_consensus_tpu.validator import build_signed_block
    from lambda_ethereum_consensus_tpu.witness.multiproof import (
        WitnessProof, verify_host,
    )

    rehearse = args.rehearse
    # (interpret-mode drains take minutes: the rehearsal's slots are long
    # enough that its aggregates are still timely when their drain starts)
    spec = minimal_spec().replace(SECONDS_PER_SLOT=120) if rehearse else mainnet_spec()
    n_validators = 256 if rehearse else 1 << 20
    aggregators = 2 if rehearse else 16  # TARGET_AGGREGATORS_PER_COMMITTEE
    n_slots, n_corrupt, n_blocks = 3, 3, 2
    rng = random.Random(args.seed)

    with use_chain_spec(spec):
        slots_per_epoch = int(spec.SLOTS_PER_EPOCH)
        sec_per_slot = int(spec.SECONDS_PER_SLOT)

        # ---- phase 1: boot --------------------------------------------
        with phase(1, "boot", clock) as out:
            t0 = time.perf_counter()
            keys = Keys(args.seed, n_validators)
            # the wall clock is the node's clock: genesis sits far enough
            # back that every block imported below crosses an epoch boundary
            genesis_time = int(time.time()) - (slots_per_epoch + 4) * sec_per_slot
            genesis = build_genesis_state(
                keys.pubkeys(), genesis_time=genesis_time, spec=spec)
            cps = accessors.get_committee_count_per_slot(genesis, 0, spec)
            committee = n_validators // (slots_per_epoch * cps)
            host_anchor_root = genesis.hash_tree_root(spec, backend=HashlibBackend())
            out["state_build_s"] = round(time.perf_counter() - t0, 2)

            per_slot = cps * aggregators
            node = BeaconNode(NodeConfig(
                db_path=os.path.join(workdir, "beacon.wal"),
                genesis_state=genesis,
                enable_range_sync=False,
                warm_drain_shapes=DrainShapes(
                    n_validators=n_validators,
                    n_committees=cps * slots_per_epoch,
                    committee=committee, entries=per_slot, groups=cps,
                ),
            ))
            nodes.append(node)  # the caller stops it, whatever happens below
            t0 = time.perf_counter()
            await node.start()
            out["node_start_s"] = round(time.perf_counter() - t0, 2)
            expect(node.device_backend is not None, "device paths are not ON")
            t0 = time.perf_counter()
            await asyncio.get_running_loop().run_in_executor(
                None, node._warmer.join)
            out["warmer_wait_s"] = round(time.perf_counter() - t0, 2)
            out["warmer"] = dict(node.warmer_stats)
            expect("error" not in node.warmer_stats,
                   f"warmer failed: {node.warmer_stats.get('error')}")
            expect(node.kv.native, "KV store fell back to the Python engine")
            store = node.store
            anchor_root = next(iter(store.blocks))
            device_anchor_root = bytes(store.blocks[anchor_root].state_root)
            expect(device_anchor_root == host_anchor_root,
                   "anchor state root: device != hashlib")
            out.update(
                preset="minimal" if rehearse else "mainnet",
                validators=n_validators, committees_per_slot=cps,
                committee_width=committee,
                sync_committee_width=int(spec.SYNC_COMMITTEE_SIZE),
                compared="anchor state root: device backend == hashlib",
                anchor_state_root="0x" + host_anchor_root.hex(),
            )

        verdicts: dict[bytes, int] = {}
        port_validate = node.port.validate_message

        async def record_verdict(msg_id, verdict):
            verdicts[msg_id] = verdict
            await port_validate(msg_id, verdict)

        node.port.validate_message = record_verdict
        subs = {sub.topic_label: sub for sub in node._subs}

        async def feed(label: str, items: list[tuple[bytes, bytes]]) -> None:
            """Wire payloads into the node's own subscription, then wait
            for every verdict the node hands back to the sidecar."""
            sub = subs[label]
            for msg_id, payload in items:
                await sub._on_gossip(sub.topic, msg_id, payload, b"smoke-peer")
            while not all(m in verdicts for m, _ in items):
                await asyncio.sleep(0.02)

        # the plain reference's own lineage of the chain, advanced under
        # host routing as the phases need it
        host_view = genesis

        def host_advance(view, slot: int):
            with host_routing():
                return process_slots(view, slot, spec) if view.slot < slot else view

        # ---- phase 2: gossip ingest ------------------------------------
        with phase(2, "gossip_ingest", clock) as out:
            t0 = time.perf_counter()
            now_slot = store.current_slot(spec)
            first = now_slot - n_slots
            epoch = misc.compute_epoch_at_slot(first, spec)
            # (at least one slot: slot 0's header has no state root yet)
            host_view = host_advance(
                host_view, max(misc.compute_start_slot_at_epoch(epoch, spec), 1))
            out["host_advance_s"] = round(time.perf_counter() - t0, 2)
            t0 = time.perf_counter()
            minter = Minter(keys, spec, rng)
            corrupt_at = {(s, rng.randrange(per_slot)) for s in range(n_corrupt)}
            batches, minted = [], {}
            for s in range(n_slots):
                items = []
                for j in range(per_slot):
                    bad = (s, j) in corrupt_at
                    att, members, entry = minter.aggregate(
                        host_view, first + s, j // aggregators, corrupt=bad)
                    wrapped = SignedAggregateAndProof(
                        message=AggregateAndProof(
                            aggregator_index=int(members[0]), aggregate=att,
                            selection_proof=b"\xc0" + b"\x00" * 95),
                        signature=b"\xc0" + b"\x00" * 95)
                    msg_id = b"agg:%d:%d" % (s, j)
                    minted[msg_id] = (att, members, entry, bad)
                    items.append((msg_id, compress(wrapped.encode(spec))))
                batches.append(items)
            out["mint_s"] = round(time.perf_counter() - t0, 2)

            drain_s = []
            for items in batches:  # one slot's aggregates per drain
                t0 = time.perf_counter()
                await feed("beacon_aggregate_and_proof", items)
                drain_s.append(round(time.perf_counter() - t0, 2))
            out["drain_s"] = drain_s

            t0 = time.perf_counter()
            ids = list(minted)
            host_ok = host_verdicts([minted[m][2] for m in ids])
            out["host_oracle_s"] = round(time.perf_counter() - t0, 2)
            accepted = rejected = votes = 0
            for msg_id, ok in zip(ids, host_ok):
                att, members, _entry, bad = minted[msg_id]
                expect(ok != bad, f"host route disagrees with the minting of {msg_id}")
                want = VERDICT_ACCEPT if ok else VERDICT_REJECT
                expect(verdicts[msg_id] == want,
                       f"{msg_id}: node verdict {verdicts[msg_id]} != host {want}")
                accepted += ok
                rejected += not ok
                if ok:
                    lm = [store.latest_messages.get(int(v)) for v in members]
                    expect(all(m is not None
                               and m.epoch >= int(att.data.target.epoch)
                               and m.root == bytes(att.data.beacon_block_root)
                               for m in lm),
                           f"{msg_id}: accepted votes are not in the store")
                    votes += len(members)
            expect(rejected == n_corrupt, "corrupted aggregates not all rejected")
            out.update(
                slots=n_slots, aggregates=len(ids), accepted=accepted,
                rejected=rejected, member_votes_read_back=votes,
                target_epoch=int(epoch),
                compared="every verdict == batch_verify_each_points (native); "
                         "accepted votes read back from the store",
            )

        # ---- phase 3: block import -------------------------------------
        with phase(3, "block_import", clock) as out:
            t0 = time.perf_counter()
            tip = store.current_slot(spec) - 2 * (n_blocks - 1) - 1
            expect(tip >= slots_per_epoch, "no epoch boundary below the blocks")
            blocks, minter = [], Minter(keys, spec, rng)
            with host_routing():
                for b in range(n_blocks):
                    slot = tip + 2 * b
                    pre = process_slots(host_view, slot, spec)
                    atts = [
                        minter.aggregate(pre, slot - back, index)[0]
                        for back in (2, 1)
                        for index in range(cps)
                        if slot - back >= 1
                    ][: int(spec.MAX_ATTESTATIONS)]
                    signed, host_view = build_signed_block(
                        pre, slot, keys, attestations=atts, spec=spec,
                        sync_secret_keys=keys.sync_keys())
                    blocks.append((signed, signed.message.hash_tree_root(spec),
                                   len(atts)))
                host_post_root = state_root(host_view, spec)
            out["host_build_s"] = round(time.perf_counter() - t0, 2)

            head_before = get_head(store, spec)
            import_s = []
            for b, (signed, root, _n) in enumerate(blocks):
                t0 = time.perf_counter()
                await feed("beacon_block",
                           [(b"blk:%d" % b, compress(signed.encode(spec)))])
                expect(verdicts[b"blk:%d" % b] == VERDICT_ACCEPT,
                       f"block {b} was not admitted to the pending set")
                while root not in store.blocks:  # the pending-blocks loop
                    expect(root not in node.pending.invalid,
                           f"block {b} (slot {signed.message.slot}) marked invalid")
                    await asyncio.sleep(0.05)
                import_s.append(round(time.perf_counter() - t0, 2))
            out["import_s"] = import_s
            last_root = blocks[-1][1]
            head = get_head(store, spec)
            expect(head == last_root and head != head_before, "the head did not move")
            post = store.block_states[last_root]
            plane = getattr(post, "_resident_plane", None)
            expect(plane is not None and plane.stats["sweeps"] > 0,
                   "the resident epoch plane did not run")
            device_post_root = state_root(post, spec)
            expect(device_post_root == host_post_root,
                   "post-state root: device lineage != host lineage")
            out.update(
                blocks=n_blocks, slots=[int(s.message.slot) for s, _, _ in blocks],
                attestations_per_block=[n for _, _, n in blocks],
                sync_aggregate_members=int(spec.SYNC_COMMITTEE_SIZE),
                epoch_boundaries_crossed=int(blocks[-1][0].message.slot)
                // slots_per_epoch,
                resident_sweeps=plane.stats["sweeps"],
                compared="post-state root == the same blocks under hashlib + "
                         "the non-resident transition; signature and "
                         "state-root validation on",
                post_state_root="0x" + host_post_root.hex(),
            )

        # ---- phase 4: serve --------------------------------------------
        with phase(4, "serve", clock) as out:
            api = node.api.port
            got = await http(api, "GET", "/eth/v1/beacon/states/head/root")
            expect(got["data"]["root"] == "0x" + host_post_root.hex(),
                   "served head state root != host oracle")
            got = await http(api, "GET", "/eth/v2/beacon/blocks/head")
            msg = got["data"]["message"]
            expect(msg["slot"] == str(int(blocks[-1][0].message.slot))
                   and msg["state_root"] == "0x" + host_post_root.hex(),
                   "served head block is not the last imported block")
            got = await http(
                api, "GET",
                "/eth/v0/witness/head?indices=balances:17,validators:42")
            proof = WitnessProof.from_json(got["data"])
            expect(proof.state_root == host_post_root
                   and verify_host(proof, host_post_root),
                   "served multiproof does not verify against the host root")
            proofs = []
            for i in rng.sample(range(n_validators), 64):
                got = await http(api, "GET",
                                 f"/eth/v0/witness/head?indices=balances:{i}")
                proofs.append(got["data"])
            tampered = rng.randrange(64)
            leaf = proofs[tampered]["leaves"][0][1]
            proofs[tampered]["leaves"][0][1] = (
                leaf[:-1] + ("0" if leaf[-1] != "0" else "1"))
            got = await http(api, "POST", "/eth/v0/witness/verify",
                             {"state_id": "head", "proofs": proofs})
            want = [verify_host(WitnessProof.from_json(p), host_post_root)
                    for p in proofs]
            expect(want == [i != tampered for i in range(64)],
                   "host oracle disagrees with the tampering")
            expect(got["data"]["results"] == want and got["data"]["anchored"],
                   "witness verify verdicts != verify_host")
            out.update(
                requests=3 + 64 + 1, proofs_verified=64, tampered=1,
                compared="roots and block == host oracle; 64 verdicts == "
                         "witness.verify.verify_host",
            )

        # ---- phase 5: no quiet fallback --------------------------------
        with phase(5, "no_quiet_fallback", clock) as out:
            fault = telemetry.device_fault_state()
            expect(not fault["faulted"], f"device fault latched: {fault}")
            for name in ("gossip_batch_error_count", "aot_errors_total",
                         "device_fault_total"):
                total = counter_total(name, telemetry.get_metrics(), node.metrics)
                expect(total == 0, f"{name} = {total}")
            rows = aot.compile_profile()
            expect(all(r["source"] in ("disk", "compile") for r in rows),
                   "an AOT row was neither loaded nor compiled")
            called = {}
            for r in rows:
                called[r["entry"]] = called.get(r["entry"], 0) + r["hits"] + r["misses"]
            families = (("transition_",) if rehearse  # interpret mode jits no chain
                        else ("chain_", "pair_", "transition_", "witness_verify"))
            for fam in families:
                expect(any(e.startswith(fam) and c > 0 for e, c in called.items()),
                       f"no {fam}* program was dispatched")
            ctxs = list(store.attestation_contexts.values()) + list(
                FA._STATE_CTX.values())
            expect(ctxs and all(c._device_cache is not None for c in ctxs),
                   "an attestation context has no device committee cache")
            out.update(
                aot_rows=len(rows),
                aot_entries_called=sorted(e for e, c in called.items() if c),
                attestation_contexts=len(ctxs),
                device_faulted=False,
                compared="fault latch clear; error counters zero; every "
                         "AOT row loaded or compiled; device caches built",
            )


# ----------------------------------------------------------- four chips


def run_four_chips(args, clock: CompileClock) -> None:
    """The sharded programs against their single-device twins, and nothing
    else: (a) the registry subtree root, (b) one drain's RLC verify, (c)
    the state planes' placement and one sharded epoch, (d) the witness
    plane — every path that is on by itself on a multi-device TPU."""
    import numpy as np

    from lambda_ethereum_consensus_tpu.config import (
        mainnet_spec, minimal_spec, use_chain_spec,
    )
    from lambda_ethereum_consensus_tpu.ops import aot
    from lambda_ethereum_consensus_tpu.ops import bls_batch as BB
    from lambda_ethereum_consensus_tpu.ops import sha256 as S
    from lambda_ethereum_consensus_tpu.ops.bls_shard import sharded_chain_verify
    from lambda_ethereum_consensus_tpu.ops.mesh import (
        default_mesh, shard_enabled, shard_plane_store_enabled,
        state_shard_enabled,
    )
    from lambda_ethereum_consensus_tpu.ssz.hash import hashlib_level
    from lambda_ethereum_consensus_tpu.witness import verify as WV

    sys.path.insert(0, os.path.join(HERE, "scripts"))
    import bench_state_shard as BSS  # the epoch-sequence driver, reused

    rehearse = args.rehearse
    n = 256 if rehearse else 1 << 20
    n_entries, n_groups = (16, 4) if rehearse else (1024, 64)
    mesh = default_mesh()
    d = int(mesh.devices.size)
    rng = np.random.default_rng(args.seed)
    pyrng = random.Random(args.seed)

    def quarter(arr, axis: int) -> list[int]:
        shards = arr.addressable_shards
        expect(len(shards) == d and len({s.device for s in shards}) == d,
               "a plane is not spread over every device")
        sizes = [int(s.data.shape[axis]) for s in shards]
        expect(all(z * d == arr.shape[axis] for z in sizes),
               f"a device holds more than 1/{d}: {sizes}")
        return sizes

    if not rehearse:  # on a multi-device TPU these are on by themselves
        expect(shard_enabled() and state_shard_enabled() and WV._shard_enabled(),
               "sharded defaults are off")
    # ...and this one is not: the registry planes' consumers are Pallas
    # programs the TPU compiler cannot partition (ops/mesh.py)
    expect(not shard_plane_store_enabled(), "registry planes shard by default")

    with phase(1, "sharded_merkle_root", clock) as out:
        chunks = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
        t0 = time.perf_counter()
        root_mesh, depth = S.merkle_root_device(chunks)
        out["sharded_s"] = round(time.perf_counter() - t0, 2)
        words = np.ascontiguousarray(chunks).reshape(-1, 64).view(">u4").astype(
            np.uint32)
        t0 = time.perf_counter()
        digest = np.asarray(S._merkle_tree_jnp(words, depth - 1))
        out["single_device_s"] = round(time.perf_counter() - t0, 2)
        root_one = np.ascontiguousarray(digest.astype(">u4")).view(np.uint8).tobytes()
        level = chunks
        while level.shape[0] > 1:
            level = hashlib_level(level.reshape(-1, 64))
        expect(root_mesh == root_one == level[0].tobytes(),
               "registry subtree root: mesh / single device / hashlib differ")
        out.update(leaves=n, devices=d, root="0x" + root_mesh.hex(),
                   compared="merkle_root_device on the mesh == "
                            "_merkle_tree_jnp on one device == hashlib")

    with phase(2, "sharded_chain_verify", clock) as out:
        from lambda_ethereum_consensus_tpu.crypto.bls import curve as C
        from lambda_ethereum_consensus_tpu.crypto.bls.batch import _COEFF_BITS
        from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (
            DST_POP, hash_to_g2,
        )

        hs = [hash_to_g2(pyrng.randbytes(32), DST_POP) for _ in range(n_groups)]
        entries, gids = [], []
        for i in range(n_entries):
            sk = pyrng.randrange(1, C.R)
            g = i * n_groups // n_entries
            entries.append((C.g1.multiply_raw(C.G1_GENERATOR, sk),
                            C.g2.multiply_raw(hs[g], sk),
                            pyrng.getrandbits(_COEFF_BITS) | 1))
            gids.append(g)
        bad_at = pyrng.randrange(n_entries)
        bad = list(entries)
        bad[bad_at] = (bad[bad_at][0],
                       C.g2.multiply_raw(bad[bad_at][1], 3), bad[bad_at][2])
        got = {}
        for tag, verify in (("sharded", sharded_chain_verify),
                            ("single_device", BB.chain_verify)):
            t0 = time.perf_counter()
            got[tag] = [verify([(e, hs, gids)])[0] for e in (entries, bad)]
            out[f"{tag}_s"] = round(time.perf_counter() - t0, 2)
        expect(got["sharded"] == got["single_device"] == [True, False],
               f"chain verdicts differ or are wrong: {got}")
        out.update(entries=n_entries, groups=n_groups, verdicts=got,
                   compared="sharded_chain_verify == chain_verify on one "
                            "drain's entries, one corrupted signature included")

    with phase(3, "sharded_state_planes", clock) as out, use_chain_spec(
            minimal_spec() if rehearse else mainnet_spec()) as spec:
        sizes = {}
        cols = BSS._columns(n, args.seed)
        inputs = BSS._epoch_inputs(n, 1, args.seed)
        results = {}
        for sharded in (True, False):
            plane = BSS._make_plane(n, sharded=sharded)
            expect(plane.sharded == sharded, "plane sharding decision ignored")
            BSS._upload(plane, cols)
            if sharded:
                for name in ("bal_lo", "bal_hi", "scores", "part_prev", "part_cur"):
                    sizes[f"resident/{name}"] = quarter(getattr(plane, name), 0)
            results[sharded] = BSS._run_epochs(plane, cols, inputs, spec)
        expect(results[True]["sums"][0] == BSS._oracle_sums(cols, inputs[0]),
               "sharded epoch sums != numpy oracle")
        for key in ("sums", "mask_pop"):
            expect(results[True][key] == results[False][key], f"{key} differ")
        for key in ("bal", "scores", "part_prev", "part_cur"):
            np.testing.assert_array_equal(results[True][key], results[False][key])
        out.update(validators=n, devices=d, shard_sizes=sizes,
                   registry_planes="one device (sharding them is opt-in)",
                   compared="shard_rules.place: every device holds 1/devices "
                            "of each plane; one sharded epoch == the "
                            "single-device kernels == numpy sums")

    with phase(4, "sharded_witness_verify", clock) as out, use_chain_spec(
            minimal_spec() if rehearse else mainnet_spec()) as spec:
        from lambda_ethereum_consensus_tpu.state_transition.genesis import (
            build_genesis_state,
        )
        from lambda_ethereum_consensus_tpu.witness.multiproof import (
            WitnessPlanner, WitnessProof, verify_host,
        )

        # a small registry: a proof's shape comes from the type's depth
        pk = C.g1_to_bytes(C.G1_GENERATOR)
        state = build_genesis_state([pk] * 256, spec=spec)
        planner = WitnessPlanner()
        root = planner.root(state, spec)
        proofs = [planner.prove(state, [("balances", i)], spec)
                  for i in pyrng.sample(range(256), 64)]
        bad_at = pyrng.randrange(64)
        p = proofs[bad_at]
        flipped = bytes([p.leaves[0][1][0] ^ 1]) + p.leaves[0][1][1:]
        proofs[bad_at] = WitnessProof(
            p.state_root, p.indices, ((p.leaves[0][0], flipped),), p.siblings)
        if rehearse:
            os.environ["WITNESS_SHARD"] = "1"
        got = WV.verify_batch(proofs, root, device=True if rehearse else None)
        want = [verify_host(q, root) for q in proofs]
        expect(got == want == [i != bad_at for i in range(64)],
               "sharded witness verdicts != verify_host")
        called = [r["entry"] for r in aot.compile_profile()
                  if r["hits"] + r["misses"] > 0]
        expect("witness_verify_sharded" in called,
               "the mesh-sharded witness program was not the one dispatched")
        out.update(proofs=64, tampered=1, devices=d,
                   compared="verify_batch on the mesh == verify_host")


# ----------------------------------------------------------------- main


def main(argv=None) -> int:
    args = parse_args(argv)
    faulthandler.dump_traceback_later(TIME_LIMIT_S - 30, exit=True)
    if args.rehearse:
        steer_rehearsal(args.chips)
    try:
        sys.path.insert(0, HERE)
        # the sidecar child imports the package too, whatever the cwd
        os.environ["PYTHONPATH"] = HERE + os.pathsep + os.environ.get("PYTHONPATH", "")
        import lambda_ethereum_consensus_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository is not beside this script: {e}",
              file=sys.stderr)
        return 3

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU ({device}); --rehearse runs the CPU "
              "rehearsal, which is not a result", file=sys.stderr)
        return 2
    if device["count"] != args.chips:
        print(f"chip_smoke: {device['count']} device(s) here, --chips "
              f"{args.chips} asked", file=sys.stderr)
        return 2

    from lambda_ethereum_consensus_tpu.utils import env as env_mod

    if args.rehearse:
        env_mod._TPU_BACKEND = True  # take the TPU routing branches on the CPU
    import logging

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    t_start = time.perf_counter()
    try:
        import importlib.metadata as md

        import jaxlib

        try:
            libtpu = md.version("libtpu")
        except md.PackageNotFoundError:
            libtpu = None
        clock = CompileClock()
        emit({"phase": 0, "name": "device", "device": device,
              "rehearsal": args.rehearse, "seed": args.seed,
              "jax": jax.__version__, "jaxlib": jaxlib.__version__,
              "libtpu": libtpu,
              "jax_cache_dir": env_mod.enable_compile_cache(),
              "aot_cache_dir": env_mod.compile_cache_dirs()[1],
              "cache_dir_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
              **build_native()})
        if args.chips == 4:
            run_four_chips(args, clock)
        else:
            asyncio.run(run_node_path(args, clock, workdir))
        emit({"summary": {"wall_s": round(time.perf_counter() - t_start, 2),
                          **clock.snapshot()}})
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    out = {"ok": True, "device": device}
    if args.rehearse:
        out["rehearsal"] = True
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # os._exit: a failed phase may leave the node's threads behind; the
    # verdict must not wait on them
    try:
        code = main()
    except SystemExit as e:  # argparse
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
