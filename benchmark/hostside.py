#!/usr/bin/env python3
"""benchmark/hostside.py — the traffic's makers on the program's host path, in
processes of their own.

A child of ``run.py`` that never imports JAX and never touches the chip:
BLS on the native host library, ``hashlib`` Merkleization, the non-resident
transition.  It makes the traffic from the seed and keeps the truth each
item is held to.  It imports the program, so it is a second path of it and
not a plain reference: block import is held to ``plainref.py``.  Two roles:

``lineage``  builds the seeded genesis state, roots it with ``hashlib`` and,
             on request, builds signed blocks on the host's own lineage of
             the chain (what a peer serving a range request would hold).
``mint``     mints one slot's aggregate channel per burst
             (``SignedAggregateAndProof``, snappy+SSZ as on the wire) and,
             where asked, runs ``batch_verify_each_points`` on the native
             library over the burst.

Commands arrive as JSON lines on stdin; frames leave on stdout as pickles
with an 8-byte length in front.  Copied in idea from ``chip_smoke.py``
(``Keys``, ``Minter``, ``host_routing``): the yardstick keeps its own copy.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import queue
import random
import struct
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# the host path's routing, for the whole life of this process
HOST_ENV = {"JAX_PLATFORMS": "cpu", "BLS_NO_DEVICE": "1", "GRAFT_RESIDENT_EPOCH": "0"}


def _send(obj: dict) -> None:
    blob = pickle.dumps(obj, protocol=5)
    out = sys.stdout.buffer
    out.write(struct.pack("<Q", len(blob)))
    out.write(blob)
    out.flush()


def chain_spec(cfg: dict, rehearse: bool):
    from lambda_ethereum_consensus_tpu.config import mainnet_spec, minimal_spec

    size = cfg["rehearse"] if rehearse else cfg
    spec = {"mainnet": mainnet_spec, "minimal": minimal_spec}[size["preset"]]()
    if "seconds_per_slot" in size:  # the CPU rehearsal only: interpret-mode
        spec = spec.replace(SECONDS_PER_SLOT=size["seconds_per_slot"])  # drains take minutes
    return spec, int(size["validators"])


class Keys:
    """A registry of ``n`` validators cycling ``cycle`` seeded keys (minting
    2^20 distinct keys on the host would dominate set-up); aggregate secrets
    and public keys come from the cycle counts."""

    def __init__(self, seed: int, n: int, cycle: int = 64):
        from lambda_ethereum_consensus_tpu.crypto.bls import curve as C

        rng = random.Random(seed)
        self.C, self.n, self.cycle = C, n, cycle
        self.sks = [rng.randrange(1, C.R) for _ in range(cycle)]
        self.pks = [C.g1_to_bytes(C.g1.multiply_raw(C.G1_GENERATOR, sk))
                    for sk in self.sks]

    def pubkeys(self) -> list[bytes]:
        return [self.pks[i % self.cycle] for i in range(self.n)]

    def __getitem__(self, i: int) -> bytes:  # secret_keys[i] for block signing
        return self.sks[i % self.cycle].to_bytes(32, "big")

    def sync_keys(self) -> dict:
        return {pk: sk.to_bytes(32, "big") for pk, sk in zip(self.pks, self.sks)}

    def agg_sk(self, members) -> int:
        import numpy as np

        counts = np.bincount(np.asarray(members, np.int64) % self.cycle,
                             minlength=self.cycle)
        return sum(int(c) * sk for c, sk in zip(counts, self.sks)) % self.C.R


def build_genesis(cfg: dict, seed: int, genesis_time: int, rehearse: bool):
    """The configuration's registry, from the seed: the same in every
    process that builds it."""
    from lambda_ethereum_consensus_tpu.state_transition.genesis import (
        build_genesis_state,
    )

    spec, n = chain_spec(cfg, rehearse)
    keys = Keys(seed, n, int(cfg.get("key_cycle", 64)))
    state = build_genesis_state(keys.pubkeys(), genesis_time=genesis_time, spec=spec)
    return spec, keys, state


def committees_per_slot(spec, n_validators: int) -> int:
    """The spec's committee count per slot with every validator active."""
    return max(1, min(int(spec.MAX_COMMITTEES_PER_SLOT),
                      n_validators // int(spec.SLOTS_PER_EPOCH)
                      // int(spec.TARGET_COMMITTEE_SIZE)))


def draw_bits(rng, k: int, participation):
    """Aggregation bits of a ``k``-member committee: the number of misses
    uniform over what the ``[lo, hi]`` participation range allows."""
    import numpy as np

    lo, hi = participation
    bits = np.ones(k, bool)
    miss = rng.randrange(int(round((1 - hi) * k)), int((1 - lo) * k) + 1)
    if miss:
        bits[rng.sample(range(k), miss)] = False
    return bits


def g2_mul(C, point, scalar: int):
    from lambda_ethereum_consensus_tpu.crypto.bls import native

    if native.available():
        return native.g2_mul(point, scalar)
    return C.g2.multiply_raw(point, scalar)


def g1_mul(C, scalar: int):
    from lambda_ethereum_consensus_tpu.crypto.bls import native

    if native.available():
        return native.g1_mul(C.G1_GENERATOR, scalar)
    return C.g1.multiply_raw(C.G1_GENERATOR, scalar)


class Chain:
    """What minting an attestation needs to know of the (empty) chain above
    the anchor: every slot's head and target is the anchor block, the
    source is the genesis checkpoint, and an epoch's shuffling seed is the
    genesis randao mix's."""

    def __init__(self, spec, n_validators: int, params: dict):
        self.spec, self.n = spec, n_validators
        self.block_root = params["block_root"]
        self.gvr = params["genesis_validators_root"]
        self.seeds = params["seeds"]
        self._shuffled: dict[int, object] = {}
        self._h: dict[bytes, object] = {}

    def shuffled(self, epoch: int):
        import numpy as np

        from lambda_ethereum_consensus_tpu.state_transition import misc

        hit = self._shuffled.get(epoch)
        if hit is None:
            perm = misc.compute_shuffled_indices(
                self.n, self.seeds[epoch], self.spec.SHUFFLE_ROUND_COUNT)
            hit = self._shuffled[epoch] = np.arange(self.n, dtype=np.int64)[perm]
        return hit

    def committee(self, slot: int, index: int):
        spe = int(self.spec.SLOTS_PER_EPOCH)
        cps = committees_per_slot(self.spec, self.n)
        shuffled = self.shuffled(slot // spe)
        count, i = cps * spe, (slot % spe) * cps + index
        return shuffled[self.n * i // count: self.n * (i + 1) // count]

    def attestation_data(self, slot: int, index: int):
        from lambda_ethereum_consensus_tpu.types.beacon import (
            AttestationData, Checkpoint,
        )

        epoch = slot // int(self.spec.SLOTS_PER_EPOCH)
        return AttestationData(
            slot=slot, index=index, beacon_block_root=self.block_root,
            source=Checkpoint(epoch=0, root=b"\x00" * 32),
            target=Checkpoint(epoch=epoch, root=self.block_root),
        )

    def signing_root(self, data) -> bytes:
        from lambda_ethereum_consensus_tpu.config import constants
        from lambda_ethereum_consensus_tpu.state_transition import misc

        # the seeded genesis is a capella state: both fork versions are capella's
        domain = misc.compute_domain(
            constants.DOMAIN_BEACON_ATTESTER, self.spec.CAPELLA_FORK_VERSION, self.gvr)
        return misc.compute_signing_root(data, domain)

    def h_point(self, signing_root: bytes):
        from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (
            DST_POP, hash_to_g2,
        )

        h = self._h.get(signing_root)
        if h is None:
            h = self._h[signing_root] = hash_to_g2(signing_root, DST_POP)
        return h


def mint_burst(chain: Chain, keys: Keys, mix: dict, seed: int, job: dict) -> dict:
    """One slot's aggregate channel: ``committees x aggregators`` distinct
    aggregates of slot ``job["slot"]``.  Every draw comes from ``(seed,
    burst id)``, so a burst is the same whichever worker mints it."""
    import numpy as np

    from lambda_ethereum_consensus_tpu.compression.snappy import compress
    from lambda_ethereum_consensus_tpu.crypto.bls.batch import batch_verify_each_points
    from lambda_ethereum_consensus_tpu.types.beacon import Attestation
    from lambda_ethereum_consensus_tpu.types.validator import (
        AggregateAndProof, SignedAggregateAndProof,
    )

    t0 = time.perf_counter()
    C, spec = keys.C, chain.spec
    rng = random.Random((seed << 20) ^ (int(job["id"]) + 1))
    aggregators = int(mix["aggregators_per_committee"])
    per_burst = committees_per_slot(spec, chain.n) * aggregators
    slot, reuse = int(job["slot"]), int(job.get("reuse", 0))
    bad_at = set(rng.sample(range(per_burst), int(job.get("invalid", 0))))
    placeholder = b"\xc0" + b"\x00" * 95  # never checked by the program (PERF.md)
    items, index_col, bad_col, bit_rows, entries = [], [], [], [], []
    for j in range(per_burst):
        index, a = divmod(j, aggregators)
        committee = chain.committee(slot, index)
        k = len(committee)
        bits = draw_bits(rng, k, mix["participation"])
        pos = (reuse * aggregators + a) % k  # a distinct aggregator per reuse
        bits[pos] = True
        data = chain.attestation_data(slot, index)
        sroot = chain.signing_root(data)
        sk = keys.agg_sk(committee[bits])
        bad = j in bad_at
        sig = g2_mul(C, chain.h_point(sroot), sk + 1 if bad else sk)
        att = Attestation(aggregation_bits=bits.tolist(), data=data,
                          signature=C.g2_to_bytes(sig))
        wrapped = SignedAggregateAndProof(
            message=AggregateAndProof(
                aggregator_index=int(committee[pos]), aggregate=att,
                selection_proof=placeholder),
            signature=placeholder)
        items.append((b"agg:%d:%d" % (job["id"], j), compress(wrapped.encode(spec))))
        index_col.append(index)
        bad_col.append(bad)
        bit_rows.append(np.packbits(bits))
        if job.get("oracle"):
            # the host route's entry: the aggregate key from the secrets,
            # never from the registry planes the device sums
            entries.append((g1_mul(C, sk), sroot, sig))
    out = {
        "kind": "burst", "id": job["id"], "role": job["role"], "slot": slot,
        "items": items, "index": np.asarray(index_col, np.int32),
        "bad": np.asarray(bad_col, bool), "bits": np.stack(bit_rows),
        "committee_size": k, "oracle": None,
    }
    mint_s = time.perf_counter() - t0
    if entries:
        out["oracle"] = [bool(v) for v in batch_verify_each_points(entries)]
    out["mint_s"], out["oracle_s"] = mint_s, time.perf_counter() - t0 - mint_s
    return out


def _commands() -> "queue.Queue[dict | None]":
    """stdin's JSON lines on a queue (None at end of file), so a minting
    loop can look for ``stop`` between bursts without blocking."""
    q: queue.Queue = queue.Queue()

    def pump():
        for line in sys.stdin:
            if line.strip():
                q.put(json.loads(line))
        q.put(None)

    threading.Thread(target=pump, daemon=True).start()
    return q


def run_mint(args, cfg: dict, mix: dict) -> None:
    spec, n = chain_spec(cfg, args.rehearse)
    keys = Keys(args.seed, n, int(cfg.get("key_cycle", 64)))
    cmds = _commands()
    chain = None
    _send({"kind": "ready", "role": "mint"})
    while (cmd := cmds.get()) is not None:
        if cmd["cmd"] == "params":
            chain = Chain(spec, n, {
                "block_root": bytes.fromhex(cmd["block_root"]),
                "genesis_validators_root": bytes.fromhex(cmd["genesis_validators_root"]),
                "seeds": {int(e): bytes.fromhex(s) for e, s in cmd["seeds"].items()},
            })
        elif cmd["cmd"] == "mint":
            for job in cmd["bursts"]:
                _send(mint_burst(chain, keys, mix, args.seed, job))
        elif cmd["cmd"] == "mint_window":
            # bursts start, start+stride, ... cycling over the window's
            # slots, until the limit or the next command (a stop)
            slots, burst_id = cmd["slots"], int(cmd["start"])
            while burst_id < int(cmd["limit"]) and cmds.empty():
                k = burst_id - int(cmd["first"])
                job = {"id": burst_id, "role": "window",
                       "slot": slots[k % len(slots)],
                       "reuse": int(cmd["reuse_base"]) + k // len(slots),
                       "invalid": int(mix.get("invalid_per_burst", 0)),
                       "oracle": burst_id in cmd["oracle_ids"]}
                _send(mint_burst(chain, keys, mix, args.seed, job))
                burst_id += int(cmd["stride"])
            _send({"kind": "mint_done", "next": burst_id})
        elif cmd["cmd"] == "stop":
            continue


# ------------------------------------------------------------------ lineage


class StateMinter:
    """Aggregates signed on a state of the host's own lineage (the blocks'
    attestations), with the epoch's shuffled active set computed once."""

    def __init__(self, keys: Keys, spec, rng):
        self.keys, self.spec, self.rng = keys, spec, rng
        self._shuffled: dict[tuple, tuple] = {}
        self._h: dict[bytes, object] = {}

    def committee(self, view, slot: int, index: int):
        import numpy as np

        from lambda_ethereum_consensus_tpu.config import constants
        from lambda_ethereum_consensus_tpu.state_transition import accessors, misc
        from lambda_ethereum_consensus_tpu.state_transition.mutable import (
            BeaconStateMut,
        )

        spec = self.spec
        epoch = misc.compute_epoch_at_slot(slot, spec)
        seed = accessors.get_seed(view, epoch, constants.DOMAIN_BEACON_ATTESTER, spec)
        hit = self._shuffled.get((epoch, seed))
        if hit is None:
            ws = BeaconStateMut(view)
            active = np.asarray(ws.active_indices(epoch), np.int64)
            perm = misc.compute_shuffled_indices(
                len(active), seed, spec.SHUFFLE_ROUND_COUNT)
            cps = accessors.get_committee_count_per_slot(ws, epoch, spec)
            hit = self._shuffled[(epoch, seed)] = (active[perm], cps)
        shuffled, cps = hit
        count = cps * int(spec.SLOTS_PER_EPOCH)
        i = (slot % int(spec.SLOTS_PER_EPOCH)) * cps + index
        total = len(shuffled)
        return shuffled[total * i // count: total * (i + 1) // count]

    def attestation_data(self, view, slot: int, index: int):
        """What an honest attester of ``slot`` signs on ``view``'s chain."""
        from lambda_ethereum_consensus_tpu.state_transition import accessors, misc
        from lambda_ethereum_consensus_tpu.types.beacon import (
            AttestationData, Checkpoint,
        )

        spec = self.spec
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
        return AttestationData(
            slot=slot, index=index, beacon_block_root=block_root,
            source=Checkpoint(epoch=src.epoch, root=bytes(src.root)),
            target=Checkpoint(epoch=epoch, root=target_root),
        )

    def aggregate(self, view, slot: int, index: int, participation):
        from lambda_ethereum_consensus_tpu.config import constants
        from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (
            DST_POP, hash_to_g2,
        )
        from lambda_ethereum_consensus_tpu.state_transition import accessors, misc
        from lambda_ethereum_consensus_tpu.types.beacon import Attestation

        C, spec = self.keys.C, self.spec
        committee = self.committee(view, slot, index)
        k = len(committee)
        bits = draw_bits(self.rng, k, participation)
        data = self.attestation_data(view, slot, index)
        domain = accessors.get_domain(
            view, constants.DOMAIN_BEACON_ATTESTER, int(data.target.epoch), spec)
        sroot = misc.compute_signing_root(data, domain)
        h = self._h.get(sroot)
        if h is None:
            h = self._h[sroot] = hash_to_g2(sroot, DST_POP)
        sig = g2_mul(C, h, self.keys.agg_sk(committee[bits]))
        return Attestation(aggregation_bits=bits.tolist(), data=data,
                           signature=C.g2_to_bytes(sig))


def build_blocks(spec, keys: Keys, view, cmd: dict, seed: int):
    """Signed capella blocks on the host's lineage, each carrying the
    committee aggregates of the slots just before it and a full sync
    aggregate; yields a frame per block, then the lineage's last root."""
    from lambda_ethereum_consensus_tpu.compression.snappy import compress
    from lambda_ethereum_consensus_tpu.state_transition import accessors, process_slots
    from lambda_ethereum_consensus_tpu.state_transition.core import state_root
    from lambda_ethereum_consensus_tpu.validator import build_signed_block

    minter = StateMinter(keys, spec, random.Random(seed))
    for role, slot in cmd["blocks"]:
        t0 = time.perf_counter()
        pre = process_slots(view, slot, spec) if view.slot < slot else view
        cps = accessors.get_committee_count_per_slot(
            pre, accessors.get_current_epoch(pre, spec), spec)
        atts = [
            minter.aggregate(pre, slot - back, index, cmd["participation"])
            for back in cmd["attestation_slots_back"]
            for index in range(cps)
            if slot - back >= 1
        ][: int(spec.MAX_ATTESTATIONS)]
        signed, view = build_signed_block(
            pre, slot, keys, attestations=atts, spec=spec,
            sync_secret_keys=keys.sync_keys())
        ssz = signed.encode(spec)
        yield {
            "kind": "block", "role": role, "slot": slot,
            "root": signed.message.hash_tree_root(spec),
            "wire": compress(ssz), "ssz": ssz,
            "attestations": len(atts),
            "sync_members": int(spec.SYNC_COMMITTEE_SIZE),
            "post_state_root": bytes(signed.message.state_root),
            "build_s": time.perf_counter() - t0,
        }
        if slot == cmd.get("prestate_after"):
            # what the plain reference (plainref.py) starts from: this
            # lineage's state under the first block it follows, as SSZ
            t0 = time.perf_counter()
            yield {"kind": "prestate", "slot": slot, "ssz": view.encode(spec),
                   "encode_s": time.perf_counter() - t0}
    last = {"kind": "lineage", "post_state_root": state_root(view, spec),
            "slot": int(view.slot)}
    if cmd.get("poststate"):  # tests only: the lineage's last state, as SSZ
        last["ssz"] = view.encode(spec)
    yield last


def run_lineage(args, cfg: dict, mix: dict) -> None:
    from lambda_ethereum_consensus_tpu.config import constants
    from lambda_ethereum_consensus_tpu.ssz.hash import HashlibBackend
    from lambda_ethereum_consensus_tpu.state_transition import accessors

    t0 = time.perf_counter()
    spec, keys, genesis = build_genesis(cfg, args.seed, args.genesis_time, args.rehearse)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state_root = genesis.hash_tree_root(spec, backend=HashlibBackend())
    header = genesis.latest_block_header.copy(state_root=state_root)
    seeds = {e: accessors.get_seed(genesis, e, constants.DOMAIN_BEACON_ATTESTER, spec)
             for e in range(int(cfg.get("seed_epochs", 64)))}
    _send({
        "kind": "anchor", "state_root": state_root,
        "block_root": header.hash_tree_root(spec),
        "genesis_validators_root": bytes(genesis.genesis_validators_root),
        "seeds": seeds, "build_s": build_s, "root_s": time.perf_counter() - t0,
    })
    cmds = _commands()
    while (cmd := cmds.get()) is not None:
        if cmd["cmd"] == "blocks":
            for frame in build_blocks(spec, keys, genesis, cmd, args.seed):
                _send(frame)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--role", choices=("lineage", "mint"), required=True)
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--genesis-time", type=int, required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    os.environ.update(HOST_ENV)
    sys.path.insert(0, ROOT)
    from lambda_ethereum_consensus_tpu.config import use_chain_spec
    from lambda_ethereum_consensus_tpu.ssz.hash import HashlibBackend, set_hash_backend

    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    if args.rehearse:  # the rehearsal's sizes lie beside the real ones
        mix = {**mix, **mix.get("rehearse", {})}
    set_hash_backend(HashlibBackend())
    spec, _n = chain_spec(cfg, args.rehearse)
    with use_chain_spec(spec):
        (run_lineage if args.role == "lineage" else run_mint)(args, cfg, mix)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BrokenPipeError:  # the parent went away: nothing left to tell it
        code = 0
    sys.stdout.flush()
    os._exit(code)
