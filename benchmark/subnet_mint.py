#!/usr/bin/env python3
"""benchmark/subnet_mint.py — the mint worker of the attestation subnet
channel (``generators/subnet_votes.py``), a process of its own on the
program's host path, as ``hostside.py``'s ``mint`` role is for aggregates.

A burst is one slot's unaggregated votes: for every committee of the slot
(or the ones a job names) each participating member's own ``Attestation``
with exactly one aggregation bit, snappy+SSZ as on the wire, in the order
they are pushed — round-robin over the committees, so over the subnets, as
meshes deliver them.  With ``key_cycle`` keys a committee's votes carry at
most ``key_cycle`` distinct signatures over its one signing root, so a burst
costs ``committees x key_cycle`` G2 multiplications, not one a vote.

Every draw comes from ``(seed, burst id)``.  A guard job adds the faults the
run checks before the window: one wrong-secret signature (REJECT), one second
vote of an attester of the burst with another head root (IGNORE, and
evidence), one vote pushed on another subnet's topic (REJECT).  Where asked,
the native library's ``batch_verify_each_points`` runs over the burst's
signed votes: the oracle of the minted truth.

Commands arrive as JSON lines on stdin; frames leave on stdout as pickles
with an 8-byte length in front (``hostside.py``'s framing).
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import hostside  # noqa: E402
from hostside import Chain, Keys, _commands, _send, chain_spec, draw_bits, g1_mul, g2_mul  # noqa: E402

SUBNETS = 64  # ATTESTATION_SUBNET_COUNT


def subnet_of(spec, cps: int, slot: int, index: int) -> int:
    """p2p-interface.md ``compute_subnet_for_attestation``."""
    return (cps * (slot % int(spec.SLOTS_PER_EPOCH)) + index) % SUBNETS


class Votes:
    """One committee's votes of one slot, minted from the secrets."""

    def __init__(self, chain: Chain, keys: Keys, slot: int, index: int,
                 head_root: bytes | None = None):
        from lambda_ethereum_consensus_tpu.types.beacon import Attestation

        self.chain, self.keys, self.C = chain, keys, keys.C
        self.slot, self.index = slot, index
        self.committee = chain.committee(slot, index)
        self.k = len(self.committee)
        data = chain.attestation_data(slot, index)
        if head_root is not None:  # a second vote: another head, same target
            data = data.copy(beacon_block_root=head_root)
        self.sroot = chain.signing_root(data)
        self.h = chain.h_point(self.sroot)
        self._sig: dict[tuple[int, bool], tuple] = {}
        # the SSZ of an Attestation: offset of the bit list, the data, the
        # signature, the bit list — assembled by hand from the program's own
        # encoding of one vote and held to it below
        first = Attestation(aggregation_bits=[i == 0 for i in range(self.k)],
                            data=data, signature=b"\x00" * 96).encode(chain.spec)
        self._prefix = first[:-96 - (self.k // 8 + 1)]
        assert self.raw(0, b"\x00" * 96) == first, "hand-assembled SSZ differs"

    def raw(self, position: int, signature: bytes) -> bytes:
        bits = bytearray(self.k // 8 + 1)
        bits[self.k >> 3] |= 1 << (self.k & 7)  # the bit list's delimiter
        bits[position >> 3] |= 1 << (position & 7)
        return self._prefix + signature + bytes(bits)

    def signature(self, validator: int, bad: bool = False) -> tuple:
        """``(point, bytes)`` of the validator's signature over this
        committee's signing root; ``bad``: by its secret plus one."""
        key = int(validator) % self.keys.cycle
        hit = self._sig.get((key, bad))
        if hit is None:
            point = g2_mul(self.C, self.h, self.keys.sks[key] + (1 if bad else 0))
            hit = self._sig[(key, bad)] = (point, self.C.g2_to_bytes(point))
        return hit


@dataclasses.dataclass
class Vote:
    """One message of a burst, in the order it is pushed."""

    votes: Votes  # its committee's minting
    position: int  # the member's place in the committee: its aggregation bit
    subnet: int  # the topic it is pushed on
    bad: bool = False  # signed by the secret plus one
    ssz: bytes = b""
    point: tuple | None = None  # the signature as a G2 point (the oracle's entry)

    @property
    def validator(self) -> int:
        return int(self.votes.committee[self.position])

    def sign(self) -> None:
        self.point, signature = self.votes.signature(self.validator, bad=self.bad)
        self.ssz = self.votes.raw(self.position, signature)


def mint_burst(chain: Chain, keys: Keys, mix: dict, seed: int, job: dict) -> dict:
    import numpy as np

    from lambda_ethereum_consensus_tpu.compression.snappy import compress
    from lambda_ethereum_consensus_tpu.crypto.bls.batch import batch_verify_each_points

    t0 = time.perf_counter()
    spec, C = chain.spec, keys.C
    rng = random.Random((seed << 20) ^ (int(job["id"]) + 1))
    slot = int(job["slot"])
    cps = hostside.committees_per_slot(spec, chain.n)
    indices = job.get("committees") or list(range(cps))
    guard = job.get("guard") or {}
    ranked = []  # (rank in its committee, committee index, vote)
    for index in indices:
        votes = Votes(chain, keys, slot, index)
        bits = draw_bits(rng, votes.k, [1.0, 1.0] if guard else mix["participation"])
        subnet = subnet_of(spec, cps, slot, index)
        ranked += [(rank, index, Vote(votes, position, subnet))
                   for rank, position in enumerate(np.flatnonzero(bits).tolist())]
    # as meshes deliver them: round-robin over the committees (the subnets)
    ranked.sort(key=lambda r: r[:2])
    burst = [vote for _rank, _index, vote in ranked]
    for at in rng.sample(range(len(burst)), int(guard.get("invalid", 0))):
        burst[at].bad = True  # a wrong-secret signature
    expect = ["R" if vote.bad else "A" for vote in burst]
    if guard.get("second_vote"):
        # a sound attester of the burst votes again, for another head: the
        # same cell, validly signed — first seen decides, and the pair is
        # double-vote evidence
        first = rng.choice([vote for vote in burst if not vote.bad])
        other = hashlib.sha256(b"another head %d" % job["id"]).digest()
        twin = Votes(chain, keys, slot, first.votes.index, head_root=other)
        burst.append(Vote(twin, first.position, first.subnet))
        expect.append("I")
    if guard.get("wrong_subnet"):
        # a sound vote of another committee of the slot, on this topic
        stray = Votes(chain, keys, slot, (indices[0] + 1) % cps)
        burst.append(Vote(stray, 0, subnet_of(spec, cps, slot, indices[0])))
        expect.append("R")
    for vote in burst:
        vote.sign()
    out = {
        "kind": "burst", "id": job["id"], "role": job["role"], "slot": slot,
        "items": [(b"sub:%d:%d" % (job["id"], j), compress(vote.ssz))
                  for j, vote in enumerate(burst)],
        "ssz": [vote.ssz for vote in burst],
        "subnet": np.asarray([vote.subnet for vote in burst], np.int16),
        "validator": np.asarray([vote.validator for vote in burst], np.int32),
        "bad": np.asarray([vote.bad for vote in burst], bool),
        "expect": "".join(expect), "oracle": None,
    }
    mint_s = time.perf_counter() - t0
    if job.get("oracle"):
        # the host route's entries: the key from the secret, never from the
        # registry planes the device gathers
        pk = {key: g1_mul(C, keys.sks[key])
              for key in {vote.validator % keys.cycle for vote in burst}}
        out["oracle"] = [bool(ok) for ok in batch_verify_each_points(
            [(pk[vote.validator % keys.cycle], vote.votes.sroot, vote.point)
             for vote in burst])]
    out["mint_s"], out["oracle_s"] = mint_s, time.perf_counter() - t0 - mint_s
    return out


def run(args, cfg: dict, mix: dict) -> None:
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
                if not cmds.empty():  # a stop: what is left is not wanted
                    break
                _send(mint_burst(chain, keys, mix, args.seed, job))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--role", default="mint")
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--genesis-time", type=int, required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    os.environ.update(hostside.HOST_ENV)
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
        run(args, cfg, mix)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BrokenPipeError:  # the parent went away: nothing left to tell it
        code = 0
    sys.stdout.flush()
    os._exit(code)
