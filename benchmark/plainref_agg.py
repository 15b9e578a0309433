#!/usr/bin/env python3
"""benchmark/plainref_agg.py — the plain reference of the aggregate channel,
in a process of its own.

What a node has to conclude from a ``SignedAggregateAndProof`` on the
``beacon_aggregate_and_proof`` topic (ethereum/consensus-specs phase0
``p2p-interface.md``), as far as this program checks the channel (the
aggregate's own signature; ``configs/*.json`` ``limits``): the attesting
indices from the aggregation bits (phase0 ``beacon-chain.md``
``get_attesting_indices``), the verdict ``on_attestation`` gives (phase0
``fork-choice.md`` ``validate_on_attestation``), the latest-message table it
leaves (``update_latest_messages``) and **the participants' aggregate public
key** (``eth_aggregate_pubkeys``: the G1 sum ``fast_aggregate_verify`` pairs
with the signature).  Written from the specs on ``hashlib`` and ``numpy``:
**it imports nothing of the program under test**.  SSZ decoding, the
swap-or-not shuffle and the committee slicing are ``plainref.py``'s, G1
decompression and the affine addition ``plainref_epoch.py``'s (the plain
references of block import, beside this file).

Given the anchor state and, in push order, each message's raw SSZ bytes, the
slot the clock showed when it was pushed and whether its signature is valid,
it answers each message's verdict:

  REJECT  not a ``SignedAggregateAndProof``; no aggregation bit set;
          committee index out of range; target epoch is not the slot's; bit
          list is not the committee's length; target root is not the anchor;
          signature invalid
  IGNORE  slot not yet over (fork-choice: a vote counts from the slot after
          its own) or outside ``ATTESTATION_PROPAGATION_SLOT_RANGE``; target
          epoch neither current nor previous; head block unknown
  ACCEPT  otherwise — and every attester's latest message becomes (target
          epoch, beacon block root) where the epoch is newer than the one held

What it does not do, and says so (``reference.not_covered``): the BLS pairing
(validity of a signature is known by construction and enters as the minter's
bit; an independent BLS12-381 verifier is an open question, PERF.md section
7), ``selection_proof`` and the aggregator's own signature (the program
checks neither), fork-choice weights, and anything of the chain above the
anchor (it is empty: every epoch's checkpoint state is the anchor state read
at that epoch — ``process_epoch`` is not followed).

Messages on stdin as ``plainref.py`` frames them (8-byte length, JSON header,
``header["bytes"]`` bytes).  One JSON line leaves per message:

``state``       a ``BeaconState``: the anchor.  Answers the anchor block's root.
``aggregates``  ``pushed_slots``, ``valid``, ``sizes`` (one entry a message),
                ``sums`` (whether the aggregate keys are wanted) and the
                messages' SSZ bytes back to back.  Answers one letter a
                message (``A``, ``R``, ``I``), the number of attesters of
                each and, where asked, each aggregate key as 96 bytes of hex
                (x then y, big-endian; 96 zero bytes for the identity).
``table``       answers the latest-message table: validator indices, target
                epochs and beacon block roots of every validator that has one.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from plainref import B96, U64, Container, Reference, Refused, need, read_message
from plainref_epoch import g1_add, g1_decompress

# consensus-specs phase0 p2p-interface.md / config
ATTESTATION_PROPAGATION_SLOT_RANGE = 32
ACCEPT, REJECT, IGNORE = "A", "R", "I"


class AggregateReference(Reference):
    """The anchor state, the latest messages and the registry's keys."""

    def __init__(self, preset: str, seconds_per_slot: int):
        super().__init__(preset, seconds_per_slot)
        aggregate_and_proof = Container(
            aggregator_index=U64, aggregate=self.t.Attestation, selection_proof=B96)
        self.SignedAggregateAndProof = Container(message=aggregate_and_proof, signature=B96)
        self.anchor_root: bytes | None = None
        # the latest messages: target epoch (-1: none) and root, per validator
        self.latest_epoch = np.zeros(0, np.int64)
        self.latest_root = np.zeros(0, np.int64)  # an index into self.roots
        self.roots: list[bytes] = []
        self._points: dict[bytes, tuple] = {}  # compressed key -> affine point

    def hold(self, state: dict) -> bytes:
        self.state = state
        self._registry.clear()
        header = dict(state["latest_block_header"])
        if header["state_root"] == b"\x00" * 32:
            header["state_root"] = self.state_root()
        self.anchor_root = self.t.BeaconBlockHeader.root(header)
        n = len(state["validators"])
        self.latest_epoch, self.latest_root = np.full(n, -1, np.int64), np.zeros(n, np.int64)
        return self.anchor_root

    def pubkey_point(self, validator: int):
        raw = bytes(self.state["validators"][validator][:48])
        point = self._points.get(raw)
        if point is None:
            point = self._points[raw] = g1_decompress(raw)
        return point

    def aggregate_key(self, attesters) -> tuple | None:
        """``eth_aggregate_pubkeys``: the participants' keys added one by one."""
        total = None
        for v in attesters:
            total = g1_add(total, self.pubkey_point(int(v)))
        return total

    def verdict(self, raw, pushed_slot: int, valid: bool):
        """``(letter, attesting indices)`` of one message; the indices are
        empty where the message names no committee."""
        none = np.zeros(0, np.int64)
        try:
            att = self.SignedAggregateAndProof.decode(raw)["message"]["aggregate"]
        except Exception:
            return REJECT, none
        data, bits = att["data"], att["aggregation_bits"]
        slot, index, target = data["slot"], data["index"], data["target"]
        epoch = target["epoch"]
        if epoch != self.epoch_at(slot):
            return REJECT, none
        if index >= self.committees_per_slot(epoch):
            return REJECT, none
        committee = self.beacon_committee(slot, index)
        if len(bits) != len(committee):
            return REJECT, none
        attesters = committee[bits]  # get_attesting_indices
        if not len(attesters):
            return REJECT, attesters
        if not slot + 1 <= pushed_slot <= slot + ATTESTATION_PROPAGATION_SLOT_RANGE:
            return IGNORE, attesters
        now = self.epoch_at(pushed_slot)
        if epoch not in (now, max(now, 1) - 1):
            return IGNORE, attesters
        if data["beacon_block_root"] != self.anchor_root:
            return IGNORE, attesters  # a block this node has not seen
        if target["root"] != self.anchor_root:
            return REJECT, attesters
        if not valid:
            return REJECT, attesters
        # update_latest_messages: where the target epoch is newer than the one held
        root = data["beacon_block_root"]
        if root not in self.roots:
            self.roots.append(root)
        newer = attesters[self.latest_epoch[attesters] < epoch]
        self.latest_epoch[newer] = epoch
        self.latest_root[newer] = self.roots.index(root)
        return ACCEPT, attesters

    def table(self) -> dict:
        order = np.flatnonzero(self.latest_epoch >= 0)
        return {
            "validators": order.astype("<u4").tobytes().hex(),
            "epochs": self.latest_epoch[order].astype("<u4").tobytes().hex(),
            "root_ids": self.latest_root[order].astype("<u4").tobytes().hex(),
            "roots": [r.hex() for r in self.roots],
        }


def point_hex(point) -> str:
    if point is None:
        return "00" * 96
    return (point[0].to_bytes(48, "big") + point[1].to_bytes(48, "big")).hex()


def answer(ref: AggregateReference, header: dict, payload: bytes) -> dict:
    if header["cmd"] == "state":
        root = ref.hold(ref.t.BeaconState.decode(payload))
        return {"kind": "state", "slot": ref.state["slot"],
                "validators": len(ref.state["validators"]), "anchor_root": root.hex()}
    need(ref.state is not None, "no state yet")
    if header["cmd"] == "aggregates":
        verdicts, counts, sums, at = [], [], [], 0
        view = memoryview(payload)
        for pushed, valid, size in zip(
                header["pushed_slots"], header["valid"], header["sizes"]):
            letter, attesters = ref.verdict(view[at:at + size], pushed, bool(valid))
            verdicts.append(letter)
            counts.append(len(attesters))
            if header.get("sums"):
                sums.append(point_hex(ref.aggregate_key(attesters)))
            at += size
        need(at == len(payload), "the sizes do not add up to the bytes")
        out = {"kind": "aggregates", "verdicts": "".join(verdicts), "attesters": counts}
        if header.get("sums"):
            out["sums"] = sums
        return out
    if header["cmd"] == "table":
        return {"kind": "table", **ref.table()}
    raise Refused(f"unknown command {header['cmd']!r}")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ref = AggregateReference(argv[0], int(argv[1]))
    stream = sys.stdin.buffer
    while True:
        header, payload = read_message(stream)
        if header is None:
            return 0
        t0 = time.perf_counter()
        try:
            out = answer(ref, header, payload)
        except Refused as e:
            out = {"kind": "refused", "cmd": header.get("cmd"), "what": str(e)}
        except Exception as e:  # a reference that crashes has not agreed
            out = {"kind": "refused", "cmd": header.get("cmd"),
                   "what": f"{type(e).__name__}: {e}"}
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
