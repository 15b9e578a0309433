#!/usr/bin/env python3
"""benchmark/plainref_subnet.py — the plain reference of the attestation
subnet channel, in a process of its own.

The validation conditions of the ``beacon_attestation_{subnet_id}`` topic
(ethereum/consensus-specs phase0 ``p2p-interface.md``) and the latest-message
table ``on_attestation`` leaves (phase0 ``fork-choice.md``
``update_latest_messages``), written from the specs on ``hashlib`` and
``numpy``: **it imports nothing of the program under test**.  SSZ decoding,
the swap-or-not shuffle and the committee slicing are ``plainref.py``'s (the
plain reference of block import, beside this file).

Given the anchor state and, in push order, each message's subnet, its raw SSZ
bytes, the slot the clock showed when it was pushed and whether its signature
is valid, it answers each message's verdict:

  REJECT  not an ``Attestation``; not exactly one aggregation bit; committee
          index out of range; ``compute_subnet_for_attestation`` is not the
          topic's subnet; target epoch is not the slot's; bit list is not the
          committee's length; target root is not the anchor; signature invalid
  IGNORE  slot outside ``ATTESTATION_PROPAGATION_SLOT_RANGE`` (or in the
          future); target epoch neither current nor previous; the attester
          already had a valid vote for this target epoch; head block unknown
  ACCEPT  otherwise — and the attester's latest message becomes (target epoch,
          beacon block root) where the epoch is newer than the one held

What it does not do, and says so: the BLS pairing (validity of a signature is
known by construction and enters as the minter's bit; an independent
BLS12-381 verifier is an open question, PERF.md section 7), fork-choice
weights, and the target/ancestor conditions beyond "the anchor" (the chain
above the anchor is empty: the only known block is the anchor, every epoch's
checkpoint state is the anchor state read at that epoch — ``process_epoch`` is
not followed).

Messages on stdin as ``plainref.py`` frames them (8-byte length, JSON header,
``header["bytes"]`` bytes).  One JSON line leaves per message:

``state``  a ``BeaconState``: the anchor.  Answers the anchor block's root.
``votes``  ``subnets``, ``pushed_slots``, ``valid``, ``sizes`` (one entry a
           message) and the messages' SSZ bytes back to back.  Answers one
           letter a message: ``A``, ``R`` or ``I``.
``table``  answers the latest-message table: validator indices, target epochs
           and beacon block roots of every validator that has one.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from plainref import DOMAIN_BEACON_ATTESTER, Reference, Refused, need, read_message  # noqa: F401

# consensus-specs phase0 p2p-interface.md / config
ATTESTATION_SUBNET_COUNT = 64
ATTESTATION_PROPAGATION_SLOT_RANGE = 32
ACCEPT, REJECT, IGNORE = "A", "R", "I"


class SubnetReference(Reference):
    """The anchor state, the attesters seen and the latest messages."""

    def __init__(self, preset: str, seconds_per_slot: int):
        super().__init__(preset, seconds_per_slot)
        self.anchor_root: bytes | None = None
        self.seen: set[tuple[int, int]] = set()  # (target epoch, validator)
        self.latest: dict[int, tuple[int, bytes]] = {}  # validator -> (epoch, root)

    def hold(self, state: dict) -> bytes:
        self.state = state
        self._registry.clear()
        header = dict(state["latest_block_header"])
        if header["state_root"] == b"\x00" * 32:
            header["state_root"] = self.state_root()
        self.anchor_root = self.t.BeaconBlockHeader.root(header)
        return self.anchor_root

    def subnet_for(self, cps: int, slot: int, index: int) -> int:
        """p2p-interface.md ``compute_subnet_for_attestation``."""
        slots_since_epoch_start = slot % self.p["SLOTS_PER_EPOCH"]
        return (cps * slots_since_epoch_start + index) % ATTESTATION_SUBNET_COUNT

    def verdict(self, subnet: int, raw: bytes, pushed_slot: int, valid: bool) -> str:
        try:
            att = self.t.Attestation.decode(raw)
        except Exception:
            return REJECT
        data, bits = att["data"], att["aggregation_bits"]
        slot, index, target = data["slot"], data["index"], data["target"]
        epoch = target["epoch"]
        if int(bits.sum()) != 1:
            return REJECT
        if epoch != self.epoch_at(slot):
            return REJECT
        cps = self.committees_per_slot(epoch)
        if index >= cps:
            return REJECT
        if self.subnet_for(cps, slot, index) != subnet:
            return REJECT
        if not slot <= pushed_slot <= slot + ATTESTATION_PROPAGATION_SLOT_RANGE:
            return IGNORE
        # fork-choice.md validate_on_attestation: only later slots count it
        if pushed_slot < slot + 1:
            return IGNORE
        now = self.epoch_at(pushed_slot)
        if epoch not in (now, max(now, 1) - 1):
            return IGNORE
        committee = self.beacon_committee(slot, index)
        if len(bits) != len(committee):
            return REJECT
        attester = int(committee[int(np.flatnonzero(bits)[0])])
        if (epoch, attester) in self.seen:
            return IGNORE
        if data["beacon_block_root"] != self.anchor_root:
            return IGNORE  # a block this node has not seen
        if target["root"] != self.anchor_root:
            return REJECT
        if not valid:
            return REJECT
        self.seen.add((epoch, attester))
        held = self.latest.get(attester)
        if held is None or epoch > held[0]:
            self.latest[attester] = (epoch, data["beacon_block_root"])
        return ACCEPT

    def table(self) -> dict:
        order = sorted(self.latest)
        roots = sorted({self.latest[v][1] for v in order})
        at = {r: i for i, r in enumerate(roots)}
        return {
            "validators": np.asarray(order, "<u4").tobytes().hex(),
            "epochs": np.asarray([self.latest[v][0] for v in order], "<u4").tobytes().hex(),
            "root_ids": np.asarray([at[self.latest[v][1]] for v in order], "<u4").tobytes().hex(),
            "roots": [r.hex() for r in roots],
        }


def answer(ref: SubnetReference, header: dict, payload: bytes) -> dict:
    if header["cmd"] == "state":
        root = ref.hold(ref.t.BeaconState.decode(payload))
        return {"kind": "state", "slot": ref.state["slot"],
                "validators": len(ref.state["validators"]), "anchor_root": root.hex()}
    need(ref.state is not None, "no state yet")
    if header["cmd"] == "votes":
        verdicts, at = [], 0
        view = memoryview(payload)
        for subnet, pushed, valid, size in zip(
                header["subnets"], header["pushed_slots"], header["valid"], header["sizes"]):
            verdicts.append(ref.verdict(subnet, view[at:at + size], pushed, bool(valid)))
            at += size
        need(at == len(payload), "the sizes do not add up to the bytes")
        return {"kind": "votes", "verdicts": "".join(verdicts), "seen": len(ref.seen)}
    if header["cmd"] == "table":
        return {"kind": "table", **ref.table()}
    raise Refused(f"unknown command {header['cmd']!r}")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ref = SubnetReference(argv[0], int(argv[1]))
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
