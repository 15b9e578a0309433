#!/usr/bin/env python3
"""benchmark/plainref.py — the plain reference of block import, in a process
of its own.

A straightforward capella ``state_transition`` for blocks inside one epoch,
written from ethereum/consensus-specs (phase0/altair/bellatrix/capella
``beacon-chain.md``, ``ssz/simple-serialize.md``) on ``hashlib``, ``numpy`` and
nothing else: **it imports nothing of the program under test** and shares no
table with it — the presets below are the spec's ``presets/{mainnet,minimal}``
copied by hand.  It decodes SSZ itself, follows ``process_slots`` and
``process_block`` field by field and roots the state with ``hashlib.sha256``.

What it does not do, and says so instead of guessing: verify BLS signatures
(the traffic is all valid; the signature path has a control of its own),
cross an epoch boundary (``process_epoch``), or process any operation but
attestations (a block that carries one is refused).

Messages on stdin: an 8-byte little-endian length, a JSON header of that
length, then ``header["bytes"]`` bytes of SSZ.  One JSON line leaves on
stdout per message:

``state``     a ``BeaconState``: the pre-state of the first block
``block``     a ``SignedBeaconBlock``: applied to the state held; answers the
              block's root, the post-state root computed here and the one the
              block claims
``readback``  a ``BeaconState`` as some store gave it back: compared field by
              field with the state held here
"""

from __future__ import annotations

import hashlib
import json
import struct
import sys
import time

import numpy as np

# consensus-specs presets/{mainnet,minimal}/{phase0,altair,bellatrix,capella}.yaml
PRESETS = {
    "mainnet": dict(
        SLOTS_PER_EPOCH=32, SLOTS_PER_HISTORICAL_ROOT=8192,
        EPOCHS_PER_HISTORICAL_VECTOR=65536, EPOCHS_PER_SLASHINGS_VECTOR=8192,
        EPOCHS_PER_ETH1_VOTING_PERIOD=64, SYNC_COMMITTEE_SIZE=512,
        MAX_COMMITTEES_PER_SLOT=64, TARGET_COMMITTEE_SIZE=128, SHUFFLE_ROUND_COUNT=90,
        MAX_WITHDRAWALS_PER_PAYLOAD=16, MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP=16384),
    "minimal": dict(
        SLOTS_PER_EPOCH=8, SLOTS_PER_HISTORICAL_ROOT=64,
        EPOCHS_PER_HISTORICAL_VECTOR=64, EPOCHS_PER_SLASHINGS_VECTOR=64,
        EPOCHS_PER_ETH1_VOTING_PERIOD=4, SYNC_COMMITTEE_SIZE=32,
        MAX_COMMITTEES_PER_SLOT=4, TARGET_COMMITTEE_SIZE=4, SHUFFLE_ROUND_COUNT=10,
        MAX_WITHDRAWALS_PER_PAYLOAD=4, MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP=16),
}
# the same in both presets
HISTORICAL_ROOTS_LIMIT = 2 ** 24
VALIDATOR_REGISTRY_LIMIT = 2 ** 40
MAX_VALIDATORS_PER_COMMITTEE = 2048
MAX_PROPOSER_SLASHINGS, MAX_ATTESTER_SLASHINGS, MAX_ATTESTATIONS = 16, 2, 128
MAX_DEPOSITS, MAX_VOLUNTARY_EXITS, MAX_BLS_TO_EXECUTION_CHANGES = 16, 16, 16
MAX_BYTES_PER_TRANSACTION, MAX_TRANSACTIONS_PER_PAYLOAD = 2 ** 30, 2 ** 20
MAX_EXTRA_DATA_BYTES, BYTES_PER_LOGS_BLOOM = 32, 256
MIN_ATTESTATION_INCLUSION_DELAY, MIN_SEED_LOOKAHEAD = 1, 1
EFFECTIVE_BALANCE_INCREMENT, MAX_EFFECTIVE_BALANCE = 10 ** 9, 32 * 10 ** 9
BASE_REWARD_FACTOR = 64
TIMELY_SOURCE, TIMELY_TARGET, TIMELY_HEAD = 0, 1, 2
PARTICIPATION_FLAG_WEIGHTS = (14, 26, 14)
SYNC_REWARD_WEIGHT, PROPOSER_WEIGHT, WEIGHT_DENOMINATOR = 2, 8, 64
DOMAIN_BEACON_PROPOSER, DOMAIN_BEACON_ATTESTER = b"\x00\x00\x00\x00", b"\x01\x00\x00\x00"
ETH1_ADDRESS_WITHDRAWAL_PREFIX = 1


class Refused(Exception):
    """The block is invalid by the spec, or outside what this reference follows."""


def need(cond, what: str) -> None:
    if not cond:
        raise Refused(what)


# ------------------------------------------------------------------ SSZ


def sha(data) -> bytes:
    return hashlib.sha256(data).digest()


ZERO = [b"\x00" * 32]
for _ in range(64):
    ZERO.append(sha(ZERO[-1] * 2))


def pad32(data: bytes) -> bytes:
    return data + b"\x00" * (-len(data) % 32)


def merkleize(chunks, limit: int | None = None) -> bytes:
    """The root of 32-byte ``chunks`` (one bytes-like), padded with zero
    chunks to ``limit``."""
    count = len(chunks) // 32
    limit = count if limit is None else limit
    need(count <= limit, "more chunks than the type's limit")
    depth = (limit - 1).bit_length() if limit > 1 else 0
    if count == 0:
        return ZERO[depth]
    layer = bytes(chunks)
    for d in range(depth):
        if len(layer) == 32:  # alone on its level: the sibling is a zero subtree
            layer = sha(layer + ZERO[d])
            continue
        if (len(layer) // 32) & 1:
            layer += ZERO[d]
        view = memoryview(layer)
        layer = b"".join([hashlib.sha256(view[i:i + 64]).digest()
                          for i in range(0, len(layer), 64)])
    return layer


def mix_in_length(root: bytes, length: int) -> bytes:
    return sha(root + length.to_bytes(32, "little"))


class Uint:
    def __init__(self, size: int):
        self.fixed = size

    def decode(self, data) -> int:
        return int.from_bytes(data, "little")

    def root(self, value: int) -> bytes:
        return int(value).to_bytes(self.fixed, "little").ljust(32, b"\x00")


class ByteVector:
    def __init__(self, size: int):
        self.fixed = size

    def decode(self, data) -> bytes:
        return bytes(data)

    def root(self, value: bytes) -> bytes:
        return merkleize(pad32(value))


class ByteList:
    fixed = None

    def __init__(self, limit: int):
        self.limit = limit

    def decode(self, data) -> bytes:
        need(len(data) <= self.limit, "byte list over its limit")
        return bytes(data)

    def root(self, value: bytes) -> bytes:
        return mix_in_length(merkleize(pad32(value), (self.limit + 31) // 32), len(value))


class Bits:
    """Bitvector (``is_list`` false) or Bitlist; the value is a bool array."""

    def __init__(self, size: int, is_list: bool):
        self.size, self.is_list = size, is_list
        self.fixed = None if is_list else (size + 7) // 8

    def decode(self, data):
        raw = np.frombuffer(bytes(data), np.uint8)
        if not self.is_list:
            return np.unpackbits(raw, bitorder="little")[: self.size].astype(bool)
        need(len(raw) > 0 and raw[-1] != 0, "bitlist without its delimiter")
        n = (len(raw) - 1) * 8 + int(raw[-1]).bit_length() - 1
        need(n <= self.size, "bitlist over its limit")
        return np.unpackbits(raw, bitorder="little")[:n].astype(bool)

    def root(self, value) -> bytes:
        packed = pad32(np.packbits(value, bitorder="little").tobytes())
        root = merkleize(packed, (self.size + 255) // 256)
        return mix_in_length(root, len(value)) if self.is_list else root


class Sequence:
    """Vector (``is_list`` false) or List of ``elem``.  Basic elements decode
    to a numpy array, 32-byte vectors to one ``bytearray``, the rest to a
    Python list."""

    def __init__(self, elem, size: int, is_list: bool):
        self.elem, self.size, self.is_list = elem, size, is_list
        self.fixed = None if is_list or elem.fixed is None else elem.fixed * size

    def decode(self, data):
        elem, data = self.elem, memoryview(data)
        if elem.fixed is not None:
            need(len(data) % elem.fixed == 0, "sequence of a broken length")
            n = len(data) // elem.fixed
            need(n <= self.size if self.is_list else n == self.size, "sequence length")
            if isinstance(elem, Uint) and elem.fixed in (1, 8):
                return np.frombuffer(bytes(data), f"<u{elem.fixed}").copy()
            if isinstance(elem, ByteVector) and elem.fixed == 32:
                return bytearray(data)
            return [elem.decode(data[i * elem.fixed:(i + 1) * elem.fixed]) for i in range(n)]
        if len(data) == 0:
            return []
        first = int.from_bytes(data[:4], "little")
        need(first % 4 == 0 and 0 < first <= len(data), "broken first offset")
        offsets = [int.from_bytes(data[i:i + 4], "little") for i in range(0, first, 4)]
        need(len(offsets) <= self.size, "list over its limit")
        ends = offsets[1:] + [len(data)]
        need(all(a <= b for a, b in zip(offsets, ends)), "offsets out of order")
        return [elem.decode(data[a:b]) for a, b in zip(offsets, ends)]

    def root(self, value) -> bytes:
        elem = self.elem
        if isinstance(value, np.ndarray):
            root = merkleize(pad32(value.tobytes()), (self.size * elem.fixed + 31) // 32)
        elif isinstance(value, bytearray):
            root = merkleize(value, self.size)
        else:
            root = merkleize(b"".join([elem.root(v) for v in value]), self.size)
        return mix_in_length(root, len(value) // 32 if isinstance(value, bytearray)
                             else len(value)) if self.is_list else root


class ValidatorList:
    """``List[Validator, VALIDATOR_REGISTRY_LIMIT]`` as one (n, 121) byte
    array: pubkey 48, withdrawal_credentials 32, effective_balance 8, slashed
    1, then four epochs of 8."""

    fixed, RECORD = None, 121

    def decode(self, data):
        need(len(data) % self.RECORD == 0, "registry of a broken length")
        return np.frombuffer(bytes(data), np.uint8).reshape(-1, self.RECORD).copy()

    @staticmethod
    def record_root(rec: bytes) -> bytes:
        chunks = (sha(rec[:48] + b"\x00" * 16), rec[48:80], pad32(rec[80:88]),
                  pad32(rec[88:89]), pad32(rec[89:97]), pad32(rec[97:105]),
                  pad32(rec[105:113]), pad32(rec[113:121]))
        return merkleize(b"".join(chunks))

    def root(self, value) -> bytes:
        seen: dict[bytes, bytes] = {}  # equal records have equal roots
        raw, size, out = value.tobytes(), self.RECORD, []
        for i in range(0, len(raw), size):
            rec = raw[i:i + size]
            hit = seen.get(rec)
            if hit is None:
                hit = seen[rec] = self.record_root(rec)
            out.append(hit)
        return mix_in_length(merkleize(b"".join(out), VALIDATOR_REGISTRY_LIMIT), len(out))


class Container:
    def __init__(self, **fields):
        self.fields = fields
        sizes = [t.fixed for t in fields.values()]
        self.fixed = None if None in sizes else sum(sizes)

    def decode(self, data) -> dict:
        data = memoryview(data)
        out, variable, at = {}, [], 0
        for name, t in self.fields.items():
            if t.fixed is None:
                variable.append((name, t, int.from_bytes(data[at:at + 4], "little")))
                at += 4
            else:
                out[name] = t.decode(data[at:at + t.fixed])
                at += t.fixed
        need(at <= len(data), "container shorter than its fixed part")
        need(not variable or variable[0][2] == at, "container's first offset")
        need(variable or at == len(data), "container longer than its fields")
        ends = [o for _n, _t, o in variable[1:]] + [len(data)]
        for (name, t, start), end in zip(variable, ends):
            need(start <= end <= len(data), "container offsets out of order")
            out[name] = t.decode(data[start:end])
        return out

    def root(self, value: dict) -> bytes:
        return merkleize(b"".join([t.root(value[n]) for n, t in self.fields.items()]))


def Vector(elem, size):  # noqa: N802 — the spec's names
    return Sequence(elem, size, False)


def List(elem, limit):  # noqa: N802
    return Sequence(elem, limit, True)


U8, U64, U256 = Uint(1), Uint(8), Uint(32)
B4, B20, B32, B48, B96 = (ByteVector(n) for n in (4, 20, 32, 48, 96))


class Types:
    """The capella containers of one preset."""

    def __init__(self, p: dict):
        C = Container
        self.Checkpoint = C(epoch=U64, root=B32)
        self.BeaconBlockHeader = C(slot=U64, proposer_index=U64, parent_root=B32,
                                   state_root=B32, body_root=B32)
        self.Eth1Data = C(deposit_root=B32, deposit_count=U64, block_hash=B32)
        self.AttestationData = C(slot=U64, index=U64, beacon_block_root=B32,
                                 source=self.Checkpoint, target=self.Checkpoint)
        self.Attestation = C(aggregation_bits=Bits(MAX_VALIDATORS_PER_COMMITTEE, True),
                             data=self.AttestationData, signature=B96)
        indexed = C(attesting_indices=List(U64, MAX_VALIDATORS_PER_COMMITTEE),
                    data=self.AttestationData, signature=B96)
        signed_header = C(message=self.BeaconBlockHeader, signature=B96)
        deposit_data = C(pubkey=B48, withdrawal_credentials=B32, amount=U64, signature=B96)
        self.SyncCommittee = C(pubkeys=Vector(B48, p["SYNC_COMMITTEE_SIZE"]),
                               aggregate_pubkey=B48)
        self.SyncAggregate = C(sync_committee_bits=Bits(p["SYNC_COMMITTEE_SIZE"], False),
                               sync_committee_signature=B96)
        self.Withdrawal = C(index=U64, validator_index=U64, address=B20, amount=U64)
        payload_head = dict(
            parent_hash=B32, fee_recipient=B20, state_root=B32, receipts_root=B32,
            logs_bloom=ByteVector(BYTES_PER_LOGS_BLOOM), prev_randao=B32, block_number=U64,
            gas_limit=U64, gas_used=U64, timestamp=U64,
            extra_data=ByteList(MAX_EXTRA_DATA_BYTES), base_fee_per_gas=U256, block_hash=B32)
        self.Transactions = List(ByteList(MAX_BYTES_PER_TRANSACTION),
                                 MAX_TRANSACTIONS_PER_PAYLOAD)
        self.Withdrawals = List(self.Withdrawal, p["MAX_WITHDRAWALS_PER_PAYLOAD"])
        self.ExecutionPayload = C(**payload_head, transactions=self.Transactions,
                                  withdrawals=self.Withdrawals)
        self.ExecutionPayloadHeader = C(**payload_head, transactions_root=B32,
                                        withdrawals_root=B32)
        self.BeaconBlockBody = C(
            randao_reveal=B96, eth1_data=self.Eth1Data, graffiti=B32,
            proposer_slashings=List(C(signed_header_1=signed_header,
                                      signed_header_2=signed_header), MAX_PROPOSER_SLASHINGS),
            attester_slashings=List(C(attestation_1=indexed, attestation_2=indexed),
                                    MAX_ATTESTER_SLASHINGS),
            attestations=List(self.Attestation, MAX_ATTESTATIONS),
            deposits=List(C(proof=Vector(B32, 33), data=deposit_data), MAX_DEPOSITS),
            voluntary_exits=List(C(message=C(epoch=U64, validator_index=U64),
                                   signature=B96), MAX_VOLUNTARY_EXITS),
            sync_aggregate=self.SyncAggregate, execution_payload=self.ExecutionPayload,
            bls_to_execution_changes=List(
                C(message=C(validator_index=U64, from_bls_pubkey=B48,
                            to_execution_address=B20), signature=B96),
                MAX_BLS_TO_EXECUTION_CHANGES))
        self.BeaconBlock = C(slot=U64, proposer_index=U64, parent_root=B32,
                             state_root=B32, body=self.BeaconBlockBody)
        self.SignedBeaconBlock = C(message=self.BeaconBlock, signature=B96)
        roots = Vector(B32, p["SLOTS_PER_HISTORICAL_ROOT"])
        self.BeaconState = C(
            genesis_time=U64, genesis_validators_root=B32, slot=U64,
            fork=C(previous_version=B4, current_version=B4, epoch=U64),
            latest_block_header=self.BeaconBlockHeader, block_roots=roots, state_roots=roots,
            historical_roots=List(B32, HISTORICAL_ROOTS_LIMIT), eth1_data=self.Eth1Data,
            eth1_data_votes=List(self.Eth1Data, p["EPOCHS_PER_ETH1_VOTING_PERIOD"]
                                 * p["SLOTS_PER_EPOCH"]),
            eth1_deposit_index=U64, validators=ValidatorList(),
            balances=List(U64, VALIDATOR_REGISTRY_LIMIT),
            randao_mixes=Vector(B32, p["EPOCHS_PER_HISTORICAL_VECTOR"]),
            slashings=Vector(U64, p["EPOCHS_PER_SLASHINGS_VECTOR"]),
            previous_epoch_participation=List(U8, VALIDATOR_REGISTRY_LIMIT),
            current_epoch_participation=List(U8, VALIDATOR_REGISTRY_LIMIT),
            justification_bits=Bits(4, False),
            previous_justified_checkpoint=self.Checkpoint,
            current_justified_checkpoint=self.Checkpoint,
            finalized_checkpoint=self.Checkpoint,
            inactivity_scores=List(U64, VALIDATOR_REGISTRY_LIMIT),
            current_sync_committee=self.SyncCommittee, next_sync_committee=self.SyncCommittee,
            latest_execution_payload_header=self.ExecutionPayloadHeader,
            next_withdrawal_index=U64, next_withdrawal_validator_index=U64,
            historical_summaries=List(C(block_summary_root=B32, state_summary_root=B32),
                                      HISTORICAL_ROOTS_LIMIT))


def same(a, b) -> bool:
    """Two decoded values, equal to the last byte."""
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(same(a[k], b[k]) for k in a)
    if isinstance(a, np.ndarray):
        return a.shape == b.shape and bool(np.array_equal(a, b))
    if isinstance(a, list):
        return len(a) == len(b) and all(same(x, y) for x, y in zip(a, b))
    return a == b


# ------------------------------------------------------------ transition


def column(validators, lo: int, hi: int):
    """A ``uint64`` field of every validator record."""
    return np.ascontiguousarray(validators[:, lo:hi]).view("<u8").ravel()


def integer_squareroot(n: int) -> int:
    x, y = n, (n + 1) // 2
    while y < x:
        x, y = y, (y + n // y) // 2
    return x


class Reference:
    """The state held, and the spec's functions over it."""

    def __init__(self, preset: str, seconds_per_slot: int):
        self.p = PRESETS[preset]
        self.seconds_per_slot = seconds_per_slot
        self.t = Types(self.p)
        self.state: dict | None = None
        self._field_roots: dict[str, tuple] = {}  # the big unchanged fields' roots
        # what follows from the registry alone.  No operation followed here
        # changes a validator record (those that do are refused), so it is
        # worked out once for the state held and dropped with it
        self._registry: dict = {}

    # -- roots

    def state_root(self) -> bytes:
        """``hash_tree_root(state)``.  The registry is rooted once; the other
        long fields are re-rooted only when their bytes changed."""
        state, roots = self.state, []
        for name, t in self.t.BeaconState.fields.items():
            value = state[name]
            if name == "validators":
                roots.append(self.of_registry("root", lambda: t.root(state["validators"])))
            elif isinstance(value, (np.ndarray, bytearray)) and len(value) >= 1024:
                key = hashlib.blake2b(value.tobytes() if isinstance(value, np.ndarray)
                                      else value, digest_size=16).digest()
                hit = self._field_roots.get(name)
                if hit is None or hit[0] != key:
                    hit = self._field_roots[name] = (key, t.root(value))
                roots.append(hit[1])
            else:
                roots.append(t.root(value))
        return merkleize(b"".join(roots))

    # -- accessors

    def epoch_at(self, slot: int) -> int:
        return slot // self.p["SLOTS_PER_EPOCH"]

    def current_epoch(self) -> int:
        return self.epoch_at(self.state["slot"])

    def previous_epoch(self) -> int:
        return max(self.current_epoch(), 1) - 1

    def randao_mix(self, epoch: int) -> bytes:
        i = epoch % self.p["EPOCHS_PER_HISTORICAL_VECTOR"]
        return bytes(self.state["randao_mixes"][32 * i:32 * i + 32])

    def block_root_at_slot(self, slot: int) -> bytes:
        span = self.p["SLOTS_PER_HISTORICAL_ROOT"]
        need(slot < self.state["slot"] <= slot + span, "block root out of range")
        i = slot % span
        return bytes(self.state["block_roots"][32 * i:32 * i + 32])

    def of_registry(self, key, make):
        hit = self._registry.get(key)
        if hit is None:
            hit = self._registry[key] = make()
        return hit

    def effective_balances(self):
        return self.of_registry("eff", lambda: column(self.state["validators"], 80, 88))

    def active_indices(self, epoch: int):
        def make():
            v = self.state["validators"]
            active = (column(v, 97, 105) <= epoch) & (epoch < column(v, 105, 113))
            return np.flatnonzero(active)

        return self.of_registry(("active", epoch), make)

    def total_active_balance(self) -> int:
        def make():
            eff = self.effective_balances()[self.active_indices(self.current_epoch())]
            return max(EFFECTIVE_BALANCE_INCREMENT, int(eff.sum(dtype=np.uint64)))

        return self.of_registry(("total", self.current_epoch()), make)

    def base_reward_per_increment(self) -> int:
        return (EFFECTIVE_BALANCE_INCREMENT * BASE_REWARD_FACTOR
                // integer_squareroot(self.total_active_balance()))

    def seed(self, epoch: int, domain_type: bytes) -> bytes:
        mix = self.randao_mix(epoch + self.p["EPOCHS_PER_HISTORICAL_VECTOR"]
                              - MIN_SEED_LOOKAHEAD - 1)
        return sha(domain_type + epoch.to_bytes(8, "little") + mix)

    def shuffled_index(self, index: int, count: int, seed: bytes) -> int:
        for r in range(self.p["SHUFFLE_ROUND_COUNT"]):
            rb = bytes([r])
            pivot = int.from_bytes(sha(seed + rb)[:8], "little") % count
            flip = (pivot + count - index) % count
            position = max(index, flip)
            source = sha(seed + rb + (position // 256).to_bytes(4, "little"))
            if (source[(position % 256) // 8] >> (position % 8)) & 1:
                index = flip
        return index

    def shuffled_active(self, epoch: int):
        """``[active[compute_shuffled_index(i)] for i in range(n)]``, every
        index walked through the rounds at once."""
        seed = self.seed(epoch, DOMAIN_BEACON_ATTESTER)
        return self.of_registry(("shuffled", epoch, seed), lambda: self.shuffle(epoch, seed))

    def shuffle(self, epoch: int, seed: bytes):
        active = self.active_indices(epoch)
        n = len(active)
        index = np.arange(n, dtype=np.int64)
        for r in range(self.p["SHUFFLE_ROUND_COUNT"]):
            rb = bytes([r])
            pivot = int.from_bytes(sha(seed + rb)[:8], "little") % n
            flip = (pivot + n - index) % n
            position = np.maximum(index, flip)
            table = np.frombuffer(b"".join(
                [sha(seed + rb + k.to_bytes(4, "little")) for k in range((n + 255) // 256 + 1)]),
                np.uint8).reshape(-1, 32)
            byte = table[position >> 8, (position & 255) >> 3]
            bit = (byte >> (position & 7).astype(np.uint8)) & 1
            index = np.where(bit.astype(bool), flip, index)
        return active[index]

    def committees_per_slot(self, epoch: int) -> int:
        p = self.p
        return max(1, min(p["MAX_COMMITTEES_PER_SLOT"],
                          len(self.active_indices(epoch)) // p["SLOTS_PER_EPOCH"]
                          // p["TARGET_COMMITTEE_SIZE"]))

    def beacon_committee(self, slot: int, index: int):
        epoch, spe = self.epoch_at(slot), self.p["SLOTS_PER_EPOCH"]
        cps = self.committees_per_slot(epoch)
        shuffled = self.shuffled_active(epoch)
        n, i, count = len(shuffled), (slot % spe) * cps + index, cps * spe
        return shuffled[n * i // count: n * (i + 1) // count]

    def proposer_index(self) -> int:
        epoch, slot = self.current_epoch(), self.state["slot"]
        seed = sha(self.seed(epoch, DOMAIN_BEACON_PROPOSER) + slot.to_bytes(8, "little"))
        indices, eff = self.active_indices(epoch), self.effective_balances()
        need(len(indices) > 0, "no active validator")
        i, total = 0, len(indices)
        while True:
            candidate = int(indices[self.shuffled_index(i % total, total, seed)])
            random_byte = sha(seed + (i // 32).to_bytes(8, "little"))[i % 32]
            if int(eff[candidate]) * 255 >= MAX_EFFECTIVE_BALANCE * random_byte:
                return candidate
            i += 1

    def increase_balance(self, index: int, delta: int) -> None:
        self.state["balances"][index] += np.uint64(delta)

    def decrease_balance(self, index: int, delta: int) -> None:
        b = int(self.state["balances"][index])
        self.state["balances"][index] = np.uint64(0 if delta > b else b - delta)

    # -- slots

    def process_slots(self, slot: int) -> None:
        state, p = self.state, self.p
        need(state["slot"] < slot, "block not after the state")
        while state["slot"] < slot:
            span = p["SLOTS_PER_HISTORICAL_ROOT"]
            i = state["slot"] % span
            previous_state_root = self.state_root()
            state["state_roots"][32 * i:32 * i + 32] = previous_state_root
            header = state["latest_block_header"]
            if header["state_root"] == b"\x00" * 32:
                header["state_root"] = previous_state_root
            state["block_roots"][32 * i:32 * i + 32] = self.t.BeaconBlockHeader.root(header)
            need((state["slot"] + 1) % p["SLOTS_PER_EPOCH"] != 0,
                 "not followed here: process_epoch (an epoch boundary)")
            state["slot"] += 1

    # -- block

    def process_block_header(self, block: dict) -> int:
        state = self.state
        need(block["slot"] == state["slot"], "block slot")
        need(block["slot"] > state["latest_block_header"]["slot"], "block not newer")
        proposer = self.proposer_index()
        need(block["proposer_index"] == proposer, "wrong proposer index")
        need(block["parent_root"]
             == self.t.BeaconBlockHeader.root(state["latest_block_header"]), "parent root")
        state["latest_block_header"] = {
            "slot": block["slot"], "proposer_index": block["proposer_index"],
            "parent_root": block["parent_root"], "state_root": b"\x00" * 32,
            "body_root": self.t.BeaconBlockBody.root(block["body"])}
        need(state["validators"][proposer, 88] == 0, "proposer slashed")
        return proposer

    def expected_withdrawals(self) -> list[dict]:
        state, p = self.state, self.p
        epoch, v, balances = self.current_epoch(), state["validators"], state["balances"]
        n = len(v)
        index, at = state["next_withdrawal_index"], state["next_withdrawal_validator_index"]
        out = []
        for _ in range(min(n, p["MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP"])):
            rec, balance = v[at], int(balances[at])
            has_eth1 = rec[48] == ETH1_ADDRESS_WITHDRAWAL_PREFIX
            amount = 0
            if has_eth1 and balance > 0 and int.from_bytes(
                    rec[113:121].tobytes(), "little") <= epoch:
                amount = balance  # fully withdrawable
            elif (has_eth1 and balance > MAX_EFFECTIVE_BALANCE and int.from_bytes(
                    rec[80:88].tobytes(), "little") == MAX_EFFECTIVE_BALANCE):
                amount = balance - MAX_EFFECTIVE_BALANCE  # partially
            if amount:
                out.append({"index": index, "validator_index": at,
                            "address": rec[60:80].tobytes(), "amount": amount})
                index += 1
            if len(out) == p["MAX_WITHDRAWALS_PER_PAYLOAD"]:
                break
            at = (at + 1) % n
        return out

    def process_withdrawals(self, payload: dict) -> None:
        state, p = self.state, self.p
        expected = self.expected_withdrawals()
        need(payload["withdrawals"] == expected, "payload withdrawals != expected")
        for w in expected:
            self.decrease_balance(w["validator_index"], w["amount"])
        n = len(state["validators"])
        if expected:
            state["next_withdrawal_index"] = expected[-1]["index"] + 1
        if len(expected) == p["MAX_WITHDRAWALS_PER_PAYLOAD"]:
            state["next_withdrawal_validator_index"] = (expected[-1]["validator_index"] + 1) % n
        else:
            state["next_withdrawal_validator_index"] = (
                state["next_withdrawal_validator_index"]
                + p["MAX_VALIDATORS_PER_WITHDRAWALS_SWEEP"]) % n

    def process_execution_payload(self, payload: dict) -> None:
        state, t = self.state, self.t
        head = state["latest_execution_payload_header"]
        if any(v if isinstance(v, int) else any(v) for v in head.values()):
            # is_merge_transition_complete: the header is not the default one
            need(payload["parent_hash"] == head["block_hash"], "payload parent hash")
        need(payload["prev_randao"] == self.randao_mix(self.current_epoch()),
             "payload prev_randao")
        need(payload["timestamp"]
             == state["genesis_time"] + state["slot"] * self.seconds_per_slot,
             "payload timestamp")
        new = {k: payload[k] for k in t.ExecutionPayloadHeader.fields if k in payload}
        new["transactions_root"] = t.Transactions.root(payload["transactions"])
        new["withdrawals_root"] = t.Withdrawals.root(payload["withdrawals"])
        state["latest_execution_payload_header"] = new

    def process_randao(self, body: dict) -> None:
        epoch = self.current_epoch()
        mix = bytes(a ^ b for a, b in zip(self.randao_mix(epoch), sha(body["randao_reveal"])))
        i = epoch % self.p["EPOCHS_PER_HISTORICAL_VECTOR"]
        self.state["randao_mixes"][32 * i:32 * i + 32] = mix

    def process_eth1_data(self, body: dict) -> None:
        state, p = self.state, self.p
        state["eth1_data_votes"].append(dict(body["eth1_data"]))
        votes = sum(v == body["eth1_data"] for v in state["eth1_data_votes"])
        if votes * 2 > p["EPOCHS_PER_ETH1_VOTING_PERIOD"] * p["SLOTS_PER_EPOCH"]:
            state["eth1_data"] = dict(body["eth1_data"])

    def participation_flags(self, data: dict, delay: int) -> list[int]:
        state, spe = self.state, self.p["SLOTS_PER_EPOCH"]
        justified = (state["current_justified_checkpoint"]
                     if data["target"]["epoch"] == self.current_epoch()
                     else state["previous_justified_checkpoint"])
        matching_source = data["source"] == justified
        need(matching_source, "attestation source is not the justified checkpoint")
        matching_target = data["target"]["root"] == self.block_root_at_slot(
            data["target"]["epoch"] * spe)
        matching_head = (matching_target and data["beacon_block_root"]
                         == self.block_root_at_slot(data["slot"]))
        flags = []
        if delay <= integer_squareroot(spe):
            flags.append(TIMELY_SOURCE)
        if matching_target and delay <= spe:
            flags.append(TIMELY_TARGET)
        if matching_head and delay == MIN_ATTESTATION_INCLUSION_DELAY:
            flags.append(TIMELY_HEAD)
        return flags

    def process_attestation(self, att: dict, proposer: int, per_increment: int) -> None:
        state, spe = self.state, self.p["SLOTS_PER_EPOCH"]
        data = att["data"]
        target = data["target"]["epoch"]
        need(target in (self.previous_epoch(), self.current_epoch()), "target epoch")
        need(target == self.epoch_at(data["slot"]), "target epoch is not the slot's")
        need(data["slot"] + MIN_ATTESTATION_INCLUSION_DELAY <= state["slot"]
             <= data["slot"] + spe, "attestation inclusion window")
        need(data["index"] < self.committees_per_slot(target), "committee index")
        committee = self.beacon_committee(data["slot"], data["index"])
        bits = att["aggregation_bits"]
        need(len(bits) == len(committee), "aggregation bits length")
        flags = self.participation_flags(data, state["slot"] - data["slot"])
        participation = state["current_epoch_participation" if target == self.current_epoch()
                              else "previous_epoch_participation"]
        members = committee[bits]
        need(len(members) > 0, "empty attestation")
        increments = self.effective_balances()[members] // np.uint64(EFFECTIVE_BALANCE_INCREMENT)
        numerator = 0
        for flag in flags:
            fresh = ((participation[members] >> flag) & 1) == 0
            participation[members[fresh]] |= np.uint8(1 << flag)
            numerator += (int(increments[fresh].sum(dtype=np.uint64)) * per_increment
                          * PARTICIPATION_FLAG_WEIGHTS[flag])
        denominator = (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT) * WEIGHT_DENOMINATOR // PROPOSER_WEIGHT
        self.increase_balance(proposer, numerator // denominator)

    def process_operations(self, body: dict, proposer: int) -> None:
        state = self.state
        need(len(body["deposits"]) == min(
            MAX_DEPOSITS, state["eth1_data"]["deposit_count"] - state["eth1_deposit_index"]),
            "deposit count")
        for kind in ("proposer_slashings", "attester_slashings", "deposits",
                     "voluntary_exits", "bls_to_execution_changes"):
            need(not body[kind], f"not followed here: {kind}")
        per_increment = self.base_reward_per_increment()
        for att in body["attestations"]:
            self.process_attestation(att, proposer, per_increment)

    def process_sync_aggregate(self, aggregate: dict, proposer: int) -> None:
        state, p = self.state, self.p
        increments = self.total_active_balance() // EFFECTIVE_BALANCE_INCREMENT
        total_base_rewards = self.base_reward_per_increment() * increments
        max_participant_rewards = (total_base_rewards * SYNC_REWARD_WEIGHT
                                   // WEIGHT_DENOMINATOR // p["SLOTS_PER_EPOCH"])
        participant_reward = max_participant_rewards // p["SYNC_COMMITTEE_SIZE"]
        proposer_reward = (participant_reward * PROPOSER_WEIGHT
                           // (WEIGHT_DENOMINATOR - PROPOSER_WEIGHT))
        # the spec's all_pubkeys.index(pubkey) presumes a registry without a
        # repeated key; where a key repeats (the benchmark's registry cycles a
        # few seeded keys) the LAST holder is taken, and that is stated in
        # PERF.md: with a repeated key the spec leaves the holder undefined
        def holders():
            pubkeys = np.ascontiguousarray(state["validators"][:, :48]).tobytes()
            return {pubkeys[48 * i:48 * i + 48]: i for i in range(len(state["validators"]))}

        holder = self.of_registry("holders", holders)
        for pk, bit in zip(state["current_sync_committee"]["pubkeys"],
                           aggregate["sync_committee_bits"]):
            if bit:
                self.increase_balance(holder[pk], participant_reward)
                self.increase_balance(proposer, proposer_reward)
            else:
                self.decrease_balance(holder[pk], participant_reward)

    def apply(self, signed: dict) -> bytes:
        """``state_transition`` without the signature checks; the post-state
        root, which the caller holds the block's ``state_root`` to."""
        block = signed["message"]
        body = block["body"]
        self.process_slots(block["slot"])
        proposer = self.process_block_header(block)
        self.process_withdrawals(body["execution_payload"])
        self.process_execution_payload(body["execution_payload"])
        self.process_randao(body)
        self.process_eth1_data(body)
        self.process_operations(body, proposer)
        self.process_sync_aggregate(body["sync_aggregate"], proposer)
        return self.state_root()


# ----------------------------------------------------------------- serve


def read_message(stream):
    head = stream.read(8)
    if len(head) < 8:
        return None, None
    header = json.loads(stream.read(struct.unpack("<Q", head)[0]))
    return header, stream.read(int(header.get("bytes", 0)))


def answer(ref: Reference, header: dict, payload: bytes) -> dict:
    t = ref.t
    if header["cmd"] == "state":
        ref.state = t.BeaconState.decode(payload)
        ref._registry.clear()
        return {"kind": "state", "slot": ref.state["slot"],
                "validators": len(ref.state["validators"]),
                "state_root": ref.state_root().hex()}
    if header["cmd"] == "block":
        need(ref.state is not None, "no state yet")
        signed = t.SignedBeaconBlock.decode(payload)
        block = signed["message"]
        post = ref.apply(signed)
        return {"kind": "block", "slot": block["slot"],
                "block_root": t.BeaconBlock.root(block).hex(),
                "attestations": len(block["body"]["attestations"]),
                "post_state_root": post.hex(), "claimed_state_root": block["state_root"].hex()}
    if header["cmd"] == "readback":
        need(ref.state is not None, "no state yet")
        other = t.BeaconState.decode(payload)
        differ = [k for k in ref.state if not same(ref.state[k], other[k])]
        return {"kind": "readback", "slot": other["slot"], "fields": len(ref.state),
                "fields_differ": differ}
    raise Refused(f"unknown command {header['cmd']!r}")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    preset, seconds_per_slot = argv[0], int(argv[1])
    ref = Reference(preset, seconds_per_slot)
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
