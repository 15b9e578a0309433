#!/usr/bin/env python3
"""benchmark/plainref_epoch.py — the plain reference of block import with
the epoch boundary followed, in a process of its own.

``plainref.py`` beside it refuses a block that crosses an epoch boundary
("not followed here: process_epoch").  This file follows it: capella
``process_epoch`` written from ethereum/consensus-specs
(``specs/altair/beacon-chain.md`` "Epoch processing" with bellatrix's
quotients and ``specs/capella/beacon-chain.md``
``process_historical_summaries_update``) on ``numpy`` and ``hashlib``, over
the state ``plainref.py`` decodes.  It imports ``plainref`` and **nothing of
the program under test**; no incremental root, no device, no table shared
with the program (the constants below are the spec's, copied by hand).

Same messages as ``plainref.py`` (``state``, ``block``, ``readback``).

Where this file departs from the spec's text, and why:

- the per-validator loops are array expressions over the whole registry
  (the spec's Python lists would take minutes at 2^20 validators); each
  keeps the spec's order of increase and saturating decrease per delta
  pair, and holds its products inside 64 bits by a check, not by belief
  (``need(... < 2**64)``: the spec's ``uint64`` would raise there too);
- ``process_registry_updates`` runs its two per-validator steps as two
  passes (eligibility, then ejections in index order): they write
  different fields, and ``initiate_validator_exit`` reads only exit epochs;
- ``eth_aggregate_pubkeys`` adds the points without ``KeyValidate`` (the
  registry's keys were validated when they were deposited), on a G1
  arithmetic of a few lines over Python integers;
- BLS signatures are not verified and no operation but attestations is
  followed, as in ``plainref.py``.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

import plainref
from plainref import (
    EFFECTIVE_BALANCE_INCREMENT, MAX_EFFECTIVE_BALANCE, MIN_SEED_LOOKAHEAD,
    PARTICIPATION_FLAG_WEIGHTS, TIMELY_HEAD, TIMELY_TARGET, WEIGHT_DENOMINATOR,
    Refused, column, need, sha,
)

# consensus-specs presets/{mainnet,minimal}/{phase0,altair,bellatrix}.yaml and
# configs/{mainnet,minimal}.yaml as of capella (v1.3.0): what process_epoch reads
# beyond plainref.PRESETS.  (minimal's MIN_PER_EPOCH_CHURN_LIMIT became 2 with
# deneb's EIP-7514; this is a capella reference.)
EPOCH_PRESETS = {
    "mainnet": dict(EPOCHS_PER_SYNC_COMMITTEE_PERIOD=256, MIN_PER_EPOCH_CHURN_LIMIT=4,
                    CHURN_LIMIT_QUOTIENT=65536),
    "minimal": dict(EPOCHS_PER_SYNC_COMMITTEE_PERIOD=8, MIN_PER_EPOCH_CHURN_LIMIT=4,
                    CHURN_LIMIT_QUOTIENT=32),
}
# the same in both
GENESIS_EPOCH, FAR_FUTURE_EPOCH = 0, 2 ** 64 - 1
MAX_SEED_LOOKAHEAD, MIN_VALIDATOR_WITHDRAWABILITY_DELAY = 4, 256
MIN_EPOCHS_TO_INACTIVITY_PENALTY = 4
EJECTION_BALANCE = 16 * 10 ** 9
INACTIVITY_SCORE_BIAS, INACTIVITY_SCORE_RECOVERY_RATE = 4, 16
INACTIVITY_PENALTY_QUOTIENT_BELLATRIX = 2 ** 24
PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX = 3
HYSTERESIS_QUOTIENT, HYSTERESIS_DOWNWARD_MULTIPLIER, HYSTERESIS_UPWARD_MULTIPLIER = 4, 1, 5
DOMAIN_SYNC_COMMITTEE = b"\x07\x00\x00\x00"

# the validator record's fields, as byte ranges of plainref.ValidatorList's rows
EFFECTIVE, SLASHED = (80, 88), 88
ELIGIBILITY, ACTIVATION, EXIT, WITHDRAWABLE = (89, 97), (97, 105), (105, 113), (113, 121)

U64 = np.uint64


# -------------------------------------------------- G1, for one aggregate key

P = int("1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241"
        "eabfffeb153ffffb9feffffffffaaab", 16)


def g1_decompress(data: bytes):
    """A compressed G1 point (ZCash format) as ``(x, y)``, ``None`` at infinity."""
    need(len(data) == 48 and data[0] & 0x80, "pubkey is not a compressed G1 point")
    if data[0] & 0x40:
        return None
    x = int.from_bytes(data, "big") & ((1 << 381) - 1)
    rhs = (x * x * x + 4) % P
    y = pow(rhs, (P + 1) // 4, P)
    need(y * y % P == rhs, "pubkey is not on the curve")
    if (y > (P - 1) // 2) != bool(data[0] & 0x20):
        y = P - y
    return x, y


def g1_add(a, b):
    if a is None or b is None:
        return b if a is None else a
    (x1, y1), (x2, y2) = a, b
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        slope = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        slope = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (slope * slope - x1 - x2) % P
    return x3, (slope * (x1 - x3) - y1) % P


def g1_compress(point) -> bytes:
    if point is None:
        return bytes([0xC0]) + bytes(47)
    x, y = point
    out = bytearray(x.to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if y > (P - 1) // 2 else 0)
    return bytes(out)


# ------------------------------------------------------------- the reference


class Reference(plainref.Reference):
    """``plainref.Reference`` with ``process_slots`` across epoch boundaries."""

    def __init__(self, preset: str, seconds_per_slot: int):
        super().__init__(preset, seconds_per_slot)
        self.p = {**self.p, **EPOCH_PRESETS[preset]}
        self.epochs_processed = 0

    # -- the registry's columns

    def col(self, field) -> np.ndarray:
        return column(self.state["validators"], *field)

    def set_col(self, field, index, values) -> None:
        """Write ``values`` into one ``uint64`` field of the records ``index``."""
        rows = np.asarray(values, "<u8").reshape(-1, 1).view(np.uint8)
        self.state["validators"][index, field[0]:field[1]] = rows
        self.registry_changed()

    def slashed(self) -> np.ndarray:
        return self.state["validators"][:, SLASHED] != 0

    def registry_changed(self) -> None:
        """A validator record changed: what ``plainref`` worked out from the
        registry is dropped (a key's holders stay: no pubkey changes)."""
        holders = self._registry.get("holders")
        self._registry.clear()
        if holders is not None:
            self._registry["holders"] = holders

    def active_mask(self, epoch: int) -> np.ndarray:
        return (self.col(ACTIVATION) <= U64(epoch)) & (U64(epoch) < self.col(EXIT))

    def total_balance(self, mask) -> int:
        return max(EFFECTIVE_BALANCE_INCREMENT,
                   int(self.effective_balances()[mask].sum(dtype=U64)))

    def unslashed_participating(self, flag: int, epoch: int) -> np.ndarray:
        need(epoch in (self.previous_epoch(), self.current_epoch()), "participation epoch")
        which = ("current_epoch_participation" if epoch == self.current_epoch()
                 else "previous_epoch_participation")
        has_flag = (self.state[which] >> np.uint8(flag)) & np.uint8(1) != 0
        return self.active_mask(epoch) & has_flag & ~self.slashed()

    def eligible(self) -> np.ndarray:
        previous = self.previous_epoch()
        return self.active_mask(previous) | (
            self.slashed() & (U64(previous + 1) < self.col(WITHDRAWABLE)))

    def block_root(self, epoch: int) -> bytes:
        return self.block_root_at_slot(epoch * self.p["SLOTS_PER_EPOCH"])

    def is_in_inactivity_leak(self) -> bool:
        delay = self.previous_epoch() - self.state["finalized_checkpoint"]["epoch"]
        return delay > MIN_EPOCHS_TO_INACTIVITY_PENALTY

    def churn_limit(self) -> int:
        return max(self.p["MIN_PER_EPOCH_CHURN_LIMIT"],
                   len(self.active_indices(self.current_epoch()))
                   // self.p["CHURN_LIMIT_QUOTIENT"])

    def activation_exit_epoch(self, epoch: int) -> int:
        return epoch + 1 + MAX_SEED_LOOKAHEAD

    def apply_deltas(self, rewards: np.ndarray, penalties: np.ndarray) -> None:
        """``increase_balance`` then the saturating ``decrease_balance``, for
        every validator."""
        balances = self.state["balances"]
        need(int(balances.max(initial=0)) + int(rewards.max(initial=0)) < 2 ** 64,
             "a balance over uint64")
        balances += rewards
        np.subtract(balances, np.minimum(balances, penalties), out=balances)

    # -- slots

    def process_slots(self, slot: int) -> None:
        state, p = self.state, self.p
        need(state["slot"] < slot, "block not after the state")
        span = p["SLOTS_PER_HISTORICAL_ROOT"]
        while state["slot"] < slot:
            i = state["slot"] % span
            previous_state_root = self.state_root()
            state["state_roots"][32 * i:32 * i + 32] = previous_state_root
            header = state["latest_block_header"]
            if header["state_root"] == b"\x00" * 32:
                header["state_root"] = previous_state_root
            state["block_roots"][32 * i:32 * i + 32] = self.t.BeaconBlockHeader.root(header)
            if (state["slot"] + 1) % p["SLOTS_PER_EPOCH"] == 0:
                self.process_epoch()
            state["slot"] += 1

    # -- epoch

    def process_epoch(self) -> None:
        self.process_justification_and_finalization()
        self.process_inactivity_updates()
        self.process_rewards_and_penalties()
        self.process_registry_updates()
        self.process_slashings()
        self.process_eth1_data_reset()
        self.process_effective_balance_updates()
        self.process_slashings_reset()
        self.process_randao_mixes_reset()
        self.process_historical_summaries_update()
        self.process_participation_flag_updates()
        self.process_sync_committee_updates()
        self.epochs_processed += 1

    def process_justification_and_finalization(self) -> None:
        # the first two epochs are skipped: their votes cannot be weighed yet
        if self.current_epoch() <= GENESIS_EPOCH + 1:
            return
        previous = self.unslashed_participating(TIMELY_TARGET, self.previous_epoch())
        current = self.unslashed_participating(TIMELY_TARGET, self.current_epoch())
        self.weigh_justification_and_finalization(
            self.total_active_balance(), self.total_balance(previous),
            self.total_balance(current))

    def weigh_justification_and_finalization(self, total_active: int, previous_target: int,
                                             current_target: int) -> None:
        state = self.state
        previous_epoch, current_epoch = self.previous_epoch(), self.current_epoch()
        old_previous = dict(state["previous_justified_checkpoint"])
        old_current = dict(state["current_justified_checkpoint"])
        state["previous_justified_checkpoint"] = dict(old_current)
        bits = state["justification_bits"]
        bits[1:] = bits[:-1].copy()
        bits[0] = False
        if previous_target * 3 >= total_active * 2:
            state["current_justified_checkpoint"] = {
                "epoch": previous_epoch, "root": self.block_root(previous_epoch)}
            bits[1] = True
        if current_target * 3 >= total_active * 2:
            state["current_justified_checkpoint"] = {
                "epoch": current_epoch, "root": self.block_root(current_epoch)}
            bits[0] = True
        # the 2nd/3rd/4th, 2nd/3rd, 1st/2nd/3rd and 1st/2nd most recent epochs
        # justified, with the oldest of them as the source
        if bits[1:4].all() and old_previous["epoch"] + 3 == current_epoch:
            state["finalized_checkpoint"] = old_previous
        if bits[1:3].all() and old_previous["epoch"] + 2 == current_epoch:
            state["finalized_checkpoint"] = old_previous
        if bits[0:3].all() and old_current["epoch"] + 2 == current_epoch:
            state["finalized_checkpoint"] = old_current
        if bits[0:2].all() and old_current["epoch"] + 1 == current_epoch:
            state["finalized_checkpoint"] = old_current

    def process_inactivity_updates(self) -> None:
        if self.current_epoch() == GENESIS_EPOCH:
            return
        scores = self.state["inactivity_scores"]
        eligible = self.eligible()
        participating = self.unslashed_participating(TIMELY_TARGET, self.previous_epoch())
        down = eligible & participating
        scores[down] -= np.minimum(U64(1), scores[down])
        scores[eligible & ~participating] += U64(INACTIVITY_SCORE_BIAS)
        if not self.is_in_inactivity_leak():
            scores[eligible] -= np.minimum(U64(INACTIVITY_SCORE_RECOVERY_RATE), scores[eligible])

    def base_rewards(self) -> np.ndarray:
        return (self.effective_balances() // U64(EFFECTIVE_BALANCE_INCREMENT)
                * U64(self.base_reward_per_increment()))

    def flag_index_deltas(self, flag: int):
        n = len(self.state["validators"])
        rewards, penalties = np.zeros(n, U64), np.zeros(n, U64)
        unslashed = self.unslashed_participating(flag, self.previous_epoch())
        weight = PARTICIPATION_FLAG_WEIGHTS[flag]
        participating_increments = self.total_balance(unslashed) // EFFECTIVE_BALANCE_INCREMENT
        active_increments = self.total_active_balance() // EFFECTIVE_BALANCE_INCREMENT
        eligible, base = self.eligible(), self.base_rewards()
        need(int(base.max(initial=0)) * weight * participating_increments < 2 ** 64,
             "a reward numerator over uint64")
        if not self.is_in_inactivity_leak():
            gets = eligible & unslashed
            rewards[gets] = (base[gets] * U64(weight * participating_increments)
                             // U64(active_increments * WEIGHT_DENOMINATOR))
        if flag != TIMELY_HEAD:
            pays = eligible & ~unslashed
            penalties[pays] = base[pays] * U64(weight) // U64(WEIGHT_DENOMINATOR)
        return rewards, penalties

    def inactivity_penalty_deltas(self):
        n = len(self.state["validators"])
        rewards, penalties = np.zeros(n, U64), np.zeros(n, U64)
        matching = self.unslashed_participating(TIMELY_TARGET, self.previous_epoch())
        pays = self.eligible() & ~matching
        effective, scores = self.effective_balances(), self.state["inactivity_scores"]
        need(int(effective.max(initial=0)) * int(scores.max(initial=0)) < 2 ** 64,
             "an inactivity penalty numerator over uint64")
        penalties[pays] = (effective[pays] * scores[pays] // U64(
            INACTIVITY_SCORE_BIAS * INACTIVITY_PENALTY_QUOTIENT_BELLATRIX))
        return rewards, penalties

    def process_rewards_and_penalties(self) -> None:
        # no rewards are paid at the end of GENESIS_EPOCH: they are for work
        # done in the epoch before
        if self.current_epoch() == GENESIS_EPOCH:
            return
        deltas = [self.flag_index_deltas(f) for f in range(len(PARTICIPATION_FLAG_WEIGHTS))]
        deltas.append(self.inactivity_penalty_deltas())
        for rewards, penalties in deltas:
            self.apply_deltas(rewards, penalties)

    def initiate_validator_exit(self, index: int) -> None:
        exits = self.col(EXIT)
        if int(exits[index]) != FAR_FUTURE_EPOCH:
            return
        known = exits[exits != U64(FAR_FUTURE_EPOCH)]
        queue_epoch = max([int(known.max(initial=0)),
                           self.activation_exit_epoch(self.current_epoch())])
        if int((exits == U64(queue_epoch)).sum()) >= self.churn_limit():
            queue_epoch += 1
        self.set_col(EXIT, [index], [queue_epoch])
        self.set_col(WITHDRAWABLE, [index], [queue_epoch + MIN_VALIDATOR_WITHDRAWABILITY_DELAY])

    def process_registry_updates(self) -> None:
        current = self.current_epoch()
        effective = self.effective_balances()
        # is_eligible_for_activation_queue
        queued = np.flatnonzero((self.col(ELIGIBILITY) == U64(FAR_FUTURE_EPOCH))
                                & (effective == U64(MAX_EFFECTIVE_BALANCE)))
        if len(queued):
            self.set_col(ELIGIBILITY, queued, np.full(len(queued), current + 1, U64))
        for index in np.flatnonzero(self.active_mask(current)
                                    & (effective <= U64(EJECTION_BALANCE))):
            self.initiate_validator_exit(int(index))
        # is_eligible_for_activation, ordered by eligibility epoch, then index
        eligibility = self.col(ELIGIBILITY)
        finalized = self.state["finalized_checkpoint"]["epoch"]
        waiting = np.flatnonzero((eligibility <= U64(finalized))
                                 & (self.col(ACTIVATION) == U64(FAR_FUTURE_EPOCH)))
        waiting = waiting[np.argsort(eligibility[waiting], kind="stable")]
        dequeued = waiting[: self.churn_limit()]
        if len(dequeued):
            self.set_col(ACTIVATION, dequeued,
                         np.full(len(dequeued), self.activation_exit_epoch(current), U64))

    def process_slashings(self) -> None:
        epoch, total = self.current_epoch(), self.total_active_balance()
        adjusted = min(int(self.state["slashings"].sum(dtype=U64))
                       * PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX, total)
        due = self.slashed() & (self.col(WITHDRAWABLE) == U64(
            epoch + self.p["EPOCHS_PER_SLASHINGS_VECTOR"] // 2))
        effective = self.effective_balances()
        for index in np.flatnonzero(due):
            numerator = int(effective[index]) // EFFECTIVE_BALANCE_INCREMENT * adjusted
            self.decrease_balance(int(index),
                                  numerator // total * EFFECTIVE_BALANCE_INCREMENT)

    def process_eth1_data_reset(self) -> None:
        if (self.current_epoch() + 1) % self.p["EPOCHS_PER_ETH1_VOTING_PERIOD"] == 0:
            self.state["eth1_data_votes"] = []

    def process_effective_balance_updates(self) -> None:
        step = EFFECTIVE_BALANCE_INCREMENT // HYSTERESIS_QUOTIENT
        downward = U64(step * HYSTERESIS_DOWNWARD_MULTIPLIER)
        upward = U64(step * HYSTERESIS_UPWARD_MULTIPLIER)
        balances, effective = self.state["balances"], self.effective_balances()
        moved = np.flatnonzero((balances + downward < effective)
                               | (effective + upward < balances))
        if len(moved):
            b = balances[moved]
            self.set_col(EFFECTIVE, moved, np.minimum(
                b - b % U64(EFFECTIVE_BALANCE_INCREMENT), U64(MAX_EFFECTIVE_BALANCE)))

    def process_slashings_reset(self) -> None:
        next_epoch = self.current_epoch() + 1
        self.state["slashings"][next_epoch % self.p["EPOCHS_PER_SLASHINGS_VECTOR"]] = 0

    def process_randao_mixes_reset(self) -> None:
        current = self.current_epoch()
        i = (current + 1) % self.p["EPOCHS_PER_HISTORICAL_VECTOR"]
        self.state["randao_mixes"][32 * i:32 * i + 32] = self.randao_mix(current)

    def process_historical_summaries_update(self) -> None:
        p = self.p
        if (self.current_epoch() + 1) % (p["SLOTS_PER_HISTORICAL_ROOT"]
                                         // p["SLOTS_PER_EPOCH"]) == 0:
            roots = self.t.BeaconState.fields["block_roots"]
            self.state["historical_summaries"].append({
                "block_summary_root": roots.root(self.state["block_roots"]),
                "state_summary_root": roots.root(self.state["state_roots"])})

    def process_participation_flag_updates(self) -> None:
        state = self.state
        state["previous_epoch_participation"] = state["current_epoch_participation"]
        state["current_epoch_participation"] = np.zeros(len(state["validators"]), np.uint8)

    def next_sync_committee_indices(self) -> list[int]:
        epoch = self.current_epoch() + 1
        active, effective = self.active_indices(epoch), self.effective_balances()
        mix = self.randao_mix(epoch + self.p["EPOCHS_PER_HISTORICAL_VECTOR"]
                              - MIN_SEED_LOOKAHEAD - 1)
        seed = sha(DOMAIN_SYNC_COMMITTEE + epoch.to_bytes(8, "little") + mix)
        need(len(active) > 0, "no active validator")
        out, i = [], 0
        while len(out) < self.p["SYNC_COMMITTEE_SIZE"]:
            candidate = int(active[self.shuffled_index(i % len(active), len(active), seed)])
            random_byte = sha(seed + (i // 32).to_bytes(8, "little"))[i % 32]
            if int(effective[candidate]) * 255 >= MAX_EFFECTIVE_BALANCE * random_byte:
                out.append(candidate)
            i += 1
        return out

    def next_sync_committee(self) -> dict:
        records = self.state["validators"]
        pubkeys = [records[i, :48].tobytes() for i in self.next_sync_committee_indices()]
        points: dict[bytes, tuple] = {}
        total = None
        for pk in pubkeys:
            if pk not in points:
                points[pk] = g1_decompress(pk)
            total = g1_add(total, points[pk])
        return {"pubkeys": pubkeys, "aggregate_pubkey": g1_compress(total)}

    def process_sync_committee_updates(self) -> None:
        state = self.state
        if (self.current_epoch() + 1) % self.p["EPOCHS_PER_SYNC_COMMITTEE_PERIOD"] == 0:
            state["current_sync_committee"] = state["next_sync_committee"]
            state["next_sync_committee"] = self.next_sync_committee()


# ----------------------------------------------------------------- serve


def answer(ref: Reference, header: dict, payload: bytes) -> dict:
    before = ref.epochs_processed
    out = plainref.answer(ref, header, payload)
    if header["cmd"] == "block":
        out["epochs_processed"] = ref.epochs_processed - before
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ref = Reference(argv[0], int(argv[1]))
    stream = sys.stdin.buffer
    while True:
        header, payload = plainref.read_message(stream)
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
