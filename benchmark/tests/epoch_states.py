"""Seeded random states at the last slot of an epoch, for the tests of
``plainref_epoch.py`` (minimal preset): every state holds slashed validators
due at the ``withdrawable_epoch`` that ``process_slashings`` hits (and some
not due), an activation queue longer than the churn limit, fresh deposits,
ejections beyond the churn limit, balances on both sides of the hysteresis
band, and random participation flags and inactivity scores; the case sets the
epoch, finality (leak or none), the justification pattern and whether a
sync-committee period and a ``historical_summaries`` period end.

Built with the program's own containers (the input has to be SSZ both sides
decode); what is compared is what each side makes of it.
"""

from __future__ import annotations

import random

SEEDS = (3, 2147483659, 2147483777)
N_VALIDATORS = 64

# case -> (epoch, finalized epoch, justification: old bits, epoch of the old
# previous / current justified checkpoint, target participation of the
# previous / current epoch)
CASES = {
    # rewards and justification both skipped
    "genesis_epoch": dict(epoch=0, finalized=0, bits=(0, 0, 0, 0), old=(0, 0), target=(0.5, 0.5)),
    # rewards paid, justification still skipped
    "epoch_one": dict(epoch=1, finalized=0, bits=(1, 0, 0, 0), old=(0, 0), target=(0.9, 0.9)),
    "leak": dict(epoch=9, finalized=2, bits=(0, 0, 0, 0), old=(2, 2), target=(0.3, 0.3)),
    "no_leak_none_justified": dict(epoch=9, finalized=7, bits=(1, 1, 0, 0), old=(7, 8),
                                   target=(0.5, 0.4)),
    # the four finalization rules of weigh_justification_and_finalization
    "finalize_234_on_previous": dict(epoch=6, finalized=2, bits=(0, 1, 1, 0), old=(3, 4),
                                     target=(0.95, 0.3)),
    "finalize_23_on_previous": dict(epoch=6, finalized=3, bits=(0, 1, 0, 0), old=(4, 4),
                                    target=(0.95, 0.3)),
    "finalize_123_on_current": dict(epoch=6, finalized=3, bits=(0, 1, 0, 0), old=(3, 4),
                                    target=(0.95, 0.95)),
    "finalize_12_on_current": dict(epoch=6, finalized=4, bits=(1, 0, 0, 0), old=(4, 5),
                                   target=(0.3, 0.95)),
    # epoch 7 -> 8: a sync-committee period, a historical_summaries period and
    # an eth1 voting period end (minimal: 8, 64 / 8 and 4 epochs)
    "periods_end": dict(epoch=7, finalized=5, bits=(1, 1, 1, 0), old=(5, 6), target=(0.8, 0.8)),
}


def genesis(spec):
    from lambda_ethereum_consensus_tpu.crypto import bls
    from lambda_ethereum_consensus_tpu.state_transition.genesis import build_genesis_state

    sks = [(i + 1).to_bytes(32, "big") for i in range(N_VALIDATORS)]
    return build_genesis_state([bls.sk_to_pk(sk) for sk in sks], spec=spec)


def staged_state(base, case: str, seed: int, spec):
    """``base`` (a genesis state) moved to the last slot of the case's epoch
    and filled from ``seed``."""
    from lambda_ethereum_consensus_tpu.config import constants
    from lambda_ethereum_consensus_tpu.ssz.bitfields import Bitvector
    from lambda_ethereum_consensus_tpu.state_transition.mutable import BeaconStateMut
    from lambda_ethereum_consensus_tpu.types.beacon import Checkpoint, Eth1Data

    c = CASES[case]
    rng = random.Random(f"{seed}:{case}")
    spe, epoch = int(spec.SLOTS_PER_EPOCH), c["epoch"]
    far, inc = constants.FAR_FUTURE_EPOCH, int(spec.EFFECTIVE_BALANCE_INCREMENT)
    max_eb = int(spec.MAX_EFFECTIVE_BALANCE)
    ws = BeaconStateMut(base)
    ws._root_engine = None
    ws._resident_plane = None
    n = len(ws.validators)
    ws.slot = epoch * spe + spe - 1
    for i in range(int(spec.SLOTS_PER_HISTORICAL_ROOT)):
        ws.block_roots[i] = rng.randbytes(32)
        ws.state_roots[i] = rng.randbytes(32)
    for i in range(int(spec.EPOCHS_PER_HISTORICAL_VECTOR)):
        ws.randao_mixes[i] = rng.randbytes(32)
    for i in range(int(spec.EPOCHS_PER_SLASHINGS_VECTOR)):
        ws.slashings[i] = rng.randrange(0, 40) * inc if rng.random() < 0.2 else 0
    ws.eth1_data_votes = [
        Eth1Data(deposit_root=rng.randbytes(32), deposit_count=7, block_hash=rng.randbytes(32))
        for _ in range(3)]

    # balances round 32 ETH, a few well below and above; effective balances in
    # step with them except where the hysteresis band is tested
    for i in range(n):
        balance = max_eb + rng.randrange(-2 * inc, 2 * inc)
        if rng.random() < 0.15:
            balance = rng.randrange(10 * inc, 45 * inc)
        ws.balances[i] = balance
        effective = min(balance - balance % inc, max_eb)
        if rng.random() < 0.3:  # one step off: inside or outside the band
            effective = min(max(effective + rng.choice((-inc, inc)), inc), max_eb)
        ws.update_validator(i, effective_balance=effective)
        ws.inactivity_scores[i] = rng.randrange(0, 120) if rng.random() < 0.7 else 0
    for which, share in zip(("previous", "current"), c["target"]):
        flags = getattr(ws, f"{which}_epoch_participation")
        for i in range(n):
            value = rng.randrange(8) & ~2
            flags[i] = value | (2 if rng.random() < share else 0)

    order = list(range(n))
    rng.shuffle(order)
    take = iter(order)
    vector = int(spec.EPOCHS_PER_SLASHINGS_VECTOR)
    # slashed: two due at this boundary, one due later, one withdrawable already
    for due in (epoch + vector // 2, epoch + vector // 2, epoch + vector // 2 + 3,
                max(epoch - 1, 0)):
        ws.update_validator(next(take), slashed=True, exit_epoch=max(epoch - 1, 0),
                            withdrawable_epoch=due)
    # fresh deposits: eligibility not yet set; one of them under the maximum
    for k in range(3):
        ws.update_validator(next(take), activation_eligibility_epoch=far, activation_epoch=far,
                            effective_balance=max_eb if k else max_eb - inc)
    # the activation queue: six waiting (the churn limit is 4), some of one
    # eligibility epoch, and one not yet finalized
    for eligibility in (c["finalized"], 0, 0, max(c["finalized"] - 1, 0), 0, c["finalized"],
                        c["finalized"] + 1):
        ws.update_validator(next(take), activation_eligibility_epoch=eligibility,
                            activation_epoch=far)
    # ejections: three at or under the ejection balance, and two exits queued
    # at the head of the exit queue already (the third ejection finds it full)
    for _ in range(3):
        ws.update_validator(next(take), effective_balance=int(spec.EJECTION_BALANCE)
                            - rng.choice((0, inc)))
    queue_head = epoch + 1 + int(spec.MAX_SEED_LOOKAHEAD)
    for _ in range(2):
        ws.update_validator(next(take), exit_epoch=queue_head,
                            withdrawable_epoch=queue_head + 256)

    ws.finalized_checkpoint = Checkpoint(epoch=c["finalized"], root=rng.randbytes(32))
    ws.previous_justified_checkpoint = Checkpoint(epoch=c["old"][0], root=rng.randbytes(32))
    ws.current_justified_checkpoint = Checkpoint(epoch=c["old"][1], root=rng.randbytes(32))
    ws.justification_bits = Bitvector.from_bools([bool(b) for b in c["bits"]])
    return ws.freeze()


def program_epoch(staged, spec, resident: bool):
    """The staged state through one of the program's two epoch paths; the
    post-state and, on the resident path, the plane's statistics."""
    import os

    from lambda_ethereum_consensus_tpu.state_transition import epoch as E
    from lambda_ethereum_consensus_tpu.state_transition.mutable import BeaconStateMut
    from lambda_ethereum_consensus_tpu.state_transition.resident import ensure_plane

    ws = BeaconStateMut(staged)
    ws._root_engine = None
    ws._resident_plane = None
    if not resident:
        E._process_epoch_host(ws, spec)
        return ws.freeze(), None
    old = os.environ.get("GRAFT_RESIDENT_EPOCH")
    os.environ["GRAFT_RESIDENT_EPOCH"] = "1"
    try:
        plane = ensure_plane(ws, spec)
        E.process_epoch(ws, spec)
    finally:
        if old is None:
            del os.environ["GRAFT_RESIDENT_EPOCH"]
        else:
            os.environ["GRAFT_RESIDENT_EPOCH"] = old
    return ws.freeze(), dict(plane.stats)
