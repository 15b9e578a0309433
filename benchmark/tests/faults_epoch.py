#!/usr/bin/env python3
"""benchmark/tests/faults_epoch.py — one run of ``catchup.epoch-boundary``
with the boundary block's epoch transition broken underneath: ``correct`` has
to come out false.  ``faults.py``'s runner with this cell's table:

    python3 benchmark/tests/faults_epoch.py --fault <name> --workload catchup.epoch-boundary --seed <n> [--rehearse]

``host_fallback``            the control: ``GRAFT_RESIDENT_EPOCH=0`` in the
                             environment sends every boundary down the program's
                             host path.  Every root is still right; the run is
                             not this cell's (``epoch_not_through_resident_plane``).
``reward_off_by_one``        one validator's balance comes back from the window's
                             boundary one gwei too high.
``participation_not_rotated`` ``process_participation_flag_updates`` does nothing
                             at the window's boundary.
``plane_deltas_not_shipped`` the resident plane's third sync (the window's: the
                             first that has blocks behind it) finds nothing changed,
                             so the device columns miss the votes and rewards of
                             the blocks since the last boundary.
``randao_mix_not_carried``   ``process_randao_mixes_reset`` does nothing there.
                             Seen at the rehearsal's size; **unseen at 2^20
                             validators**, whatever the seed: the chain's proposers
                             of slots 65 and 95 (validators 1,003,377 and 241,265)
                             both hold key 49 of the 64-key cycle, their two
                             reveals of epoch 2 cancel in the XOR, and the mix
                             that is carried equals the row it overwrites (my chip
                             run, PR 32).
``hysteresis_skipped``       the resident plane's hysteresis mask reads all-false
                             there.  **This traffic cannot show it**: with two
                             blocks of votes an epoch (the configuration's
                             ``epoch_votes`` cut) no balance leaves the band round
                             its effective balance, so the run reads ``correct:
                             true`` and the runner's exit code says "unseen"; the
                             CPU's seeded states hold it
                             (``test_plainref_epoch.py``).  Kept in the table so
                             that the limit is on record.
``none``                     nothing planted: ``correct: true``.

``reward_off_by_one``, ``participation_not_rotated`` and
``plane_deltas_not_shipped`` (and ``randao_mix_not_carried`` where it shows) leave the node with a post-state whose root is
not the block's: its own check, which the plain reference's roots stand
behind, refuses the boundary block and every block after it.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import faults  # noqa: E402  (benchmark/tests/faults.py: the runner)


def at_the_windows_boundary(state, slots) -> bool:
    """``process_epoch`` runs before the slot is advanced."""
    return int(state.slot) + 1 == slots[0]


def plant_host_fallback(slots):
    steer = faults.bench_run.steer_rehearsal

    def steer_then_host():
        steer()
        os.environ["GRAFT_RESIDENT_EPOCH"] = "0"

    faults.bench_run.steer_rehearsal = steer_then_host
    os.environ["GRAFT_RESIDENT_EPOCH"] = "0"


def plant_reward_off_by_one(slots):
    from lambda_ethereum_consensus_tpu.state_transition import resident

    through_plane = resident.process_epoch_resident

    def off_by_one(state, plane, spec=None):
        done = through_plane(state, plane, spec)
        if done and at_the_windows_boundary(state, slots):
            state.balances[0] += 1
        return done

    resident.process_epoch_resident = off_by_one


def plant_participation_not_rotated(slots):
    from lambda_ethereum_consensus_tpu.state_transition import epoch

    rotate = epoch.process_participation_flag_updates

    def not_rotated(state, spec=None):
        if not at_the_windows_boundary(state, slots):
            rotate(state, spec)

    epoch.process_participation_flag_updates = not_rotated


def plant_randao_mix_not_carried(slots):
    from lambda_ethereum_consensus_tpu.state_transition import epoch

    carry = epoch.process_randao_mixes_reset

    def not_carried(state, spec=None):
        if not at_the_windows_boundary(state, slots):
            carry(state, spec)

    epoch.process_randao_mixes_reset = not_carried


def plant_plane_deltas_not_shipped(slots):
    import numpy as np

    from lambda_ethereum_consensus_tpu.state_transition import resident

    changed = resident.ResidentEpochPlane._changed_idx

    def nothing_changed(self, field, state, mirror, new):
        # sync counts itself before it looks for deltas; the window's is the third
        if self.stats["syncs"] == 3:
            return np.zeros(0, np.int64)
        return changed(self, field, state, mirror, new)

    resident.ResidentEpochPlane._changed_idx = nothing_changed


def plant_hysteresis_skipped(slots):
    from lambda_ethereum_consensus_tpu.state_transition import resident

    mask = resident.ResidentEpochPlane.hysteresis_mask
    seen = {"boundaries": 0}

    def all_false(self, *args):
        out = mask(self, *args)
        seen["boundaries"] += 1
        # the window's boundary is the last of the run (the third)
        return out & False if seen["boundaries"] == 3 else out

    resident.ResidentEpochPlane.hysteresis_mask = all_false


FAULTS = {
    "none": lambda slots: None,
    "host_fallback": plant_host_fallback,
    "reward_off_by_one": plant_reward_off_by_one,
    "participation_not_rotated": plant_participation_not_rotated,
    "plane_deltas_not_shipped": plant_plane_deltas_not_shipped,
    "randao_mix_not_carried": plant_randao_mix_not_carried,
    "hysteresis_skipped": plant_hysteresis_skipped,
}
# what this cell's traffic cannot show (see above): the runner exits 1 there
UNSEEN_BY_THIS_TRAFFIC = ("hysteresis_skipped",)

if __name__ == "__main__":
    faults.FAULTS.clear()
    faults.FAULTS.update(FAULTS)
    try:
        code = faults.main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
