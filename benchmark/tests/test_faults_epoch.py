"""The CPU rehearsal of ``catchup.epoch-boundary`` with the boundary block's
epoch transition broken underneath (``faults_epoch.py``): ``correct`` comes
out false for the control and for every fault the traffic can show, true with
nothing planted — and true, on record, for the one fault this traffic cannot
show.  Each case is a whole rehearsal run in a process of its own, minutes on
the CPU (interpret-mode kernels).  Nothing outside ``benchmark/tests``
collects them:

    python3 -m pytest benchmark/tests/test_faults_epoch.py -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import faults_epoch  # noqa: E402

CELL = "catchup.epoch-boundary"
# the number each fault is caught by (a key of the line's `compared`)
CAUGHT_BY = {
    "host_fallback": "epoch_not_through_resident_plane",
    "reward_off_by_one": "blocks_not_imported",
    "participation_not_rotated": "blocks_not_imported",
    "plane_deltas_not_shipped": "blocks_not_imported",
    "randao_mix_not_carried": "blocks_not_imported",
}


@pytest.mark.parametrize("fault", sorted(faults_epoch.FAULTS))
def test_fault_reads_not_correct(fault, tmp_path):
    # a compile cache of its own: an executable the AOT tier saved does not
    # load back whole on this CPU backend (PERF.md section 7)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "faults_epoch.py"), "--fault", fault,
         "--workload", CELL, "--seed", "2147483659", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    last = done.stdout.strip().splitlines()[-1] if done.stdout.strip() else ""
    verdict = json.loads(last)
    if fault in faults_epoch.UNSEEN_BY_THIS_TRAFFIC:
        assert done.returncode == 1 and verdict["correct"] is True, last
        return
    assert done.returncode == 0, f"{fault} went unseen: {last}\n{done.stderr[-2000:]}"
    assert verdict["seen"] and verdict["correct"] is (fault == "none")
    if fault != "none":
        value, limit = verdict["compared"][CAUGHT_BY[fault]]
        assert value > limit == 0, verdict["compared"]
        if fault == "host_fallback":  # every root still right
            for name in ("plain_block_roots_differ", "plain_state_roots_differ",
                         "blocks_not_imported", "post_state_root_differs",
                         "persisted_state_fields_differ"):
                assert verdict["compared"][name] == [0, 0], name
