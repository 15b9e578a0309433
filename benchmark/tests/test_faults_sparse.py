"""The CPU rehearsal of a sparse-aggregate cell with the timed path broken
underneath (``faults_sparse.py``): ``correct`` comes out false for the control
and for every planted fault, and true with nothing planted.  Each case is a
whole rehearsal run in a process of its own, several minutes on the CPU
(interpret-mode kernels).  Nothing outside ``benchmark/tests`` collects them:

    python3 -m pytest benchmark/tests/test_faults_sparse.py -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# what each fault has to trip among the numbers compared; a fault that turns
# every verdict wrong ends the run in its warm-up burst, before any is booked
TRIPS = {
    "none": None,
    "host_walk": "entries_through_uncached_chain",
    "wrong_side": "plain_verdicts_differ",
    "width_truncated": None,
}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def cases():
    for cell in BENCH["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            if json.load(f)["generator"] != "sparse_bursts":
                continue
        for fault in TRIPS:
            yield pytest.param(cell["name"], fault, id=f"{cell['name']}-{fault}")


@pytest.mark.parametrize("cell,fault", list(cases()))
def test_fault_reads_not_correct(cell, fault, tmp_path):
    # a compile cache of its own: on this CPU backend an executable the AOT
    # tier saved in an earlier run does not load back whole (PERF.md section 7)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "faults_sparse.py"), "--fault", fault,
         "--workload", cell, "--seed", "2147483659", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=2400)
    last = done.stdout.strip().splitlines()[-1] if done.stdout.strip() else ""
    assert done.returncode == 0, f"{fault} went unseen: {last}\n{done.stderr[-2000:]}"
    verdict = json.loads(last)
    assert verdict["seen"] and verdict["correct"] is (fault == "none")
    if TRIPS[fault]:
        value, limit = verdict["compared"][TRIPS[fault]]
        assert value > limit, verdict["compared"]
