"""The CPU rehearsal of a cell with the timed path broken underneath
(``faults.py``): ``correct`` comes out false for every fault the cell can
have, and true with nothing planted (``none``).  Each case is a whole
rehearsal run in a process of its own, about a minute on the CPU
(interpret-mode kernels; 62 s for ``none``, PR 27).  Nothing outside
``benchmark/tests`` collects them:

    python3 -m pytest benchmark/tests/test_faults.py -q
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

# which faults each kind of generator can have
FAULTS_OF = {"range_blocks": ("none", "host_rlc", "state_unchanged", "half_left_out",
                              "answer_altered", "persisted_altered")}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def cases():
    for cell in BENCH["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            generator = json.load(f)["generator"]
        for fault in FAULTS_OF.get(generator, ()):
            yield pytest.param(cell["name"], fault, id=f"{cell['name']}-{fault}")


@pytest.mark.parametrize("cell,fault", list(cases()))
def test_fault_reads_not_correct(cell, fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "faults.py"), "--fault", fault,
         "--workload", cell, "--seed", "2147483659", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    last = done.stdout.strip().splitlines()[-1] if done.stdout.strip() else ""
    assert done.returncode == 0, f"{fault} went unseen: {last}\n{done.stderr[-2000:]}"
    verdict = json.loads(last)
    assert verdict["seen"] and verdict["correct"] is (fault == "none")
