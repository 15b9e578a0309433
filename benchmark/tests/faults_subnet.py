#!/usr/bin/env python3
"""benchmark/tests/faults_subnet.py — one run of a subnet-vote cell with the
timed path broken underneath: ``correct`` has to come out false.

    python3 benchmark/tests/faults_subnet.py --fault <name> --workload <cell> --seed <n> [--rehearse]

As ``faults.py`` (whose ``Tee`` this uses): everything but the planted fault
is ``run.py``'s own run, on the chip at the cell's own size or, with
``--rehearse``, on the CPU at the tiny size (``test_faults_subnet.py``).  Exit
code 0 where the run read ``correct: false`` (``true`` for ``none``), 1 where
the fault went unseen.  Faults of a subnet-vote cell (``subnet_votes``):

``sparse_branch``    the control: the single-signer routing taken out of
                     ``fork_choice/handlers.py`` (one line of its source turned
                     off before the run), which leaves the parent commit's path —
                     a one-bit vote has more missing members than the committee
                     cache corrects, so it takes the sparse branch: a host pubkey
                     point per attester and the uncached chain.  Every verdict is
                     still right; the configuration's guarantee that the votes
                     are verified in the single-signer shape is not.
``verdict_altered``  an answer altered where it is produced: one ACCEPT of every
                     window burst leaves ``_subnet_attestation_drain`` as IGNORE.
``half_not_applied`` half of the batch left out: every second accepted vote
                     never reaches ``update_latest_messages_batch``.
``none``             nothing planted: the same run has to read ``correct: true``.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(HERE, "tests"))
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import run as bench_run  # noqa: E402  (benchmark/run.py; touches neither JAX nor the package)
from faults import Tee  # noqa: E402

ROUTING = "            if len(attesting) == 1:\n"


def plant_sparse_branch():
    from lambda_ethereum_consensus_tpu.fork_choice import handlers

    source = inspect.getsource(handlers)
    assert source.count(ROUTING) == 1, "the single-signer routing line has moved"
    # the module's functions are made anew in its own namespace, so callers
    # that hold on_attestation_batch reach the new drain through its globals
    exec(compile(source.replace(ROUTING, "            if False:  # routing taken out\n"),
                 handlers.__file__, "exec"), handlers.__dict__)


def plant_verdict_altered():
    from lambda_ethereum_consensus_tpu.network.port import VERDICT_ACCEPT, VERDICT_IGNORE
    from lambda_ethereum_consensus_tpu.node.node import BeaconNode

    drain = BeaconNode._subnet_attestation_drain

    def altered(self, tagged):
        verdicts = drain(self, tagged)
        for at, (_subnet, msg) in enumerate(tagged):
            # message ids are sub:<burst>:<n>; bursts 0 and 1 are warm-up and guard
            _, burst, n = msg.msg_id.split(b":")
            if int(burst) >= 2 and int(n) == 5 and verdicts[at] == VERDICT_ACCEPT:
                verdicts[at] = VERDICT_IGNORE
        return verdicts

    BeaconNode._subnet_attestation_drain = altered


def plant_half_not_applied():
    from lambda_ethereum_consensus_tpu.fork_choice import handlers

    apply_votes = handlers.update_latest_messages_batch

    def half(store, accepted):
        apply_votes(store, accepted[::2])

    handlers.update_latest_messages_batch = half


FAULTS = {
    "none": lambda: None,
    "sparse_branch": plant_sparse_branch,
    "verdict_altered": plant_verdict_altered,
    "half_not_applied": plant_half_not_applied,
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--fault", choices=sorted(FAULTS), required=True)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    if args.rehearse:
        bench_run.steer_rehearsal()  # before the package is imported below
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    FAULTS[args.fault]()
    run_argv = ["--workload", args.workload, "--seed", str(args.seed), "--trace", "0"]
    if args.seconds is not None:
        run_argv += ["--seconds", str(args.seconds)]
    if args.rehearse:
        run_argv.append("--rehearse")
    tee = sys.stdout = Tee(sys.stdout)
    try:
        code = bench_run.main(run_argv)
    finally:
        sys.stdout = tee.out
    if code != 0 or not tee.lines:
        print(f"faults: the run ended with code {code} and no result", file=sys.stderr)
        return 2
    result = json.loads(tee.lines[-1])
    seen = result["correct"] is (args.fault == "none")  # sound run: true; a fault: false
    print(json.dumps({"fault": args.fault, "seen": seen, "correct": result["correct"],
                      "failed": result["failed"], "why": result.get("why"),
                      "compared": result.get("compared")}), flush=True)
    return 0 if seen else 1


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # as run.py: a failed run may leave the node's threads behind
