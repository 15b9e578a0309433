#!/usr/bin/env python3
"""benchmark/tests/faults_sparse.py — one run of a sparse-aggregate cell with
the timed path broken underneath: ``correct`` has to come out false.

    python3 benchmark/tests/faults_sparse.py --fault <name> --workload <cell> --seed <n> [--rehearse]

As ``faults.py`` (whose ``Tee`` this uses): everything but the planted fault
is ``run.py``'s own run, on the chip at the cell's own size or, with
``--rehearse``, on the CPU at the tiny size (``test_faults_sparse.py``).  Exit
code 0 where the run read ``correct: false`` (``true`` for ``none``), 1 where
the fault went unseen.  Faults of a sparse-aggregate cell (``sparse_bursts``):

``host_walk``        the control: the parent commit's branch put back — an
                     aggregate that misses more members than the committee
                     cache's narrowest correction holds is summed on the host,
                     a ``_pubkey_point`` and a ``g1.affine_add`` per attester,
                     and verified through the uncached point chain.  Every
                     verdict is still right; the configuration's guarantee that
                     no aggregate of the window is summed on the host is not.
``wrong_side``       the listed side added to the wrong base: from the
                     window's opening on, one aggregate of every flush has
                     its side flag turned after the index planes are packed
                     (the capacity guard has passed), so its missing members
                     are summed from the identity where they are due off the
                     committee's sum.  Set-up verifies soundly; in the window
                     that aggregate is REJECTed where it was minted good
                     (``plain_verdicts_differ``).
``width_truncated``  members beyond the narrowest width dropped: the index
                     planes are cut to ``cache.mmax`` columns, so a sparse
                     aggregate's sum lacks the rest.
``none``             nothing planted: the same run has to read ``correct: true``.
"""

from __future__ import annotations

import argparse
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


def plant_host_walk():
    import numpy as np

    from lambda_ethereum_consensus_tpu.crypto.bls import batch
    from lambda_ethereum_consensus_tpu.crypto.bls.api import _pubkey_point
    from lambda_ethereum_consensus_tpu.crypto.bls.curve import g1
    from lambda_ethereum_consensus_tpu.fork_choice.attestation import EpochAttestationContext

    owners: dict[int, object] = {}  # id(device cache) -> its epoch context
    device_cache = EpochAttestationContext.device_cache

    def recording(self):
        cache = device_cache(self)
        owners[id(cache)] = self
        return cache

    EpochAttestationContext.device_cache = recording
    cached = batch.batch_verify_each_cached

    def walk(cache, entries, dst=batch.DST_POP, message_points=None):
        ctx, flags = owners[id(cache)], [False] * len(entries)
        dense = [at for at, e in enumerate(entries)
                 if e[1] is None or len(e[1][0]) <= cache.mmax]
        sparse = [at for at in range(len(entries)) if at not in set(dense)]
        if dense:
            oks = cached(cache, [entries[at] for at in dense], dst, message_points)
            for at, ok in zip(dense, oks):
                flags[at] = ok
        points = []
        for at in sparse:  # the parent's branch: one host point per attester
            cid, (indices, attesting), message, sig = entries[at]
            voters = indices if attesting else np.setdiff1d(ctx.committee(cid), indices)
            agg = None
            for v in voters:
                pt = _pubkey_point(bytes(ctx.state.validators[int(v)].pubkey))
                agg = pt if agg is None else g1.affine_add(agg, pt)
            points.append((agg, message, sig))
        for at, ok in zip(sparse, batch.batch_verify_each_points(points, dst)):
            flags[at] = ok
        return flags

    batch.batch_verify_each_cached = walk


def plant_wrong_side():
    import session
    from lambda_ethereum_consensus_tpu.crypto.bls import batch
    from lambda_ethereum_consensus_tpu.ops import bls_batch

    armed: list[bool] = []
    turned: dict[int, object] = {}  # id(member list) -> the list, kept alive
    window_open = session.Window.open

    def opening(self):
        armed.append(True)
        window_open(self)

    session.Window.open = opening
    cached = batch.batch_verify_each_cached

    def marking(cache, entries, *args, **kwargs):
        if armed:  # the flush's first committee entry, through every bisection level
            members = next((e[1] for e in entries if e[1] is not None), None)
            if members is not None:
                turned[id(members)] = members
        return cached(cache, entries, *args, **kwargs)

    batch.batch_verify_each_cached = marking
    pack = bls_batch._pack_members

    def wrong(cache, flat, b):
        cid, is_single, idx, idx_inf, attesting = pack(cache, flat, b)
        for row, (_, members, _, _) in enumerate(flat):
            if id(members) in turned:
                attesting[row] = not attesting[row]
        return cid, is_single, idx, idx_inf, attesting

    bls_batch._pack_members = wrong


def plant_width_truncated():
    from lambda_ethereum_consensus_tpu.ops import bls_batch

    pack = bls_batch._pack_members

    def truncated(cache, flat, b):
        cut = [(cid, None if members is None else
                bls_batch.CommitteeSide(members[0][: cache.mmax], members[1]), sig, coeff)
               for cid, members, sig, coeff in flat]
        return pack(cache, cut, b)

    bls_batch._pack_members = truncated


FAULTS = {
    "none": lambda: None,
    "host_walk": plant_host_walk,
    "wrong_side": plant_wrong_side,
    "width_truncated": plant_width_truncated,
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
    # before a plant imports the package: in a fresh checkout the native
    # libraries are built by the run, and a package imported first has
    # already found them missing
    bench_run.build_native()
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
                      "metrics": result.get("metrics"),
                      "compared": result.get("compared")}), flush=True)
    return 0 if seen else 1


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:  # argparse, build_native
        if e.code is not None and not isinstance(e.code, int):
            print(e.code, file=sys.stderr)
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # as run.py: a failed run may leave the node's threads behind
