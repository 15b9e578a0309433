"""Is a DONATED executable sound after the AOT disk round trip?

``ops/aot.aot_jit(..., disk=False)`` keeps donated programs (the resident
epoch sweep and scatters) off the serialized-executable tier because round
13 saw a deserialized donated executable read garbage through its aliased
buffers.  That was measured on a stack that no longer exists.  This probe
re-asks the question on whatever backend it runs on, for ROADMAP C7:

    python scripts/aot_donation_probe.py save   # compile + serialize
    python scripts/aot_donation_probe.py load   # FRESH process: load + run

``load`` must find the executable on disk, chains ``--steps`` donated sweeps
over 2^20 seeded validator columns (each step's outputs are the next step's
donated inputs), and compares every column bit-for-bit with the same chain
through a freshly compiled, non-donating jit of the same body.  One JSON
line; exit 1 on any mismatch.  Two processes, one after the other: a chip
belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]  # the package; bench_state_shard

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("save", "load"))
    ap.add_argument("--validators", type=int, default=1 << 20)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    import bench_state_shard as BSS
    from lambda_ethereum_consensus_tpu.config import mainnet_spec, use_chain_spec
    from lambda_ethereum_consensus_tpu.ops import aot
    from lambda_ethereum_consensus_tpu.state_transition import resident as RES
    from lambda_ethereum_consensus_tpu.utils.env import enable_compile_cache

    enable_compile_cache()
    body = RES._kernel_bodies()["sweep"]
    donated = aot.aot_jit(
        jax.jit(body, donate_argnums=(0, 1, 2)), "probe_donated_sweep", disk=True
    )
    reference = jax.jit(body)  # no donation, never serialized

    n = args.validators
    with use_chain_spec(mainnet_spec()) as spec:
        cols = BSS._columns(n, args.seed)
        sums = BSS._oracle_sums(cols, {"part_idx": np.zeros(0, np.int64),
                                       "part_val": np.zeros(0, np.uint8)})
        params, luts, _total = BSS._reward_params(spec, sums, n)
    lo = (cols["bal"] & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (cols["bal"] >> np.uint64(32)).astype(np.uint32)
    scores = cols["scores"].astype(np.int32)
    rest = (
        cols["efb_incr"], cols["part_prev"].astype(np.int32), cols["eligible"],
        cols["active_prev"], cols["slashed"],
        np.asarray(params, np.int32), np.asarray(luts, np.int32),
    )

    got = tuple(jax.device_put(x) for x in (lo, hi, scores))
    want = tuple(jax.device_put(x) for x in (lo, hi, scores))
    steps = 1 if args.mode == "save" else args.steps
    mismatched_at = None
    for step in range(steps):
        got = donated(*got, *rest)  # rebinds: the inputs were donated
        want = reference(*want, *rest)
        if not all(np.array_equal(np.asarray(g), np.asarray(w))
                   for g, w in zip(got, want)):
            mismatched_at = step
            break
    row = next(r for r in aot.compile_profile() if r["entry"] == "probe_donated_sweep")
    dev = jax.devices()[0]
    out = {
        "probe": "aot_donated_round_trip", "mode": args.mode,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "validators": n, "steps": steps, "source": row["source"],
        "saves": row["saves"], "loads": row["loads"],
        "bit_exact": mismatched_at is None, "first_mismatch_step": mismatched_at,
    }
    ok = mismatched_at is None and row["source"] == (
        "compile" if args.mode == "save" else "disk")
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
