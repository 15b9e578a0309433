"""Per-stage compile/run timing of the chained device verify on real TPU.

Warms the AOT/compile caches at the production shape buckets and prints
one line per stage (cold = compile + run, warm = run).  Run before
benching: the shape set matches scripts/bench_chain.py's round-4
scenario (epoch committee cache + grouped messages + BLS_RLC_BITS
ladders), so a completed probe warm-up is exactly the bench's program
set.

Usage: python scripts/tpu_stage_probe.py [instances] [groups] [aggs] [committee]
"""

import os
import secrets
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "5")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from lambda_ethereum_consensus_tpu.crypto.bls import curve as C  # noqa: E402
from lambda_ethereum_consensus_tpu.crypto.bls.batch import _COEFF_BITS  # noqa: E402
from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (  # noqa: E402
    DST_POP,
    hash_to_g2,
)
from lambda_ethereum_consensus_tpu.ops import bls_batch as BB  # noqa: E402


def main() -> None:
    inst = int(sys.argv[1]) if len(sys.argv) > 1 else 2
    groups = int(sys.argv[2]) if len(sys.argv) > 2 else 127
    aggs = int(sys.argv[3]) if len(sys.argv) > 3 else 16
    committee = int(sys.argv[4]) if len(sys.argv) > 4 else 2048
    n_committees = int(os.environ.get("PROBE_COMMITTEES", "256"))

    print(f"backend: {jax.default_backend()}  coeff_bits: {_COEFF_BITS}", flush=True)
    interpret = jax.default_backend() != "tpu"
    ops = BB._get_chain_ops(interpret)
    rng = np.random.default_rng(0)

    a_total = inst * groups * aggs
    B, _dead = BB._entry_budget(a_total, interpret)
    mmax = BB._pow2(max(committee // 8, 2))
    m1 = BB._pow2(groups + 1) - 1
    s = BB._pow2(aggs)
    e = BB._pow2(groups * aggs)

    def stage(name, fn):
        t0 = time.perf_counter()
        out = fn()
        leaves = jax.tree_util.tree_leaves(out)
        if hasattr(leaves[0], "block_until_ready"):
            leaves[0].block_until_ready()  # hybrid tail returns host numpy
        print(f"{name}: {time.perf_counter() - t0:.1f}s", flush=True)
        return out

    # registry + committee structure (exactly the bench's shapes)
    n_vals = n_committees * committee
    pts = [C.g1.multiply_raw(C.G1_GENERATOR, 3 + i) for i in range(8)]
    rx, ry = BB._g1_planes([pts[i % 8] for i in range(n_vals)])
    rx_d, ry_d = jnp.asarray(rx), jnp.asarray(ry)
    committees = rng.permutation(n_vals).astype(np.int32).reshape(
        n_committees, committee
    )

    t0 = time.perf_counter()
    cache = BB.DeviceCommitteeCache(
        (rx_d, ry_d), committees, interpret=interpret, chunk=min(256, n_committees)
    )
    jax.block_until_ready((cache.sum_x, cache.sum_y))
    print(f"committee_sums ({n_committees}x{committee}) cold: "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    comm_ids = rng.integers(0, n_committees, size=B).astype(np.int32)
    miss_idx = np.zeros((B, mmax), np.int32)
    miss_inf = np.ones((B, mmax), bool)
    for j in range(B):
        mc = int(rng.integers(0, committee // 10 + 1))
        miss_idx[j, :mc] = committees[comm_ids[j]][:mc]
        miss_inf[j, :mc] = False
    agg = stage(
        f"agg_corrected (B={B}, mmax={mmax}) cold",
        lambda: cache.aggregate(comm_ids, miss_idx, miss_inf),
    )
    stage("agg_corrected warm", lambda: cache.aggregate(comm_ids, miss_idx, miss_inf))
    ax, ay, _ = agg

    kbits = BB._scalar_bits_batch(
        [secrets.randbits(_COEFF_BITS) | 1 for _ in range(B)], _COEFF_BITS
    ).T
    live = np.ones(B, bool)
    jac1 = stage(
        f"ladder_g1 B={B} w={_COEFF_BITS} cold",
        lambda: ops["ladder_g1"](ax, ay, jnp.asarray(kbits), jnp.asarray(live)),
    )
    stage(
        "ladder_g1 warm",
        lambda: ops["ladder_g1"](ax, ay, jnp.asarray(kbits), jnp.asarray(live)),
    )

    qts = [C.g2.multiply_raw(C.G2_GENERATOR, 3 + i) for i in range(8)]
    sgx, sgy = BB._g2_planes([qts[i % 8] for i in range(B)])
    jac2 = stage(
        f"ladder_g2 B={B} w={_COEFF_BITS} cold",
        lambda: ops["ladder_g2"](
            jnp.asarray(sgx), jnp.asarray(sgy), jnp.asarray(kbits), jnp.asarray(live)
        ),
    )
    stage(
        "ladder_g2 warm",
        lambda: ops["ladder_g2"](
            jnp.asarray(sgx), jnp.asarray(sgy), jnp.asarray(kbits), jnp.asarray(live)
        ),
    )

    idx_g1 = rng.integers(0, B, size=(inst, m1, s)).astype(np.int32)
    idx_sig = rng.integers(0, B, size=(inst, e)).astype(np.int32)
    hpts = [hash_to_g2(b"m%d" % i, DST_POP) for i in range(8)]
    hx, hy = BB._g2_planes([hpts[i % 8] for i in range(inst * m1)])
    hx = hx.reshape(32, 2, inst, m1)
    hy = hy.reshape(32, 2, inst, m1)
    live2 = np.ones((inst, m1 + 1), bool)

    args = lambda: ops["prep"](
        jac1,
        jac2,
        jnp.asarray(idx_g1),
        jnp.asarray(idx_sig),
        jnp.asarray(hx),
        jnp.asarray(hy),
        jnp.asarray(live2),
    )
    px, py, qx, qy, mask = stage(f"prep (c={inst}, m={m1+1}, s={s}, e={e}) cold", args)
    stage("prep warm", args)

    f = stage(f"miller (c={inst}, m={m1+1}) cold", lambda: ops["miller"](px, py, qx, qy))
    stage("miller warm", lambda: ops["miller"](px, py, qx, qy))

    stage("check_tail cold", lambda: ops["check_tail"](f, mask))
    stage("check_tail warm", lambda: ops["check_tail"](f, mask))
    print("STAGES DONE", flush=True)


if __name__ == "__main__":
    main()
