"""Compile the main path's TPU programs for a DESCRIBED v5e, without a chip.

The programs a TPU runs are not the programs tier-1 runs: on the CPU every
router takes its ``interpret=True`` branch.  This script takes the jitted
functions themselves (``aot_jit`` wrappers expose them as ``.jitted``),
lowers them with ``jax.ShapeDtypeStruct`` arguments placed on a described
``v5e:2x2`` device and compiles them with the chip's own compiler — at the
shapes ``chip_smoke.py`` dispatches (mainnet preset, 2^20 validators).
What the compiler refuses here costs no chip time.  Nothing runs, so this
says nothing about results or speed: a compile that passes is not a chip
run.

One JSON line per program: lower and compile seconds, ``memory_analysis()``
bytes.  Keep ``JAX_PLATFORMS=cpu``; run alone (one process at a time may
load the TPU library).

Usage:
    python scripts/tpu_compile_rehearsal.py [--only SUBSTR] [--chips 4]
        [--validators 1048576] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

I32, U32, BOOL = jnp.int32, jnp.uint32, jnp.bool_


def chain_shapes(entries: int, groups: int, checks: int = 1, k: int = 512):
    """The padded shape set one drain of ``entries`` aggregates over
    ``groups`` distinct messages dispatches (ops/bls_batch.py)."""
    from lambda_ethereum_consensus_tpu.ops import bls_batch as BB

    b, _dead = BB._entry_budget(entries, False)
    per_check = -(-entries // checks)
    return {
        "b": b,
        "mmax": BB._pow2(max(k // 8, 2)),
        "m1": BB._pow2(groups + 1) - 1,
        "s": BB._pow2(max(per_check // max(groups // checks, 1), 1)),
        "e": BB._pow2(per_check),
        "c": checks,
    }


def single_device_programs(n_validators: int, coeff_bits: int):
    """``(name, jitted fn, args, static kwargs)`` for every program the
    one-chip main path dispatches with ``interpret=False``."""
    from lambda_ethereum_consensus_tpu.ops import bigint_pallas as BP
    from lambda_ethereum_consensus_tpu.ops import bls_batch as BB
    from lambda_ethereum_consensus_tpu.ops import sha256 as S
    from lambda_ethereum_consensus_tpu.state_transition import resident as R
    from lambda_ethereum_consensus_tpu.witness import verify as WV
    from lambda_ethereum_consensus_tpu.witness.multiproof import plan_for

    sds = jax.ShapeDtypeStruct
    _pow2 = BB._pow2
    cap = _pow2(n_validators)
    k = n_validators // (32 * 64)  # committee width under the spec's shuffling
    n_comm = 32 * 64
    out = []

    # ---- base kernels
    out.append(("hash_blocks_pallas", S.hash_blocks_pallas,
                (sds((16, 1024, 128), U32),), {}))
    plane = (sds((32, 8192), I32),) * 2
    for op in ("mul_mod", "add_mod", "sub_mod"):
        out.append((f"plane_{op}", jax.jit(BP.make_plane_ops()[op]), plane, {}))

    # ---- SSZ: the state root's device calls at this registry size
    for rows in (8192, 16384, 32768):
        out.append((f"hash_blocks_pallas[rows={rows}]", S.hash_blocks_pallas,
                    (sds((16, rows, 128), U32),), {}))
    for depth in (19, 17, 15, 14, 12, 10, 8):
        out.append((f"merkle_tree_jnp[depth={depth}]", S._merkle_tree_jnp,
                    (sds((1 << depth, 16), U32),), {"depth": depth}))

    # ---- the RLC chain (ops/bls_batch._get_chain_ops(False))
    ops = BB._get_chain_ops(False)
    reg = sds((32, cap), I32)
    out.append(("chain_committee_sums", ops["committee_sums"].jitted,
                (reg, reg, sds((256, _pow2(k)), I32), sds((256, _pow2(k)), BOOL)),
                {}))
    # gossip drain: 1,024 aggregates over 64 messages; block: 128 over 64;
    # subnet flush: 4,096 one-bit votes of a slot's 64 committees, each
    # pubkey gathered from the registry planes by validator index
    b_subnet = chain_shapes(4096, 64, k=k)["b"]
    out.append((f"chain_single_gather[subnet b={b_subnet}]",
                ops["single_gather"].jitted, (reg, reg, sds((b_subnet,), I32)), {}))
    for tag, entries, groups in (("gossip", 1024, 64), ("block", 128, 64),
                                 ("subnet", 4096, 64)):
        sh = chain_shapes(entries, groups, k=k)
        b, mmax, m1, s, e, c = (sh[x] for x in ("b", "mmax", "m1", "s", "e", "c"))
        sums = sds((32, n_comm), I32)
        kb = sds((coeff_bits, b), I32)
        lv = sds((b,), BOOL)
        g1 = sds((32, b), I32)
        g2 = sds((32, 2, b), I32)
        # the aggregation program: the dense width everywhere, and for a
        # gossip drain the wider buckets a sparse flush selects (up to
        # half the committee: a network below two thirds participation)
        widths = (BB.DeviceCommitteeCache.gather_widths(k) if tag == "gossip"
                  else (mmax,))
        out += [
            (f"chain_agg_corrected[{tag} b={b}]" if w == mmax
             else f"chain_agg_corrected[{tag} b={b} w={w}]",
             ops["agg_corrected"].jitted,
             (reg, reg, sums, sums, sds((b,), I32), sds((b, w), I32),
              sds((b, w), BOOL), sds((b,), BOOL)), {})
            for w in widths
        ]
        out += [
            (f"chain_ladder_g1[{tag} b={b}]", ops["ladder_g1"].jitted,
             (g1, g1, kb, lv), {}),
            (f"chain_ladder_g2[{tag} b={b}]", ops["ladder_g2"].jitted,
             (g2, g2, kb, lv), {}),
            # prep's two gathers: a padded slot holds index b, one past
            # the last lane, and must read the identity there
            (f"chain_take_g1[{tag} b={b} c={c} m1={m1} s={s}]",
             jax.jit(lambda X, Y, Z, inf, idx: ops["take_entries"](
                 (X, Y, Z, inf), idx, axis=1)),
             (g1, g1, g1, lv, sds((c, m1, s), I32)), {}),
            (f"chain_take_g2[{tag} b={b} c={c} e={e}]",
             jax.jit(lambda X, Y, Z, inf, idx: ops["take_entries"](
                 (X, Y, Z, inf), idx, axis=2)),
             (g2, g2, g2, lv, sds((c, e), I32)), {}),
            (f"chain_reduce_g1[{tag} c={c} m1={m1} s={s}]",
             ops["reduce_g1"].jitted,
             (sds((32, c, m1, s), I32),) * 3 + (sds((c, m1, s), BOOL),), {}),
            (f"chain_reduce_g2[{tag} c={c} e={e}]", ops["reduce_g2"].jitted,
             (sds((32, 2, c, e), I32),) * 3 + (sds((c, e), BOOL),), {}),
            (f"chain_norm_g1[{tag} c={c} m1={m1}]", ops["norm_g1_jit"].jitted,
             (sds((32, c, m1), I32),) * 3, {}),
            (f"chain_norm_g2[{tag} c={c}]", ops["norm_g2_jit"].jitted,
             (sds((32, 2, c), I32),) * 3, {}),
            (f"pair_plane_miller[{tag} c={c} m={m1 + 1}]",
             ops["miller"].jitted,
             (sds((32, c, m1 + 1), I32),) * 2
             + (sds((32, 2, c, m1 + 1), I32),) * 2, {}),
        ]
        from lambda_ethereum_consensus_tpu.ops.bls_pairing import _get_ops

        out.append((f"pair_plane_masked_product[{tag} c={c} m={m1 + 1}]",
                    _get_ops(plane=True)["masked_product"].jitted,
                    (sds((32, 2, 3, 2, c, m1 + 1), I32), sds((c, m1 + 1), BOOL)),
                    {}))

    # ---- resident transition kernels (state_transition/resident.py)
    kern = R._kernels()
    zi, zb, zu = sds((cap,), I32), sds((cap,), BOOL), sds((cap,), U32)
    out += [
        ("transition_sums", kern["sums"].jitted, (zi, zi, zi, zb, zb, zb), {}),
        ("transition_sweep", kern["sweep"].jitted,
         (zu, zu, zi, zi, zi, zb, zb, zb, sds((7,), I32), sds((5, 33), I32)), {}),
        ("transition_hysteresis", kern["hysteresis"].jitted,
         (zu, zu, zi, sds((4,), U32)), {}),
    ]
    for bk in R._scatter_buckets(cap):
        idx, idu = sds((bk,), I32), sds((bk,), U32)
        out += [
            (f"transition_scatter2[{bk}]", kern["scatter2"].jitted,
             (zu, zu, idx, idu, idu), {}),
            (f"transition_scatter1[{bk}]", kern["scatter1"].jitted,
             (zi, idx, idx), {}),
            (f"transition_gather2[{bk}]", kern["gather2"].jitted,
             (zu, zu, idx), {}),
        ]

    # ---- witness_verify at bucket 64, the canonical single-index proof
    proof = WV._dummy_proof()
    packed = WV._assemble([proof] * 64, [b"\x00" * 32] * 64, [plan_for(proof)] * 64)
    bsz, slots = packed["nodes"].shape[:2]
    idx = sds(packed["lidx"].shape, I32)
    out.append((f"witness_verify[b={bsz} slots={slots}]", WV._get_kernel().jitted,
                (sds((bsz, slots, 8), U32), idx, idx, idx, sds((bsz,), I32),
                 sds((bsz, 8), U32)), {}))
    return out


def mesh_programs(mesh, n_validators: int, coeff_bits: int):
    """The four-chip phase's programs: ``(name, jitted fn, [(shape, dtype,
    PartitionSpec)], static kwargs)``."""
    from jax.sharding import PartitionSpec as P

    from lambda_ethereum_consensus_tpu.ops import bls_batch as BB
    from lambda_ethereum_consensus_tpu.ops import sha256 as S
    from lambda_ethereum_consensus_tpu.ops.bls_shard import make_shard_ops

    d = int(mesh.devices.size)
    out = []
    m = n_validators // 2  # registry subtree: one block per validator pair
    out.append((
        f"merkle_root_words_sharded[m={m}]",
        S._sharded_tree_fn(mesh, (m // d).bit_length() - 1, d.bit_length() - 1),
        [((m, 16), U32, P("dp", None))], {},
    ))
    sops = make_shard_ops(mesh, False)
    # one drain: 1,024 entries over 64 messages, dealt round-robin
    n, groups, c = 1024, 64, 1
    nl = -(-n // d)
    # ops/bls_shard.py's own per-device rule (a dead tail slot on every
    # device), not the single-device chain's BB._entry_budget
    bl = (nl // BB._QUANTUM + 1) * BB._QUANTUM
    b = d * bl
    m1 = BB._pow2(groups + 1) - 1
    s = BB._pow2(-(-(n // groups) // d))
    e = BB._pow2(nl)
    mp = max(m1 + 1, d)
    col, col2 = P(None, "dp"), P(None, None, "dp")
    jac1 = [((32, b), I32, col)] * 3 + [((b,), BOOL, P("dp"))]
    jac2 = [((32, 2, b), I32, col2)] * 3 + [((b,), BOOL, P("dp"))]
    out += [
        (f"shard_ladder_g1[b={b}]", sops["ladder_g1"],
         [((32, b), I32, col)] * 2 + [((coeff_bits, b), I32, col),
                                      ((b,), BOOL, P("dp"))], {}),
        (f"shard_ladder_g2[b={b}]", sops["ladder_g2"],
         [((32, 2, b), I32, col2)] * 2 + [((coeff_bits, b), I32, col),
                                          ((b,), BOOL, P("dp"))], {}),
        (f"shard_reduce_g1[c={c} m1={m1} s={s}]", sops["reduce_g1"],
         jac1 + [((d, c, m1, s), I32, P("dp"))], {}),
        (f"shard_reduce_g2[c={c} e={e}]", sops["reduce_g2"],
         jac2 + [((d, c, e), I32, P("dp"))], {}),
        (f"shard_miller_combine[c={c} mp={mp}]", sops["miller_combine"],
         [((32, c, mp), I32, col2)] * 2
         + [((32, 2, c, mp), I32, P(None, None, None, "dp"))] * 2
         + [((c, mp), BOOL, col)], {}),
    ]
    # the witness plane at bucket 64, proofs dealt over dp
    from lambda_ethereum_consensus_tpu.witness import verify as WV
    from lambda_ethereum_consensus_tpu.witness.multiproof import plan_for

    proof = WV._dummy_proof()
    packed = WV._assemble([proof] * 64, [b"\x00" * 32] * 64, [plan_for(proof)] * 64)
    bsz, slots = packed["nodes"].shape[:2]
    idx = (packed["lidx"].shape, I32, P(None, "dp", None))
    out.append((
        f"witness_verify_sharded[b={bsz} slots={slots}]",
        WV._get_sharded_kernel(mesh),
        [((bsz, slots, 8), U32, P("dp", None, None)), idx, idx, idx,
         ((bsz,), I32, P("dp")), ((bsz, 8), U32, P("dp", None))], {},
    ))
    return out


def compile_one(name, fn, args, static) -> dict:
    """Lower + compile one program; a refusal is recorded, not raised."""
    fn = getattr(fn, "jitted", fn)
    rec = {"program": name}
    try:
        t0 = time.perf_counter()
        lowered = fn.lower(*args, **static)
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t0, 2)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            key: int(getattr(mem, f"{key}_size_in_bytes"))
            for key in ("argument", "output", "temp", "generated_code")
        }
        rec["ok"] = True
    except Exception as e:  # the compiler's refusal is the finding
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {str(e)[:600]}"
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter on program names")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--validators", type=int, default=1 << 20)
    ap.add_argument("--coeff-bits", type=int, default=64)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep it off
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sds = jax.ShapeDtypeStruct
    if args.chips == 1:
        one_chip = SingleDeviceSharding(topo.devices[0])
        programs = [
            (name, fn, tuple(sds(a.shape, a.dtype, sharding=one_chip) for a in a_),
             st)
            for name, fn, a_, st in single_device_programs(
                args.validators, args.coeff_bits
            )
        ]
    else:
        mesh = Mesh(np.array(topo.devices), axis_names=("dp",))
        programs = [
            (name, fn,
             tuple(sds(shape, dt, sharding=NamedSharding(mesh, spec))
                   for shape, dt, spec in a_), st)
            for name, fn, a_, st in mesh_programs(
                mesh, args.validators, args.coeff_bits
            )
        ]
    sink = open(args.out, "a") if args.out else None
    failed = 0
    for name, fn, a_, st in programs:
        if args.only and args.only not in name:
            continue
        rec = compile_one(name, fn, a_, st)
        rec["target"] = f"v5e:2x2 described, {args.chips} chip(s)"
        failed += not rec["ok"]
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
