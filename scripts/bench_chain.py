"""Aggregate-BLS-verification throughput (BASELINE.json scenario 3).

Round-4 scenario — the mainnet aggregate channel, cache-shaped:

- I instances (checks) x G committees x A aggregates per committee.  The
  A aggregates of one committee share one ``AttestationData`` (the real
  gossip shape: ~16 aggregators per committee duplicate-cover the same
  message), so the drain hashes G*I messages — not one per entry — and
  the pairing count per check is G+1, not entries+1.
- Committee membership is fixed per epoch: the registry lives on device
  and each committee's FULL pubkey sum is precomputed ONCE
  (``DeviceCommitteeCache``).  A drain pays only the missing-member
  correction per aggregate (participation drawn from [90%, 100%]) —
  round 3's measured super-linear wall (8.3M-point registry gather per
  drain) collapses to a ~5% gather.
- RLC coefficients are ``BLS_RLC_BITS`` wide (64 default — the deployed
  batch-verification width; crypto/bls/batch.py) so the device ladders
  run half of round 3's depth.

The WHOLE check still runs on device per drain: correction gather +
subtract, 64-bit RLC ladders, per-message group sums, Miller loops,
shared final exponentiation — the verdict pulled back is downstream of
final exp.  Host hashing (G*I messages) is PIPELINED against the
previous drain's device work.  The epoch cache build is reported
separately AND charged to the headline rate amortized over one epoch of
drains (32 slots at >= 1 drain/slot — conservative: aggregates stay
valid for 32 slots, and a syncing node drains far more often).

Ref to beat: native/bls_nif/src/lib.rs:14-158 (blst aggregate-verify,
thousands/s per CPU core).

Setup trick (not part of the timed path): committees sign with known
scalars, so a valid aggregate signature is H(m)^(sum sk) — one small G2
multiply per aggregate instead of K signatures.

Usage: python scripts/bench_chain.py [instances] [groups] [aggs_per_group] [committee]
Prints JSON lines; the aggregate_bls_verifications_per_sec line is the metric.
"""

from __future__ import annotations

import json
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

# one epoch of drains amortizes the committee-cache build (see module doc)
DRAINS_PER_EPOCH = 32


def run(
    inst: int = 2,
    groups: int = 127,
    aggs: int = 16,
    committee: int = 2048,
    drains: int | None = None,
    n_committees: int = 256,
    progress=None,
) -> list[dict]:
    """Run the chained-verify bench; returns the JSON records (smoke line
    first, throughput line last)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lambda_ethereum_consensus_tpu.crypto.bls import curve as C
    from lambda_ethereum_consensus_tpu.crypto.bls.batch import _COEFF_BITS
    from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (
        DST_POP,
        hash_to_g2_many,
    )
    from lambda_ethereum_consensus_tpu.ops import bls_batch as BB

    if drains is None:
        drains = int(os.environ.get("BENCH_DRAINS", "3"))
    interpret = jax.default_backend() != "tpu"
    note = progress or (lambda msg: None)

    a_total = inst * groups * aggs  # aggregates (verifications) per drain
    msgs_per_drain = inst * groups
    ops = BB._get_chain_ops(interpret)

    # shape constants (needed by the warmer thread below)
    m1 = BB._pow2(groups + 1) - 1  # message groups; slot m1 is the sig pair
    s = BB._pow2(aggs)
    e_slots = BB._pow2(groups * aggs)  # sig slots per check
    mmax = BB._pow2(max(committee // 8, 2))  # correction capacity (12.5%)
    # padded slots of the index rectangles point at ``dead``: past the
    # last lane, read as the identity by prep (no lane is kept for it)
    b, dead = BB._entry_budget(a_total, interpret)
    n_vals = n_committees * committee

    # ---- program warmer: the first dispatch of each program pays its
    # load (or, cold, its compile).  Dispatch one full DUMMY drain at
    # the production shapes NOW, on a thread, so the device loads every
    # program while the host packs registries and mints signatures —
    # exactly the overlap a booting node gets (VERDICT r3 next #7).
    import threading

    warm_stats = {}

    def _warm_programs():
        if interpret:
            return  # CPU path: nothing to pre-load
        try:
            _warm_programs_inner()
        except Exception as e:  # a failed warm must be VISIBLE in the
            # record (cold first dispatch corrupts the headline), never
            # silently swallowed by the daemon thread
            warm_stats["error"] = f"{type(e).__name__}: {e}"

    def _warm_programs_inner():
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        zreg = jnp.zeros((32, n_vals), jnp.int32)
        chunk = min(256, n_committees)
        ops["committee_sums"](
            zreg, zreg,
            jnp.zeros((chunk, BB._pow2(committee)), jnp.int32),
            jnp.zeros((chunk, BB._pow2(committee)), bool),
        )
        sx = jnp.zeros((32, n_committees), jnp.int32)
        ax, ay, _ = ops["agg_corrected"](
            zreg, zreg, sx, sx,
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, mmax), jnp.int32),
            jnp.ones((b, mmax), bool),
            jnp.zeros((b,), bool),
        )
        from lambda_ethereum_consensus_tpu.crypto.bls.batch import (
            _COEFF_BITS as w,
        )

        kb = jnp.zeros((w, b), jnp.int32)
        lv = jnp.zeros((b,), bool)
        jac1 = ops["ladder_g1"](ax, ay, kb, lv)
        jac2 = ops["ladder_g2"](
            jnp.zeros((32, 2, b), jnp.int32), jnp.zeros((32, 2, b), jnp.int32),
            kb, lv,
        )
        px, py, qx, qy, mask = ops["prep"](
            jac1, jac2,
            jnp.zeros((inst, m1, s), jnp.int32),
            jnp.zeros((inst, e_slots), jnp.int32),
            jnp.zeros((32, 2, inst, m1), jnp.int32),
            jnp.zeros((32, 2, inst, m1), jnp.int32),
            jnp.zeros((inst, m1 + 1), bool),
        )
        f = ops["miller"](px, py, qx, qy)
        ops["check_tail"](f, mask)  # pulls; blocks until everything ran
        warm_stats["overlap_s"] = round(time.perf_counter() - t0, 1)

    warmer = threading.Thread(target=_warm_programs, daemon=True)
    warmer.start()

    # --- device-resident validator registry (pubkeys as limb planes) ----
    # registry points: sk_i * G -- build from a few distinct points cycled
    # (the curve math doesn't care; packing 0.5M distinct muls on host
    # would dominate setup)
    base_sks = [3 + i for i in range(64)]
    base_pts = [C.g1.multiply_raw(C.G1_GENERATOR, sk) for sk in base_sks]
    reg_pts = [base_pts[i % 64] for i in range(n_vals)]
    reg_sks = np.array([base_sks[i % 64] for i in range(n_vals)], np.int64)
    note(f"packing registry planes ({n_vals} pubkeys)")
    rx, ry = BB._g1_planes(reg_pts)
    rx_d, ry_d = jnp.asarray(rx), jnp.asarray(ry)

    rng = np.random.default_rng(7)

    # --- epoch committee structure: a disjoint partition, like the spec's
    # per-epoch shuffling (one validator serves in exactly one committee)
    committees = rng.permutation(n_vals).astype(np.int32).reshape(
        n_committees, committee
    )
    comm_sk_total = reg_sks[committees].sum(axis=1)  # (n_committees,)

    note(f"building epoch committee cache ({n_committees} x {committee})")
    t0 = time.perf_counter()
    cache = BB.DeviceCommitteeCache(
        (rx_d, ry_d), committees, interpret=interpret, chunk=min(256, n_committees)
    )
    jax.block_until_ready((cache.sum_x, cache.sum_y))
    cache_build_s = time.perf_counter() - t0
    note(f"committee cache built in {cache_build_s:.1f}s")

    def make_drain(tag: int):
        """Scenario construction — the parts a real node RECEIVES (the
        signatures, the participation bits) are built here, outside the
        timed loop; hashing and all marshalling stay in the timed path."""
        sel = (tag * msgs_per_drain + np.arange(msgs_per_drain)) % n_committees
        comm_ids = np.repeat(sel, aggs).astype(np.int32)  # (a_total,)
        # participation per aggregate: uniform in [90%, 100%]
        miss_counts = rng.integers(0, committee // 10 + 1, size=a_total)
        miss_idx = np.zeros((a_total, mmax), np.int32)
        miss_inf = np.ones((a_total, mmax), bool)
        agg_sk = np.zeros(a_total, np.int64)
        for j in range(a_total):
            mc = int(miss_counts[j])
            members = committees[comm_ids[j]]
            missing = rng.choice(members, size=mc, replace=False) if mc else []
            miss_idx[j, :mc] = missing
            miss_inf[j, :mc] = False
            agg_sk[j] = comm_sk_total[comm_ids[j]] - reg_sks[missing].sum()
        msgs = [b"drain%d-msg%d" % (tag, g) for g in range(msgs_per_drain)]
        h_pts = hash_to_g2_many(msgs, DST_POP)
        sigs = [
            C.g2.multiply_raw(h_pts[j // aggs], int(agg_sk[j]))
            for j in range(a_total)
        ]
        return comm_ids, miss_idx, miss_inf, msgs, sigs

    def hash_msgs(msgs):
        return hash_to_g2_many(msgs, DST_POP)

    def dispatch(comm_ids, miss_idx, miss_inf, h_points, sigs, live_checks=None,
                 fence=None):
        """Enqueue one drain's full device chain; returns the ok array
        (not yet pulled).  live_checks optionally marks whole checks dead
        (the on-chip 'empty drain' semantics).  ``fence(name, thunk)``
        optionally wraps each device stage — the stage-breakdown mode
        passes a blocking timer so the SAME program chain is measured,
        not a parallel copy of it."""
        run = fence if fence is not None else (lambda name, thunk: thunk())
        pad = b - a_total
        cid = np.concatenate([comm_ids, np.zeros(pad, np.int32)])
        mi = np.concatenate([miss_idx, np.zeros((pad, mmax), np.int32)])
        mf = np.concatenate([miss_inf, np.ones((pad, mmax), bool)])
        agg_x, agg_y, _agg_inf = run(
            "agg_corrected", lambda: cache.aggregate(cid, mi, mf)
        )  # (32, b)

        coeffs = [secrets.randbits(_COEFF_BITS) | 1 for _ in range(a_total)]
        sgx, sgy = BB._g2_planes(sigs + [C.G2_GENERATOR] * pad)
        kbits = BB._scalar_bits_batch(coeffs + [1] * pad, _COEFF_BITS).T
        live = np.zeros(b, bool)
        live[:a_total] = True

        jac1 = run(
            "ladder_g1",
            lambda: ops["ladder_g1"](
                agg_x, agg_y, jnp.asarray(kbits), jnp.asarray(live)
            ),
        )
        jac2 = run(
            "ladder_g2",
            lambda: ops["ladder_g2"](
                jnp.asarray(sgx), jnp.asarray(sgy), jnp.asarray(kbits),
                jnp.asarray(live),
            ),
        )

        idx_g1 = np.full((inst, m1, s), dead, np.int32)
        idx_sig = np.full((inst, e_slots), dead, np.int32)
        static_live = np.zeros((inst, m1 + 1), bool)
        per_check = groups * aggs
        for ci in range(inst):
            if live_checks is not None and not live_checks[ci]:
                continue
            for g in range(groups):
                for a in range(aggs):
                    idx_g1[ci, g, a] = (ci * groups + g) * aggs + a
            idx_sig[ci, :per_check] = ci * per_check + np.arange(per_check)
            static_live[ci, :groups] = True
            static_live[ci, m1] = True
        hx, hy = BB._g2_planes(
            [
                h_points[ci * groups + g] if g < groups else C.G2_GENERATOR
                for ci in range(inst)
                for g in range(m1)
            ]
        )
        px, py, qx, qy, mask = run(
            "prep_gather_reduce_norm",
            lambda: ops["prep"](
                jac1,
                jac2,
                jnp.asarray(idx_g1),
                jnp.asarray(idx_sig),
                jnp.asarray(hx.reshape(32, 2, inst, m1)),
                jnp.asarray(hy.reshape(32, 2, inst, m1)),
                jnp.asarray(static_live),
            ),
        )
        f = run("miller", lambda: ops["miller"](px, py, qx, qy))
        return run("final_exp_tail", lambda: ops["check_tail"](f, mask))

    # ---- warm-up drain (compiles or AOT-loads everything; not timed) ---
    note("building warm-up drain")
    warm = make_drain(0)
    t0 = time.perf_counter()
    h_points = hash_msgs(warm[3])
    hash_time = time.perf_counter() - t0
    warmer.join()  # programs loaded while the host built the scenario
    note(
        f"hashing done ({hash_time:.1f}s); warmer overlapped "
        f"{warm_stats.get('overlap_s')}s; dispatching warm-up chain"
    )
    t0 = time.perf_counter()
    ok = dispatch(warm[0], warm[1], warm[2], h_points, warm[4])
    ok_host = np.asarray(ok)
    assert all(ok_host), "warm-up drain must verify"
    warm_compile = time.perf_counter() - t0
    note(f"warm-up chain done in {warm_compile:.1f}s")

    # steady-state epoch-boundary cost: the FIRST build above may have
    # paid (or waited on) program compiles; a real node's per-epoch
    # rebuild reuses them.  Rebuild once warm and amortize THAT.
    t0 = time.perf_counter()
    cache = BB.DeviceCommitteeCache(
        (rx_d, ry_d), committees, interpret=interpret, chunk=min(256, n_committees)
    )
    jax.block_until_ready((cache.sum_x, cache.sum_y))
    cache_build_cold_s, cache_build_s = cache_build_s, time.perf_counter() - t0
    note(f"warm committee cache rebuild in {cache_build_s:.1f}s")

    # ---- on-chip smoke: valid / invalid / empty verdicts ----------------
    # (VERDICT r2 #8: every bench run certifies on-chip correctness.)
    # Same shapes as the throughput drains, so no extra programs compile.
    bad_sigs = list(warm[4])
    bad_sigs[0] = C.g2.multiply_raw(bad_sigs[0], 3)  # corrupt check 0's first sig
    ok_bad = np.asarray(dispatch(warm[0], warm[1], warm[2], h_points, bad_sigs))
    ok_empty = np.asarray(
        dispatch(
            warm[0], warm[1], warm[2], h_points, warm[4],
            live_checks=[False] + [True] * (inst - 1),
        )
    )
    smoke = {
        "metric": "chain_verify_smoke",
        "valid": bool(all(ok_host)),
        "invalid_detected": bool(not ok_bad[0] and all(ok_bad[1:])),
        "empty_trivially_ok": bool(all(ok_empty)),
        "backend": "tpu" if not interpret else "interpret",
    }
    assert smoke["invalid_detected"], "on-chip smoke: corrupted sig not rejected"

    # ---- optional stage breakdown (VERDICT r4 next #2: name the wall) --
    # one drain with a block_until_ready fence after every stage; the
    # fences serialize the pipeline, so this is measured OUTSIDE the
    # throughput loop and only when asked for
    stage_ms: dict[str, float] = {}
    if os.environ.get("BENCH_STAGES"):
        import jax as _jax

        d = make_drain(99)
        h_stage = hash_msgs(d[3])

        def fence(name, thunk):
            t = time.perf_counter()
            out = thunk()
            _jax.block_until_ready(out)
            stage_ms[name] = round((time.perf_counter() - t) * 1e3, 1)
            return out

        t_all = time.perf_counter()
        ok_stage = dispatch(d[0], d[1], d[2], h_stage, d[4], fence=fence)
        stage_ms["total_fenced"] = round((time.perf_counter() - t_all) * 1e3, 1)
        assert all(np.asarray(ok_stage))
        note(f"stage breakdown (fenced): {stage_ms}")

    # ---- steady state: device drain i overlaps host hashing of i+1 -----
    note("building steady-state drains")
    prepared = [make_drain(1 + i) for i in range(drains)]
    h_cur = hash_msgs(prepared[0][3])
    t_start = time.perf_counter()
    pending = None
    hash_busy = 0.0
    for i in range(drains):
        comm_ids, miss_idx, miss_inf, msgs, sigs = prepared[i]
        ok = dispatch(comm_ids, miss_idx, miss_inf, h_cur, sigs)
        if pending is not None:
            assert all(np.asarray(pending))
        if i + 1 < drains:
            # overlap: hash drain i+1 while the device runs drain i
            t0 = time.perf_counter()
            h_cur = hash_msgs(prepared[i + 1][3])
            hash_busy += time.perf_counter() - t0
        pending = ok
    assert all(np.asarray(pending))
    total = time.perf_counter() - t_start

    per_drain = total / drains
    amortized_cache = cache_build_s / DRAINS_PER_EPOCH
    rate = a_total / (per_drain + amortized_cache)
    from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (
        native_hash_available,
    )
    from lambda_ethereum_consensus_tpu.ops.aot import aot_stats

    record = {
        "metric": "aggregate_bls_verifications_per_sec",
        "value": round(rate, 1),
        "unit": "aggregate verifications/s",
        "scenario": (
            f"{inst}x{groups} committees x {aggs} aggregates x "
            f"{committee} committee, epoch-cached"
        ),
        "verifications_per_drain": a_total,
        "messages_per_drain": msgs_per_drain,
        "constituent_sigs_per_sec": round(rate * committee, 0),
        "drain_ms": round(per_drain * 1e3, 1),
        "epoch_cache_build_s": round(cache_build_s, 2),
        "epoch_cache_build_cold_s": round(cache_build_cold_s, 2),
        "amortized_cache_ms": round(amortized_cache * 1e3, 1),
        "host_hash_ms_per_drain": round(hash_busy / max(drains - 1, 1) * 1e3, 1),
        "participation": "uniform [90%, 100%]",
        "coeff_bits": _COEFF_BITS,
        "native_hash": native_hash_available(),
        "warmup_s": round(warm_compile, 1),
        "warmup_overlap_s": warm_stats.get("overlap_s"),
        **(
            {"warmup_error": warm_stats["error"]} if "error" in warm_stats else {}
        ),
        "setup_hash_ms": round(hash_time * 1e3, 1),
        **({"stage_ms": stage_ms} if stage_ms else {}),
        "aot": aot_stats(),
        "backend": jax.default_backend(),
        "vs_baseline": round(rate / 50000.0, 4),
    }
    return [smoke, record]


def main() -> None:
    # defaults = the measured sweet spot: 8128-entry drains (the knee
    # moved right once the full registry gather died — round-3 peaked at
    # 2040 entries, round 4 at >8k)
    inst = int(sys.argv[1]) if len(sys.argv) > 1 else 2
    groups = int(sys.argv[2]) if len(sys.argv) > 2 else 127
    aggs = int(sys.argv[3]) if len(sys.argv) > 3 else 32
    committee = int(sys.argv[4]) if len(sys.argv) > 4 else 2048
    for rec in run(
        inst, groups, aggs, committee,
        progress=lambda m: print(f"# {m}", file=sys.stderr),
    ):
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
