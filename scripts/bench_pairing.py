"""Device pairing benchmark: batched Miller loops + product checks.

Measures the ops/bls_pairing path (BASELINE.md scenario 3 shape: one
RLC pairing-product check over many pairs) against the native C++
lockstep Miller loop — the host baseline standing in for the reference's
blst-backed bls_nif (ref: native/bls_nif/src/lib.rs).

Usage: python scripts/bench_pairing.py [batch ...]
       python scripts/bench_pairing.py --devices N [batch ...]

``--devices N`` runs the MESH-SHARDED plane instead (round 11): each
batch becomes one RLC check whose ladders, group sums, Miller loops and
Fq12 combine are dealt over an N-device ``dp`` mesh
(ops/bls_shard.sharded_chain_verify — the serving path's multi-device
implementation), with verdict correctness asserted per dispatch.  The
caller (bench.py's sharded stage) is responsible for pointing the
process at a live mesh or a virtual ``--xla_force_host_platform_
device_count`` CPU mesh; this script only refuses to run on a mesh
smaller than N.  Prints one JSON line per batch size plus a
``sharded_pairing_pairs_per_sec`` summary line.
"""

from __future__ import annotations

import json
import os
import secrets
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from lambda_ethereum_consensus_tpu.crypto.bls import curve as C, native
from lambda_ethereum_consensus_tpu.ops import bls_pairing as DP
from lambda_ethereum_consensus_tpu.utils.env import enable_compile_cache

enable_compile_cache()


def make_check(n: int):
    """A valid n+1-pair product: sum_i e(a_i P, Q) * e(-(sum a_i) P, Q) = 1."""
    coeffs = [secrets.randbits(96) for _ in range(n)]
    pairs = [
        (C.g1.multiply_raw(C.G1_GENERATOR, a), C.G2_GENERATOR) for a in coeffs
    ]
    total = sum(coeffs)
    pairs.append(
        (C.g1.affine_neg(C.g1.multiply_raw(C.G1_GENERATOR, total)), C.G2_GENERATOR)
    )
    return pairs


def _sharded_check(n: int, coeff_bits: int):
    """One valid RLC check with ``n`` entries over two messages —
    entries ``(pk_i, sig_i, coeff_i)`` with ``pk_i = sk_i * G1`` and
    ``sig_i = sk_i * H_g`` so the pairing product collapses to one."""
    hs = [C.g2.multiply_raw(C.G2_GENERATOR, 7 + i) for i in range(2)]
    entries, gids = [], []
    for i in range(n):
        sk = secrets.randbits(64) | 1
        g = i % 2
        entries.append(
            (
                C.g1.multiply_raw(C.G1_GENERATOR, sk),
                C.g2.multiply_raw(hs[g], sk),
                secrets.randbits(coeff_bits) | 1,
            )
        )
        gids.append(g)
    return (entries, hs, gids)


def main_sharded(n_devices: int, batches: list[int]) -> None:
    """Sharded RLC verify throughput on the mesh.

    Rates are ENTRIES per second — one RLC entry (pk, sig, coeff)
    through the whole sharded verify (ladders + group sums + Miller +
    combine + tail).  Deliberately NOT 'pairs/s': an n-entry check runs
    only #groups+1 Miller pairs, so entries/s is the unit comparable to
    the aggregate-verification headline, not to the single-device
    pairing lines above.  On a live TPU mesh the largest batch also
    reports ``multichip_aggregate_verifications_per_sec`` — the sharded
    plane at the aggregate-channel shape (host-packed points; no
    committee-cache machinery, unlike bench_chain's cached drain).
    """
    import jax

    from lambda_ethereum_consensus_tpu.crypto.bls.batch import _COEFF_BITS
    from lambda_ethereum_consensus_tpu.ops.bls_shard import sharded_chain_verify

    live = len(jax.devices())
    if live < n_devices:
        raise SystemExit(
            f"--devices {n_devices}: backend exposes only {live} device(s); "
            "launcher must pin a virtual CPU mesh "
            "(--xla_force_host_platform_device_count)"
        )
    on_tpu = jax.default_backend() == "tpu"
    if not batches:
        # one shape on the virtual CPU mesh, chosen to land in the SAME
        # bl=8 padded bucket the dryrun/mesh tests use: every distinct
        # padded batch compiles its own shard_map ladder program
        # (minutes each on XLA CPU).  The TPU path AOT-caches and can
        # afford two real sizes.
        batches = [512, 2048] if on_tpu else [48]
    # the DEPLOYED coefficient width (BLS_RLC_BITS), so the TPU number
    # is the production check; the virtual-mesh validation launcher
    # (bench.py) narrows it to reuse the dryrun-warmed ladder shapes
    bits = _COEFF_BITS
    best = 0.0
    for n in batches:
        check = _sharded_check(n, bits)
        assert sharded_chain_verify([check], coeff_bits=bits)[0]  # compile
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            assert sharded_chain_verify([check], coeff_bits=bits)[0]
        dt = (time.perf_counter() - t0) / iters
        rate = n / dt
        best = max(best, rate)
        print(
            json.dumps(
                {
                    "metric": "sharded_verify_check",
                    "entries": n,
                    "n_devices": n_devices,
                    "entries_per_s": round(rate, 1),
                    "sharded_ms": round(dt * 1e3, 1),
                    "backend": jax.default_backend(),
                }
            ),
            flush=True,
        )
    print(
        json.dumps(
            {
                "metric": "sharded_verify_entries_per_sec",
                "value": round(best, 1),
                "unit": "entries/s",
                "n_devices": n_devices,
                "backend": jax.default_backend(),
            }
        ),
        flush=True,
    )
    if on_tpu:
        # the multichip headline, measured through the ACTUAL sharded
        # plane (bench_chain's cached drain never reads BLS_SHARD — a
        # relabeled single-device number is exactly what this line must
        # never be)
        print(
            json.dumps(
                {
                    "metric": "multichip_aggregate_verifications_per_sec",
                    "value": round(best, 1),
                    "unit": "aggregate verifications/s",
                    "n_devices": n_devices,
                    "body": "sharded RLC verify, host-packed points "
                            "(no committee-cache correction)",
                }
            ),
            flush=True,
        )


def main() -> None:
    argv = sys.argv[1:]
    if argv and argv[0] == "--devices":
        main_sharded(int(argv[1]), [int(a) for a in argv[2:]])
        return
    batches = [int(a) for a in sys.argv[1:]] or [32, 128, 512]
    for n in batches:
        pairs = make_check(n - 1)  # n pairs total
        ok = DP.pairing_product_is_one(pairs)  # compile
        assert ok
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            assert DP.pairing_product_is_one(pairs)
        dt = (time.perf_counter() - t0) / iters
        dev_rate = n / dt

        nat_rate = None
        if native.available():
            t0 = time.perf_counter()
            assert native.pairing_check(pairs)
            nat_dt = time.perf_counter() - t0
            nat_rate = n / nat_dt
        print(
            json.dumps(
                {
                    "metric": "pairing_product_check",
                    "batch": n,
                    "device_pairs_per_s": round(dev_rate, 1),
                    "device_ms": round(dt * 1e3, 1),
                    "native_pairs_per_s": round(nat_rate, 1) if nat_rate else None,
                    "backend": jax.default_backend(),
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
