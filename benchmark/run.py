#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots a real ``BeaconNode`` on the cell's configuration, drives the cell's
traffic mix through the node's own entry points, holds every output to the
plain host reference, and prints as the last line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced).  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.

It refuses to start without a TPU and with another number of chips than the
cell asks for.  ``--rehearse`` runs the same control flow on the CPU at a
tiny size (minimal preset, 256 validators, interpret-mode kernels) and
checks the trace reduction on ``fixtures/tiny.xplane.pb``: a rehearsal,
never a result — it prints ``platform: cpu`` and ``"rehearsal": true``.

One process touches JAX; the host-side workers (``hostside.py``) and the
node's network sidecar stay on the CPU.  See ``README.md``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse
import asyncio
import faulthandler
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TIME_LIMIT_S = 1190  # the contract's limit for a cell's first run in a checkout


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="the measured window (default: BENCHMARK.json run_seconds)")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU, tiny size, interpret mode: control flow only")
    return p.parse_args(argv)


def steer_rehearsal() -> None:
    """The CPU rehearsal steers the package from here, through knobs it
    already has (as ``chip_smoke.py --rehearse`` does).  Before the first
    import of jax or the package."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.update({
        "BLS_RLC_BITS": "16",  # a quarter of the ladder steps
        "BLS_DEVICE_CHAIN_MIN": "4",  # tiny drains still take the device chain
        "BLS_BLOCK_BATCH_MIN_MEMBERS": "1",
        "GRAFT_RESIDENT_EPOCH": "1",  # on by itself only above 16,384 validators
    })


def build_native() -> float:
    """``make -C native`` where ``native/build/`` is missing; both libraries
    must load afterwards — the served path has no pure-Python BLS or KV."""
    t0 = time.perf_counter()
    built = os.path.join(ROOT, "native", "build")
    if not (os.path.exists(os.path.join(built, "libbls381.so"))
            and os.path.exists(os.path.join(built, "libkvstore.so"))):
        subprocess.run(["make", "-C", os.path.join(ROOT, "native")], check=True,
                       stdout=subprocess.DEVNULL)
    from lambda_ethereum_consensus_tpu.crypto.bls import native
    from lambda_ethereum_consensus_tpu.store import kv

    if not (native.available() and native.rlc_available()
            and native.decompress_available() and native.final_exp_available()):
        raise SystemExit("benchmark: native/build/libbls381.so missing or refused")
    if kv._NATIVE is None:
        raise SystemExit("benchmark: native/build/libkvstore.so missing or refused")
    return time.perf_counter() - t0


def check_trace_reduction() -> None:
    """The rehearsal's check of ``tracered`` on the recorded tiny trace."""
    import tracered
    from fixtures.make_tiny_xplane import EXPECTED

    got = tracered.reduce_trace(os.path.join(HERE, "fixtures", "tiny.xplane.pb"))
    us = lambda rows: {k: round(v * 1e6, 3) for k, v in rows}  # noqa: E731
    assert round(got["busy_s"] * 1e6, 3) == EXPECTED["busy_us"], got
    assert us(got["ops"]) == EXPECTED["ops_us"], got["ops"]
    assert us(got["gaps"]) == EXPECTED["gaps_us"], got["gaps"]
    assert us(got["modules"]) == EXPECTED["modules_us"], got["modules"]
    idle = 1 - got["busy_s"] / (got["window_ns"] / 1e9)
    assert abs(idle - 0.45) < 1e-9, idle


def main(argv=None) -> int:
    args = parse_args(argv)
    faulthandler.dump_traceback_later(TIME_LIMIT_S, exit=True)
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
    except OSError as e:
        print(f"benchmark: no BENCHMARK.json above this script: {e}", file=sys.stderr)
        return 3
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"benchmark: no cell {args.workload!r}; BENCHMARK.json has "
              f"{[w['name'] for w in bench['workloads']]}", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    if args.rehearse:
        steer_rehearsal()
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    # the sidecar child imports the package too, whatever the cwd
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    try:
        import lambda_ethereum_consensus_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not beside the benchmark: {e}", file=sys.stderr)
        return 3
    from common import BenchFailure, load_json, note

    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    mix = load_json("traffic", cell["traffic"] + ".json")

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"benchmark: no TPU ({device}); --rehearse runs the CPU rehearsal, "
              "which is not a result", file=sys.stderr)
        return 2
    if not args.rehearse and device["count"] != int(cell["chips"]):
        print(f"benchmark: {device['count']} chip(s) here, the cell asks for "
              f"{cell['chips']}", file=sys.stderr)
        return 2
    peaks = load_json("peaks.json")
    if not args.rehearse and device["kind"] not in peaks:
        print(f"benchmark: device kind {device['kind']!r} is not in peaks.json",
              file=sys.stderr)
        return 2

    from lambda_ethereum_consensus_tpu.utils import env as env_mod

    if args.rehearse:
        env_mod._TPU_BACKEND = True  # take the TPU routing branches on the CPU
        check_trace_reduction()
    # where JAX_COMPILATION_CACHE_DIR says, else fixed paths in the checkout;
    # every program is kept, however quickly it compiled, so that only the
    # first run of a checkout compiles
    jax_cache = env_mod.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import logging

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    note(cell=cell["name"], config=cell["config"], traffic=cell["traffic"],
         seed=args.seed, seconds=args.seconds, trace=args.trace,
         rehearsal=args.rehearse, device=device, jax=jax.__version__,
         jax_cache_dir=jax_cache, aot_cache_dir=env_mod.compile_cache_dirs()[1],
         cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         native_build_s=build_native())

    import session

    try:
        result = asyncio.run(session.run(args, bench, cell, cfg, mix, T_PROCESS))
    except BenchFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
                  "device": {**device, "memory_peak_bytes": 0}, "why": str(e),
                  "compared": getattr(e, "compared", {})}
    # each number compared beside its limit: the last lines of standard error
    for name, (value, limit) in result.get("compared", {}).items():
        print(f"benchmark: compared {name} = {value} (limit {limit})", file=sys.stderr)
    print(f"benchmark: correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # os._exit: a failed run may leave the node's threads behind; the line
    # must not wait on them (children are stopped in session.run's finally)
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
    os._exit(code)
