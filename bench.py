"""Round benchmark.

Prints one JSON line per metric; the LAST line is the headline:

1. ``ssz_merkle_node_hashes_per_sec`` — SHA-256 Merkle-node hashing, the
   primitive under ``Ssz.hash_tree_root`` (ref: native/ssz_nif tree_hash
   crate); vs single-thread host hashlib.
2. ``chain_verify_smoke`` — on-chip valid/invalid/empty verdicts from the
   chained verify, certifying hardware correctness each round.
3. ``aggregate_bls_verifications_per_sec`` — the BASELINE.json north
   star (scenario 3: attestations x 2048-validator committees through
   the chained device verify; scripts/bench_chain.py).

EVERY stage runs in a guarded subprocess under one shared contract
(round-5 advisor: an unguarded in-process device dispatch on a dead
device hung the whole run at rc=124 with zero evidence):

- a per-stage wall-clock budget (env-overridable), each CLAMPED at
  launch to what remains of the driver-level total budget
  (``BENCH_TOTAL_BUDGET_S``, default 7000 s): nominal budgets are SSZ
  600 + mainnet 1500 + ingest 1500 + boot 600 + registry-planes 300 +
  telemetry 120 + pipeline 120 + trace 60 + sharded mesh 900 +
  witness 300 + duties 300 + api 120 + BLS 2x1200, and when elapsed
  time eats a later stage's slice the stage
  shrinks (or is skipped with a ``truncated: true`` absence record)
  instead of letting the SUM blow past the outer timeout — the
  BENCH_r05 zero-record failure mode;
- honest absence — a stage that times out/crashes still emits its metric
  lines with ``value: null`` and a note, so "broke" is distinguishable
  from "skipped";
- a crash tail — the last stderr lines land in the note.

The BLS stage additionally retries: compiles and measurement happen in
ONE process, and every compiled program is AOT-serialized to
``.aot_cache`` (ops/aot.py) as it lands — so a timed-out cold attempt
still makes progress, the retry resumes from the saved executables, and
any later run (this driver, the next round) starts warm in seconds.  On
total failure the SSZ line stays the headline.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# ---- driver-level total budget (round 11 / VERDICT r5 next #1a) --------
#
# BENCH_r05 was rc 124 with ZERO records: per-stage budgets existed but
# their sum exceeded the driver's outer timeout, so the driver killed the
# run mid-stage with nothing flushed.  Now every stage budget is clamped
# to the time REMAINING under BENCH_TOTAL_BUDGET_S (default 7000 s —
# deliberately inside the driver's ~2 h wall); a stage that finds the
# budget exhausted emits its honest-absence records with
# ``truncated: true`` instead of launching, so every round records
# *something* for every metric before the outer timeout can fire.

_T0 = time.monotonic()
_TRUNCATED: list[str] = []  # stages skipped by the total-budget guard
_EMITTED: list[dict] = []  # every record printed this run (self-check)


def _emit(rec: dict) -> None:
    """Print one artifact record AND remember it for the end-of-run
    self-check (rc-124/BENCH_r05: an artifact must never again end the
    round empty without the run itself saying so)."""
    _EMITTED.append(rec)
    print(json.dumps(rec), flush=True)


# ---- artifact self-check (round 12 satellite) ---------------------------
#
# The per-stage metric inventory, gated the same way main() gates the
# stages: validation demands, for every metric a run SHOULD have
# produced, either a result (value != null) or an explicit
# ``truncated: true`` absence record.  A crashed stage's honest-absence
# record (value null + note) deliberately FAILS validation — the gate's
# job is "did this round record a number", not "did it explain why not".

_STAGE_METRICS: tuple[tuple[str | None, tuple[str, ...]], ...] = (
    (None, ("ssz_merkle_node_hashes_per_sec",)),
    ("BENCH_NO_MAINNET", (
        "mainnet_state_root_warm_s",
        "mainnet_state_root_incremental_slot_s",
        "epoch_boundary_root_s",
        "capella_replay_blocks_per_sec",
    )),
    ("BENCH_NO_INGEST", (
        "node_ingest_aggregate_verifications_per_sec",
        "node_first_verify_s",
    )),
    ("BENCH_NO_PLANES", (
        "registry_planes_resident_bytes",
        "registry_context_rebuild_s",
    )),
    ("BENCH_NO_PIPELINE", (
        "pipeline_overload_block_p95_ms",
        "pipeline_overload_shed_lowest_frac",
        "pipeline_coalesce_batch_gain",
        "pipeline_sched_overhead_us_per_item",
    )),
    ("BENCH_NO_TELEMETRY", (
        "telemetry_span_overhead_pct",
        "telemetry_noop_overhead_pct",
    )),
    ("BENCH_NO_TRACE", (
        "trace_overhead_pct",
        "trace_noop_overhead_pct",
    )),
    ("BENCH_NO_FORENSICS", (
        "forensics_overhead_pct",
        "forensics_noop_overhead_pct",
    )),
    ("BENCH_NO_SHARD", ("sharded_verify_entries_per_sec",)),
    ("BENCH_NO_STATE_SHARD", (
        "sharded_epoch_validators_per_sec",
        "sharded_state_bytes_per_device",
    )),
    ("BENCH_NO_WITNESS", ("witness_verifications_per_sec",)),
    ("BENCH_NO_KZG", ("kzg_blob_verifications_per_sec",)),
    ("BENCH_NO_DUTIES", (
        "duty_signatures_per_sec",
        "duties_met_per_epoch",
    )),
    ("BENCH_NO_API", (
        "api_requests_per_sec",
        "api_cache_hit_ratio",
        "api_coalesce_mean_batch",
    )),
    (None, ("aggregate_bls_verifications_per_sec",)),
)


def _disabled_stage_gates(env=None) -> list[str]:
    """The BENCH_NO_* knobs active in ``env`` — recorded into the run's
    first artifact line so validation can judge the artifact by the
    knobs the PRODUCING run honored, not the validator's shell."""
    env = os.environ if env is None else env
    return sorted(
        gate for gate, _metrics in _STAGE_METRICS
        if gate is not None and env.get(gate)
    )


def required_metrics(env=None) -> tuple[str, ...]:
    """Every metric the given env's stage gating says a run must record
    (``env`` defaults to the validator's shell — callers with a better
    source of truth, like the artifact's own recorded knobs, pass it)."""
    env = os.environ if env is None else env
    out: list[str] = []
    for gate, metrics in _STAGE_METRICS:
        if gate is None or not env.get(gate):
            out.extend(metrics)
    return tuple(out)


def _artifact_env(records) -> dict | None:
    """The producing run's stage knobs, if any record carried them
    (``disabled_stages`` on the budget line since round 12); ``None``
    means an older artifact — fall back to the validator's shell."""
    for rec in records:
        if isinstance(rec, dict) and isinstance(rec.get("disabled_stages"), list):
            return {gate: "1" for gate in rec["disabled_stages"]}
    return None


def validate_records(records, required) -> list[str]:
    """Problems with one artifact's record list (empty list = valid).

    A surviving ``bench_artifact_selfcheck`` record with ``ok: true``
    vouches for the whole run: the in-run check saw the FULL record
    stream, while a driver-wrapper artifact keeps only a bounded stdout
    tail — early-stage records scroll out of it on a long healthy run,
    and judging those as "missing" would fail exactly the rounds that
    recorded the most.  A failed or absent selfcheck falls through to
    the full per-metric audit."""
    metric_recs: dict[str, list[dict]] = {}
    for rec in records:
        if isinstance(rec, dict) and isinstance(rec.get("metric"), str):
            metric_recs.setdefault(rec["metric"], []).append(rec)
    if not metric_recs:
        return ["artifact contains no metric records at all"]
    for rec in metric_recs.get("bench_artifact_selfcheck", ()):
        if rec.get("ok") is True:
            # the vouch covers only records PRINTED BEFORE the selfcheck
            # line — the records it listed as pending (the headline,
            # emitted after it) must still be audited, or a run killed
            # between the two flushes would validate green while missing
            # the round's primary metric
            still_pending = set(rec.get("pending") or ())
            required = [m for m in required if m in still_pending]
            break
    problems = []
    for name in required:
        recs = metric_recs.get(name)
        if not recs:
            problems.append(f"stage metric {name!r} missing from artifact")
            continue
        if not any(
            rec.get("value") is not None or rec.get("truncated") is True
            for rec in recs
        ):
            note = next((r.get("note") for r in recs if r.get("note")), None)
            suffix = f" (note: {note})" if note else ""
            problems.append(
                f"stage metric {name!r} has neither a result nor a "
                f"truncated:true absence record{suffix}"
            )
    return problems


def _wrapper_problems(path: str) -> list[str]:
    """Driver-wrapper sanity beyond the record audit: an artifact whose
    wrapper carries ``parsed: null`` is the BENCH_r05/MULTICHIP_r05
    rc-124 signature — the run was killed before the driver parsed a
    single record — and must fail validation even when the bounded
    ``tail`` happens to hold stray JSON lines."""
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return []  # not a wrapper artifact (raw JSON-lines etc.)
    if isinstance(data, dict) and "parsed" in data and data["parsed"] is None:
        rc = data.get("rc")
        return [
            f"driver wrapper has parsed: null (rc={rc}) — the run recorded "
            "nothing the driver could parse"
        ]
    return []


def _artifact_records(path: str) -> list[dict]:
    """Parse a bench artifact: the driver's wrapper JSON (``tail`` holds
    the run's stdout lines, ``parsed`` sometimes the last record), a
    plain JSON list, or raw JSON-lines output from ``python bench.py``."""
    with open(path) as fh:
        text = fh.read()
    records: list[dict] = []

    def _scan_lines(blob: str) -> None:
        for line in blob.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                records.append(rec)

    try:
        data = json.loads(text)
    except json.JSONDecodeError:
        data = None
    if isinstance(data, dict) and ("tail" in data or "parsed" in data):
        _scan_lines(data.get("tail") or "")
        parsed = data.get("parsed")
        if isinstance(parsed, dict):
            records.append(parsed)
        elif isinstance(parsed, list):
            records.extend(r for r in parsed if isinstance(r, dict))
    elif isinstance(data, list):
        records.extend(r for r in data if isinstance(r, dict))
    elif isinstance(data, dict):
        records.append(data)
    else:
        _scan_lines(text)
    return records


def validate_main(path: str) -> int:
    """``python bench.py --validate ARTIFACT`` — the ``make
    bench-validate`` entry point.  Exit 0 iff the artifact is non-empty
    and every stage required under the current BENCH_NO_* env has a
    result or a truncated absence record."""
    try:
        records = _artifact_records(path)
    except OSError as e:
        print(f"bench-validate: cannot read {path}: {e}", file=sys.stderr)
        return 2
    required = required_metrics(env=_artifact_env(records))
    problems = validate_records(records, required)
    problems.extend(_wrapper_problems(path))
    print(json.dumps({
        "metric": "bench_artifact_validation",
        "artifact": path,
        "records": len(records),
        "required": len(required),
        "value": len(problems),
        "unit": "problems",
        "ok": not problems,
    }))
    for p in problems:
        print(f"bench-validate: {p}", file=sys.stderr)
    return 1 if problems else 0


def _total_budget_s() -> float:
    return float(os.environ.get("BENCH_TOTAL_BUDGET_S", "7000"))


def _remaining_s(reserve_s: float = 30.0) -> float:
    """Wall clock left under the total budget, minus a reserve that
    keeps the final flush (and the BLS record ordering) off the cliff."""
    return _total_budget_s() - (time.monotonic() - _T0) - reserve_s


def _clamp_budget(budget_s: float) -> float:
    return max(0.0, min(float(budget_s), _remaining_s()))


def _bench_device(blocks: np.ndarray, iters: int = 20) -> float:
    import jax
    import jax.numpy as jnp

    from lambda_ethereum_consensus_tpu.ops.sha256 import (
        hash_blocks_jnp,
        hash_blocks_pallas,
        _bucket_rows,
        _to_word_planes,
    )

    n = blocks.shape[0]
    if jax.default_backend() == "tpu":
        planes = jnp.asarray(_to_word_planes(blocks, _bucket_rows(n)))
        fn = lambda: hash_blocks_pallas(planes)
    else:
        words = jnp.asarray(np.ascontiguousarray(blocks).view(">u4").astype(np.uint32))
        fn = lambda: hash_blocks_jnp(words)

    fn().block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    out.block_until_ready()
    dt = time.perf_counter() - t0
    return n * iters / dt


def _bench_host(blocks: np.ndarray, budget_s: float = 2.0) -> float:
    import hashlib

    n = min(blocks.shape[0], 4096)
    raw = [bytes(b) for b in blocks[:n]]
    done = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < budget_s:
        for b in raw:
            hashlib.sha256(b).digest()
        done += n
    dt = time.perf_counter() - t0
    return done / dt


def _bls_attempt(budget_s: float) -> tuple[list[dict], str | None]:
    """One subprocess run of the chain bench; (records, failure-note)."""
    budget_s = _clamp_budget(budget_s)
    if budget_s <= 1.0:
        if "bench_chain.py" not in _TRUNCATED:  # once across retries
            _TRUNCATED.append("bench_chain.py")
        return [], "skipped: total bench budget exhausted"
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(here, ".jax_cache"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "5")
    argv = [sys.executable, os.path.join(here, "scripts", "bench_chain.py")]
    scenario = os.environ.get("BENCH_BLS_SCENARIO")
    if scenario:
        argv += scenario.split()
    try:
        out = subprocess.run(
            argv, capture_output=True, text=True, timeout=budget_s, env=env, cwd=here
        )
    except subprocess.TimeoutExpired:
        return [], f"attempt exceeded its {budget_s:.0f}s budget"
    if out.returncode != 0:
        tail = (out.stderr or "").strip().splitlines()[-3:]
        return [], "crashed: " + " | ".join(tail)
    recs = []
    for line in out.stdout.strip().splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            recs.append(rec)
    if not any(r["metric"] == "aggregate_bls_verifications_per_sec" for r in recs):
        return recs, "produced no metric line"
    return recs, None


def _bench_bls() -> tuple[list[dict], str | None]:
    """Run the chain bench with retries: a cold-cache timeout still saved
    its compiled programs to .aot_cache, so the retry resumes from them
    instead of starting over (the round-2 failure mode was one attempt
    with no resume)."""
    budget = float(os.environ.get("BENCH_BLS_BUDGET_S", "1200"))
    attempts = int(os.environ.get("BENCH_BLS_ATTEMPTS", "2"))
    notes = []
    recs: list[dict] = []
    for i in range(attempts):
        recs, err = _bls_attempt(budget)
        if err is None:
            return recs, None
        notes.append(f"attempt {i + 1}: {err}")
    # keep the last attempt's partial records (e.g. the smoke verdicts
    # from a run that died before the throughput line)
    return recs, "; ".join(notes) or "disabled (BENCH_BLS_ATTEMPTS=0)"


def _bench_mainnet_root(budget_s: float | None = None) -> list[dict]:
    """Full + incremental 1M-validator BeaconState roots through the SSZ
    engine + device hash backend (VERDICT r2 #6: the product path, not
    the raw kernel; r3 next #2: the incremental per-slot root).  Routed
    through the shared stage guard (budget / honest absence / crash
    tail) — this was the last stage that swallowed its crash tail."""
    if budget_s is None:
        budget_s = float(os.environ.get("BENCH_MAINNET_BUDGET_S", "1500"))
    renames = {
        "beacon_state_hash_tree_root_warm": "mainnet_state_root_warm_s",
        "beacon_state_root_incremental_slot": "mainnet_state_root_incremental_slot_s",
        "epoch_boundary_root": "epoch_boundary_root_s",
        "capella_replay_blocks_per_sec": "capella_replay_blocks_per_sec",
    }
    units = {m: "s" for m in renames}
    units["capella_replay_blocks_per_sec"] = "blocks/s"
    # the per-block progress stream rides along so a stage timeout still
    # yields partial replay numbers (round-13 satellite: the rc-124
    # BENCH_r05 empty-artifact mode must be unreachable for this stage)
    recs = _bench_script(
        "bench_mainnet.py",
        tuple(renames) + ("capella_replay_progress",),
        budget_s,
        argv_extra=("1000000", "--device"), units=units,
    )
    # only REAL per-block records count as progress — the stage guard's
    # own absence record for the progress metric must not masquerade as
    # evidence (it would replace the headline's crash-tail note with a
    # fabricated "interrupted replay" story)
    progress = [
        r for r in recs
        if r.get("metric") == "capella_replay_progress"
        and r.get("block") is not None
    ]
    headline = next(
        (r for r in recs
         if r.get("metric") == "capella_replay_blocks_per_sec"), None
    )
    if progress and (headline is None or headline.get("value") is None):
        # the run died mid-replay: promote the last progress line to a
        # PARTIAL headline instead of an absence record
        last = max(progress, key=lambda r: r.get("block", 0))
        partial = {
            "metric": "capella_replay_blocks_per_sec",
            "value": last.get("cum_blocks_per_sec"),
            "unit": "blocks/s",
            "partial": True,
            "blocks_completed": last.get("block"),
            "n_blocks": last.get("n_blocks"),
            "note": "replay interrupted; rate from per-block progress stream",
        }
        recs = [r for r in recs
                if r.get("metric") != "capella_replay_blocks_per_sec"]
        recs.append(partial)
    # a run that died before the replay has no progress lines: drop the
    # guard's synthetic absence record for the progress stream itself
    # (the headline's absence record already says the stage broke)
    recs = [
        r for r in recs
        if not (r.get("metric") == "capella_replay_progress"
                and r.get("value") is None)
    ]
    for rec in recs:
        rec["metric"] = renames.get(rec["metric"], rec["metric"])
        if rec.get("value") is not None:
            rec["vs_baseline"] = rec.pop("slot_budget_frac", None)
    return recs


def _absent_records(
    name: str, metrics: tuple[str, ...], note: str,
    units: dict | None = None, truncated: bool = False,
) -> list[dict]:
    """Honest-absence records for a whole stage (crash, timeout, or the
    total-budget guard refusing to launch it)."""
    recs = []
    for m in metrics:
        rec = {"metric": m, "value": None, "note": f"{name}: {note}"}
        if truncated:
            rec["truncated"] = True
        if units and m in units:
            rec["unit"] = units[m]
        recs.append(rec)
    return recs


def _bench_script(
    name: str,
    metrics: tuple[str, ...],
    budget_s: float,
    argv_extra=(),
    units: dict | None = None,
    env_extra: dict | None = None,
) -> list[dict]:
    """The shared stage guard: run a bench script in a subprocess under a
    wall-clock budget — clamped to the driver-level total budget — keep
    only its metric lines, and emit per-metric honest-absence records
    (with the metric's ``unit`` from ``units`` and the crash tail in the
    note) for anything it failed to produce."""
    budget_s = _clamp_budget(budget_s)
    if budget_s <= 1.0:
        _TRUNCATED.append(name)
        return _absent_records(
            name, metrics,
            "skipped: total bench budget exhausted before this stage",
            units, truncated=True,
        )
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(here, ".jax_cache"))
    argv = [sys.executable, os.path.join(here, "scripts", name), *argv_extra]
    fail_note = None
    try:
        out = subprocess.run(
            argv, capture_output=True, text=True, timeout=budget_s, env=env, cwd=here
        )
        stdout = out.stdout or ""
        if out.returncode != 0:
            tail = (out.stderr or "").strip().splitlines()[-3:]
            fail_note = "crashed: " + " | ".join(tail)
    except subprocess.TimeoutExpired as e:
        stdout = e.stdout or ""
        if isinstance(stdout, bytes):
            stdout = stdout.decode(errors="replace")
        fail_note = f"exceeded its {budget_s:.0f}s budget"
    recs = []
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and rec.get("metric") in metrics:
            recs.append(rec)
    got = {r["metric"] for r in recs}
    for m in metrics:
        if m not in got:
            rec = {
                "metric": m, "value": None,
                "note": f"{name}: {fail_note or 'produced no such line'}",
            }
            if units and m in units:
                rec["unit"] = units[m]
            recs.append(rec)
    return recs


def _ssz_line_guarded(budget_s: float | None = None) -> dict:
    """The SSZ kernel micro-bench in a subprocess: a dead device
    must produce an honest-absence record, not hang the whole bench run
    at its first in-process dispatch."""
    if budget_s is None:
        budget_s = float(os.environ.get("BENCH_SSZ_BUDGET_S", "600"))
    budget_s = _clamp_budget(budget_s)
    if budget_s <= 1.0:
        _TRUNCATED.append("ssz kernel")
        return {
            "metric": "ssz_merkle_node_hashes_per_sec",
            "value": None,
            "unit": "hashes/s",
            "truncated": True,
            "note": "skipped: total bench budget exhausted",
        }
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(here, ".jax_cache"))
    code = (
        "import json, numpy as np, bench;"
        "rng = np.random.default_rng(0);"
        "blocks = rng.integers(0, 256, size=(1 << 17, 64), dtype=np.uint8);"
        "d = bench._bench_device(blocks); h = bench._bench_host(blocks);"
        "print(json.dumps({'d': d, 'h': h}))"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=budget_s, cwd=here, env=env,
        )
        if out.returncode != 0:
            tail = (out.stderr or "").strip().splitlines()[-3:]
            return {
                "metric": "ssz_merkle_node_hashes_per_sec",
                "value": None,
                "unit": "hashes/s",
                "note": "kernel bench crashed: " + " | ".join(tail),
            }
        payload = json.loads(out.stdout.strip().splitlines()[-1])
        return {
            "metric": "ssz_merkle_node_hashes_per_sec",
            "value": round(payload["d"], 1),
            "unit": "hashes/s",
            "vs_baseline": round(payload["d"] / payload["h"], 2),
        }
    except subprocess.TimeoutExpired:
        return {
            "metric": "ssz_merkle_node_hashes_per_sec",
            "value": None,
            "unit": "hashes/s",
            "note": f"device dispatch exceeded {budget_s:.0f}s (device down?)",
        }
    except Exception as e:
        return {
            "metric": "ssz_merkle_node_hashes_per_sec",
            "value": None,
            "unit": "hashes/s",
            "note": f"kernel bench failed: {type(e).__name__}: {e}",
        }


def _bench_sharded_stage() -> list[dict]:
    """The multichip bench stage (round 11): the sharded pairing/verify
    plane on an 8-way mesh, hang-proof by construction — the backend is
    probed in a budgeted subprocess (60 s default), a too-small or dead
    backend falls back to the virtual CPU mesh (same programs, honest
    ``backend`` note), and the stage itself runs under the shared
    subprocess guard so a wedged device costs one sub-budget, not
    the round."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import __graft_entry__ as graft

    mesh_n = int(os.environ.get("BENCH_SHARD_DEVICES", "8"))
    budget = float(os.environ.get("BENCH_SHARD_BUDGET_S", "900"))
    units = {
        "sharded_verify_entries_per_sec": "entries/s",
        "multichip_aggregate_verifications_per_sec": "aggregate verifications/s",
    }
    n_live = graft._probe_live_devices()  # subprocess: this parent stays off jax
    live_mesh = n_live >= mesh_n
    # BLS_SHARD_DRAIN rides along so a live-mesh stage measures the env
    # a sharded NODE would run; bench_pairing itself calls the sharded
    # ops directly and emits the multichip aggregate line only on a
    # real TPU mesh (the sharded plane, not a relabeled single-device
    # number — bench_chain's cached drain never reads these flags)
    env_extra = {"BLS_SHARD": "1", "BLS_SHARD_DRAIN": "1"}
    metrics = ("sharded_verify_entries_per_sec",)
    if live_mesh:
        metrics += ("multichip_aggregate_verifications_per_sec",)
    else:
        env_extra = graft.virtual_cpu_env(mesh_n, dict(os.environ))
        env_extra["BLS_SHARD"] = "1"
        # validation run, not a throughput record: narrow the RLC width
        # to the dryrun-warmed ladder shapes so the virtual mesh can
        # finish inside the stage budget instead of recompiling a fresh
        # 64-bit ladder program for minutes
        env_extra.setdefault("BLS_RLC_BITS", "16")
    recs = _bench_script(
        "bench_pairing.py",
        metrics,
        budget,
        argv_extra=("--devices", str(mesh_n)),
        units=units,
        env_extra=env_extra,
    )
    for rec in recs:
        rec.setdefault("backend_devices", n_live)
        rec.setdefault("mesh", "live" if live_mesh else "virtual-cpu")
    if not live_mesh:
        recs.append({
            "metric": "multichip_aggregate_verifications_per_sec",
            "value": None,
            "unit": units["multichip_aggregate_verifications_per_sec"],
            "note": (
                f"no live {mesh_n}-device backend "
                f"({n_live} device(s) probed); sharded plane validated "
                "on the virtual CPU mesh instead"
            ),
        })
    return recs


def _bench_state_shard_stage() -> list[dict]:
    """The mesh-sharded state residency stage (round 21): the full
    resident epoch kernel sequence over {1M, 10M} synthetic validators
    with every hot column sharded across an 8-way mesh by the
    partition-rule table.  Probe-guarded like the crypto-plane stage: a
    too-small or dead backend falls back to the virtual CPU mesh (same
    sharded programs, honest ``mesh`` note), and the script refuses to
    relabel an unsharded run — it exits nonzero unless the columns are
    actually spread over the full mesh and bit-exact vs the
    single-device kernel path."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import __graft_entry__ as graft

    mesh_n = int(os.environ.get("BENCH_STATE_SHARD_DEVICES", "8"))
    budget = float(os.environ.get("BENCH_STATE_SHARD_BUDGET_S", "600"))
    units = {
        "sharded_epoch_validators_per_sec": "validators/s",
        "sharded_state_bytes_per_device": "bytes",
    }
    metrics = tuple(units)
    n_live = graft._probe_live_devices()  # subprocess: this parent stays off jax
    live_mesh = n_live >= mesh_n
    env_extra = {"GRAFT_STATE_SHARD": "1"}
    if not live_mesh:
        env_extra = graft.virtual_cpu_env(mesh_n, dict(os.environ))
        env_extra["GRAFT_STATE_SHARD"] = "1"
    recs = _bench_script(
        "bench_state_shard.py",
        metrics,
        budget,
        argv_extra=("--devices", str(mesh_n)),
        units=units,
        env_extra=env_extra,
    )
    for rec in recs:
        rec.setdefault("backend_devices", n_live)
        rec.setdefault("mesh", "live" if live_mesh else "virtual-cpu")
    return recs


def main() -> None:
    # first evidence within seconds of launch (VERDICT r5 next #1a): the
    # budget line also timestamps the run for the truncation note below
    _emit({
        "metric": "bench_total_budget_s",
        "value": _total_budget_s(),
        "unit": "s",
        # the stage knobs this run honors: validation of the artifact
        # judges coverage by THESE, not by the validating shell's env
        "disabled_stages": _disabled_stage_gates(),
    })
    ssz_line = _ssz_line_guarded()

    if not os.environ.get("BENCH_NO_MAINNET"):
        for rec in _bench_mainnet_root():
            _emit(rec)

    if not os.environ.get("BENCH_NO_INGEST"):
        # node-path throughput (VERDICT r4 next #1) + boot timeline (#6)
        for rec in _bench_script(
            "bench_ingest.py",
            ("node_ingest_aggregate_verifications_per_sec",),
            float(os.environ.get("BENCH_INGEST_BUDGET_S", "1500")),
            units={"node_ingest_aggregate_verifications_per_sec":
                   "aggregate verifications/s"},
        ):
            _emit(rec)
        for rec in _bench_script(
            "bench_boot.py", ("node_first_verify_s",),
            float(os.environ.get("BENCH_BOOT_BUDGET_S", "600")),
            units={"node_first_verify_s": "s"},
        ):
            _emit(rec)

    if not os.environ.get("BENCH_NO_PLANES"):
        # registry-plane sharing: device bytes resident must be flat in
        # the live-context count, rebuilds must skip the registry upload
        for rec in _bench_script(
            "bench_registry_planes.py",
            ("registry_planes_resident_bytes", "registry_context_rebuild_s"),
            float(os.environ.get("BENCH_PLANES_BUDGET_S", "300")),
            units={"registry_planes_resident_bytes": "bytes",
                   "registry_context_rebuild_s": "s"},
        ):
            _emit(rec)

    if not os.environ.get("BENCH_NO_PIPELINE"):
        # ingest scheduler regimes (ISSUE 3): bounded high-priority p95 +
        # lowest-lane-only shedding under overload, deadline coalescing's
        # batch-size gain under light load, scheduler overhead — host-only
        for rec in _bench_script(
            "bench_pipeline.py",
            ("pipeline_overload_block_p95_ms",
             "pipeline_overload_shed_lowest_frac",
             "pipeline_coalesce_batch_gain",
             "pipeline_sched_overhead_us_per_item"),
            float(os.environ.get("BENCH_PIPELINE_BUDGET_S", "120")),
            units={"pipeline_overload_block_p95_ms": "ms",
                   "pipeline_overload_shed_lowest_frac": "fraction",
                   "pipeline_coalesce_batch_gain": "x",
                   "pipeline_sched_overhead_us_per_item": "us/item"},
        ):
            _emit(rec)

    if not os.environ.get("BENCH_NO_TELEMETRY"):
        # span/no-op overhead on the synthetic gossip drain (ISSUE 2:
        # enabled < 3%, TELEMETRY_OFF < 0.5%) — host-only, no device
        for rec in _bench_script(
            "bench_telemetry_overhead.py",
            ("telemetry_span_overhead_pct", "telemetry_noop_overhead_pct"),
            float(os.environ.get("BENCH_TELEMETRY_BUDGET_S", "120")),
            units={"telemetry_span_overhead_pct": "%",
                   "telemetry_noop_overhead_pct": "%"},
        ):
            _emit(rec)

    if not os.environ.get("BENCH_NO_TRACE"):
        # causal-tracing overhead on the same synthetic drain (ISSUE 4:
        # full per-item trace sequence <= 3%, TELEMETRY_OFF unchanged
        # from the PR 2 no-op budget, recorder memory bounded)
        for rec in _bench_script(
            "bench_trace_overhead.py",
            ("trace_overhead_pct", "trace_noop_overhead_pct"),
            float(os.environ.get("BENCH_TRACE_BUDGET_S", "60")),
            units={"trace_overhead_pct": "%",
                   "trace_noop_overhead_pct": "%"},
        ):
            _emit(rec)

    if not os.environ.get("BENCH_NO_FORENSICS"):
        # consensus-forensics overhead on the same synthetic drain
        # (round 24: per-vote/per-batch notes enabled <= 1%,
        # FORENSICS_OFF <= 0.1%)
        for rec in _bench_script(
            "bench_forensics_overhead.py",
            ("forensics_overhead_pct", "forensics_noop_overhead_pct"),
            float(os.environ.get("BENCH_FORENSICS_BUDGET_S", "60")),
            units={"forensics_overhead_pct": "%",
                   "forensics_noop_overhead_pct": "%"},
        ):
            _emit(rec)

    if not os.environ.get("BENCH_NO_SHARD"):
        # sharded crypto plane on the 8-way mesh (probe-guarded; falls
        # back to the virtual CPU mesh when no live multichip backend)
        for rec in _bench_sharded_stage():
            _emit(rec)

    if not os.environ.get("BENCH_NO_STATE_SHARD"):
        # mesh-sharded state residency (round 21): 10M validators'
        # epoch columns resident across the mesh, bit-exact by contract
        for rec in _bench_state_shard_stage():
            _emit(rec)

    if not os.environ.get("BENCH_NO_WITNESS"):
        # stateless witness plane (round 15): batched multiproof
        # verification at the witness_verify buckets; on CPU this
        # certifies the >= 10k proofs/s host-fallback floor, and the
        # VC prototype + proof-generation rates ride along
        for rec in _bench_script(
            "bench_witness.py",
            ("witness_verifications_per_sec",
             "witness_proof_generate_per_sec",
             "witness_proof_bytes",
             "witness_vc_verifications_per_sec"),
            float(os.environ.get("BENCH_WITNESS_BUDGET_S", "300")),
            units={"witness_verifications_per_sec": "proofs/s",
                   "witness_proof_generate_per_sec": "proofs/s",
                   "witness_proof_bytes": "bytes",
                   "witness_vc_verifications_per_sec": "openings/s"},
        ):
            _emit(rec)

    if not os.environ.get("BENCH_NO_KZG"):
        # data-availability plane (round 23): blob-proof verification
        # through da.kzg's batched fold — one RLC pairing check per
        # batch at the registered kzg_msm buckets; the commitment-MSM
        # rate and the fold's gain over per-blob pairings ride along
        for rec in _bench_script(
            "bench_kzg.py",
            ("kzg_blob_verifications_per_sec",
             "kzg_blob_commitments_per_sec",
             "kzg_batch_fold_gain"),
            float(os.environ.get("BENCH_KZG_BUDGET_S", "300")),
            units={"kzg_blob_verifications_per_sec": "blobs/s",
                   "kzg_blob_commitments_per_sec": "blobs/s",
                   "kzg_batch_fold_gain": "x"},
        ):
            _emit(rec)

    if not os.environ.get("BENCH_NO_DUTIES"):
        # validator-duty plane (round 16): batched signing throughput
        # at the duty_sign buckets + a full mainnet-spec epoch of
        # attester/aggregator duties judged against slot-phase
        # deadlines while a gossip-shaped load drains concurrently
        for rec in _bench_script(
            "bench_duties.py",
            ("duty_signatures_per_sec", "duties_met_per_epoch"),
            float(os.environ.get("BENCH_DUTIES_BUDGET_S", "300")),
            units={"duty_signatures_per_sec": "signatures/s",
                   "duties_met_per_epoch": "duties/epoch"},
        ):
            _emit(rec)

    if not os.environ.get("BENCH_NO_API"):
        # serving plane (round 17): mixed GET/witness dispatches/s
        # through the response cache + cross-request verify coalescer
        # (the serve gate's own harness, longer steady-state window)
        for rec in _bench_script(
            "bench_api.py",
            ("api_requests_per_sec", "api_cache_hit_ratio",
             "api_coalesce_mean_batch"),
            float(os.environ.get("BENCH_API_BUDGET_S", "120")),
            units={"api_requests_per_sec": "req/s",
                   "api_cache_hit_ratio": "fraction",
                   "api_coalesce_mean_batch": "proofs/flush"},
        ):
            _emit(rec)

    bls_recs, err = _bench_bls()
    if err is not None:
        # headline stays the SSZ metric; record the failure honestly —
        # with the truncated flag when the total-budget guard (not the
        # bench itself) was the cause, like every other clipped stage
        rec = {"metric": "aggregate_bls_verifications_per_sec",
               "value": None,
               "unit": "aggregate verifications/s",
               "note": f"bls chain bench failed: {err}"}
        if "total bench budget exhausted" in err:
            rec["truncated"] = True
        _emit(rec)
        for rec in bls_recs:  # partial records (e.g. smoke) still count
            _emit(rec)
        if _TRUNCATED:
            _emit(_truncation_record())
        _emit(_selfcheck_record(pending=[ssz_line]))
        _emit(ssz_line)
    else:
        _emit(ssz_line)
        if _TRUNCATED:
            _emit(_truncation_record())
        headline = [
            rec for rec in bls_recs
            if rec["metric"] == "aggregate_bls_verifications_per_sec"
        ]
        for rec in bls_recs:
            if rec["metric"] != "aggregate_bls_verifications_per_sec":
                _emit(rec)
        _emit(_selfcheck_record(pending=headline))
        for rec in headline:
            _emit(rec)


def _selfcheck_record(pending: list[dict]) -> dict:
    """The run's own artifact validation (the same check ``make
    bench-validate`` applies to a saved artifact), emitted second-to-last
    so the headline contract holds.  ``pending`` carries records the
    caller will still print after this line."""
    problems = validate_records(_EMITTED + pending, required_metrics())
    return {
        "metric": "bench_artifact_selfcheck",
        "value": len(problems),
        "unit": "problems",
        "ok": not problems,
        # metrics vouched for but not yet flushed when this line prints:
        # a later validator must still audit THESE from the artifact
        "pending": sorted({
            rec.get("metric") for rec in pending
            if isinstance(rec.get("metric"), str)
        }),
        "note": "; ".join(problems[:6]) or None,
    }


def _truncation_record() -> dict:
    """One summary line naming every stage the total-budget guard cut —
    the ``truncated: true`` note ROADMAP item 2 demands so a clipped
    round is distinguishable from a complete one."""
    return {
        "metric": "bench_truncated",
        "value": len(_TRUNCATED),
        "truncated": True,
        "unit": "stages",
        "note": "total budget clipped: " + ", ".join(_TRUNCATED),
        "elapsed_s": round(time.monotonic() - _T0, 1),
    }


if __name__ == "__main__":
    if "--validate" in sys.argv:
        i = sys.argv.index("--validate")
        if i + 1 >= len(sys.argv):
            print("usage: python bench.py --validate ARTIFACT.json",
                  file=sys.stderr)
            sys.exit(2)
        sys.exit(validate_main(sys.argv[i + 1]))
    main()
