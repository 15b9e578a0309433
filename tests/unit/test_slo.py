"""SLO engine: log-bucket quantile estimation (property-tested against
exact quantiles), budget pass/fail, burn-rate windows, exposition, and
the scripts/slo_check.py gate in both polarities."""

import json
import math
import os
import random
import subprocess
import sys
import time

import pytest

from lambda_ethereum_consensus_tpu.slo import (
    DEFAULT_SLOS,
    SloDef,
    SloEngine,
    estimate_quantile,
    good_fraction,
)
from lambda_ethereum_consensus_tpu.telemetry import DEFAULT_BUCKETS, Metrics

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ----------------------------------------------------- quantile estimation


def _exact_quantile(values, q):
    """The rank convention the bucket walk uses: smallest value whose
    cumulative count reaches q * n."""
    xs = sorted(values)
    rank = max(1, math.ceil(q * len(xs)))
    return xs[rank - 1]


def _hist_of(values, buckets=None):
    m = Metrics(enabled=True)
    if buckets is not None:
        m.register_histogram("x_seconds", buckets)
    for v in values:
        m.observe("x_seconds", v)
    bounds, counts, _sum, _count = m.get_histogram("x_seconds")
    return bounds, counts


def test_quantile_empty_histogram_is_none():
    assert estimate_quantile(DEFAULT_BUCKETS, [0] * (len(DEFAULT_BUCKETS) + 1), 0.95) is None


def test_quantile_exact_on_handcrafted_buckets():
    bounds = (1.0, 2.0, 4.0, 8.0)
    # 10 observations in (2, 4], nothing elsewhere
    counts = [0, 0, 10, 0, 0]
    # p50: target 5 -> halfway through the (2,4] bucket
    assert estimate_quantile(bounds, counts, 0.5) == pytest.approx(3.0)
    # p100-epsilon stays inside the bucket
    assert estimate_quantile(bounds, counts, 0.99) <= 4.0
    # first bucket interpolates from zero
    assert estimate_quantile(bounds, [10, 0, 0, 0, 0], 0.5) == pytest.approx(0.5)


def test_quantile_overflow_bucket_clamps_to_top_bound():
    bounds = (1.0, 2.0)
    counts = [0, 0, 5]  # everything beyond the top bound
    assert estimate_quantile(bounds, counts, 0.9) == 2.0


def test_quantile_monotone_in_q():
    rng = random.Random(5)
    values = [rng.lognormvariate(-4.0, 2.0) for _ in range(2000)]
    bounds, counts = _hist_of(values)
    estimates = [
        estimate_quantile(bounds, counts, q)
        for q in (0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99)
    ]
    assert estimates == sorted(estimates)


@pytest.mark.parametrize("dist", ["uniform", "lognormal", "exponential", "bimodal"])
@pytest.mark.parametrize("q", [0.5, 0.95, 0.99])
def test_quantile_bounded_relative_error_property(dist, q):
    """The estimate lands in the same bucket as the exact sample
    quantile, so with factor-2 geometric bounds the relative error is
    bounded by the bucket ratio: est/true in [1/2, 2]."""
    rng = random.Random(hash((dist, q)) & 0xFFFF)
    n = 5000
    if dist == "uniform":
        values = [rng.uniform(1e-3, 1.0) for _ in range(n)]
    elif dist == "lognormal":
        values = [min(90.0, max(3e-4, rng.lognormvariate(-5.0, 1.5))) for _ in range(n)]
    elif dist == "exponential":
        values = [min(90.0, max(3e-4, rng.expovariate(50.0))) for _ in range(n)]
    else:  # bimodal: fast path + slow tail
        values = [
            rng.uniform(2e-3, 6e-3) if rng.random() < 0.9
            else rng.uniform(0.5, 2.0)
            for _ in range(n)
        ]
    bounds, counts = _hist_of(values)
    est = estimate_quantile(bounds, counts, q)
    true = _exact_quantile(values, q)
    assert est is not None
    ratio = est / true
    assert 1 / 2.0 - 1e-9 <= ratio <= 2.0 + 1e-9, (
        f"{dist} p{q}: estimate {est} vs exact {true} (ratio {ratio:.3f})"
    )


def test_good_fraction_interpolates_and_is_conservative_past_top_bound():
    bounds = (1.0, 2.0, 4.0)
    counts = [4, 0, 4, 2]  # 2 in overflow
    # budget mid-bucket: all of bucket 1, half of bucket 3's (2,4] span
    assert good_fraction(bounds, counts, 3.0) == pytest.approx((4 + 2) / 10)
    # budget above every bound: overflow counts as bad
    assert good_fraction(bounds, counts, 100.0) == pytest.approx(0.8)
    assert good_fraction(bounds, [0, 0, 0, 0], 1.0) == 1.0


# -------------------------------------------------------------- definitions


def test_default_slos_well_formed():
    names = [s.name for s in DEFAULT_SLOS]
    assert len(set(names)) == len(names)
    for s in DEFAULT_SLOS:
        assert 0.0 < s.quantile < 1.0
        assert s.budget > 0
        assert s.family.endswith("_seconds")
        assert s.description


def test_slodef_validation():
    with pytest.raises(ValueError):
        SloDef("x", "x_seconds", 1.5, 1.0)
    with pytest.raises(ValueError):
        SloDef("x", "x_seconds", 0.95, 0.0)
    with pytest.raises(ValueError):
        SloEngine(slos=(
            SloDef("dup", "a_seconds", 0.5, 1.0),
            SloDef("dup", "b_seconds", 0.5, 1.0),
        ))


# ------------------------------------------------------------ pass / fail


def _engine(slos, m):
    return SloEngine(slos=slos, metrics=m)


def test_slo_pass_and_fail_with_violation_structure():
    m = Metrics(enabled=True)
    for _ in range(100):
        m.observe("x_seconds", 0.010)
    eng = _engine((SloDef("x_p95", "x_seconds", 0.95, 1.0),), m)
    report = eng.evaluate()
    assert report["ok"] is True
    row = report["slos"][0]
    assert row["status"] == "ok" and row["ok"] is True
    assert row["observed"] <= 0.0128 * 2  # same-bucket bound around 10ms

    tight = _engine((SloDef("x_p95", "x_seconds", 0.95, 0.001),), m)
    report = tight.evaluate()
    assert report["ok"] is False
    (v,) = report["violations"]
    assert v["slo"] == "x_p95"
    assert v["series"] == "x_seconds"
    assert v["window"] == "cumulative"
    assert v["quantile"] == 0.95
    assert v["observed"] > v["budget"] == 0.001
    assert v["count"] == 100


def test_slo_no_data_is_not_a_violation():
    m = Metrics(enabled=True)
    eng = _engine((SloDef("ghost_p95", "ghost_seconds", 0.95, 1.0),), m)
    report = eng.evaluate()
    assert report["ok"] is True
    assert report["slos"][0]["status"] == "no_data"
    assert report["slos"][0]["observed"] is None


def test_slo_label_filter_selects_series():
    m = Metrics(enabled=True)
    for _ in range(50):
        m.observe("r_seconds", 0.001, route="/fast")
        m.observe("r_seconds", 5.0, route="/slow")
    fast_only = _engine(
        (SloDef("fast_p95", "r_seconds", 0.95, 0.1,
                labels=(("route", "/fast"),)),), m
    )
    assert fast_only.evaluate()["ok"] is True
    merged = _engine((SloDef("all_p95", "r_seconds", 0.95, 0.1),), m)
    assert merged.evaluate()["ok"] is False


def test_slo_emits_gauges_and_counters():
    m = Metrics(enabled=True)
    for _ in range(10):
        m.observe("x_seconds", 5.0)
    eng = _engine((SloDef("x_p95", "x_seconds", 0.95, 0.1),), m)
    eng.evaluate()
    assert m.get("slo_budget_seconds", slo="x_p95") == pytest.approx(0.1)
    assert m.get("slo_quantile_seconds", slo="x_p95") > 0.1
    assert m.get("slo_ok", slo="x_p95") == 0.0
    assert m.get("slo_evaluations_total") == 1
    assert m.get("slo_violations_total", slo="x_p95") == 1
    # burn gauges carry both windows
    assert m.get("slo_burn_rate", slo="x_p95", window="fast") > 1.0
    assert m.get("slo_burn_rate", slo="x_p95", window="slow") > 1.0


# ------------------------------------------------------- burn-rate windows


def test_burn_rate_windows_see_different_history():
    """Good traffic for a long stretch, then a burst of bad: the fast
    window burns hot while the slow window dilutes."""
    m = Metrics(enabled=True)
    slo = SloDef("x_p95", "x_seconds", 0.95, 0.1)
    eng = SloEngine(
        slos=(slo,), metrics=m, windows=(("fast", 60.0), ("slow", 3600.0))
    )
    t0 = 10_000.0
    eng.tick(now=t0)  # slow-window baseline: empty history
    # 1000 good observations early in the slow window
    for _ in range(1000):
        m.observe("x_seconds", 0.01)
    eng.tick(now=t0 + 60.0)  # fast-window baseline: the good era
    # now 100 bad observations inside the fast window
    for _ in range(100):
        m.observe("x_seconds", 5.0)
    report = eng.evaluate(now=t0 + 3600.0)
    row = report["slos"][0]
    fast, slow = row["burn_rates"]["fast"], row["burn_rates"]["slow"]
    # fast window (baseline t0+60): 100 bad / 100 observed -> 1.0/0.05 = 20
    assert fast == pytest.approx(20.0, rel=0.01)
    # slow window (baseline t0): all 1100 -> 100/1100 / 0.05 ≈ 1.82
    assert slow == pytest.approx((100 / 1100) / 0.05, rel=0.01)
    assert fast > slow
    assert row["breaching"] is True  # both windows above threshold 1.0


def test_burn_rate_zero_traffic_windows_do_not_breach():
    m = Metrics(enabled=True)
    for _ in range(10):
        m.observe("x_seconds", 5.0)  # all bad, but before any window math
    eng = SloEngine(
        slos=(SloDef("x_p95", "x_seconds", 0.95, 0.1),), metrics=m,
        windows=(("fast", 60.0),),
    )
    t0 = 5_000.0
    eng.tick(now=t0)
    # the baseline snapshot sits inside the window and nothing new was
    # observed since: delta count 0 -> burn 0, no breach
    report = eng.evaluate(now=t0 + 90.0)
    row = report["slos"][0]
    assert row["burn_rates"]["fast"] == 0.0
    assert row["breaching"] is False
    # still a cumulative violation though
    assert report["ok"] is False


def test_engine_young_process_clamps_windows_to_lifetime():
    m = Metrics(enabled=True)
    for _ in range(100):
        m.observe("x_seconds", 5.0)
    eng = SloEngine(
        slos=(SloDef("x_p95", "x_seconds", 0.95, 0.1),), metrics=m,
        windows=(("slow", 3600.0),),
    )
    # no baseline snapshot older than the window: zero-origin applies,
    # so the whole (bad) history burns
    report = eng.evaluate()
    assert report["slos"][0]["burn_rates"]["slow"] == pytest.approx(20.0, rel=0.01)


def test_engine_snapshot_history_is_bounded():
    m = Metrics(enabled=True)
    eng = SloEngine(slos=(), metrics=m, max_snapshots=8)
    for i in range(100):
        eng.tick(now=float(i))
    assert len(eng._snaps) == 8


# ------------------------------------------------------------- the gate


def _run_gate(*extra, timeout=180):
    env = dict(os.environ)
    env.pop("TELEMETRY_OFF", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "slo_check.py"),
         "--smoke", "--duration", "0.5", *extra],
        capture_output=True, text=True, timeout=timeout, cwd=REPO_ROOT, env=env,
    )


def test_slo_check_smoke_green():
    out = _run_gate()
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout)
    assert report["ok"] is True and report["violations"] == []
    by_name = {r["slo"]: r for r in report["slos"]}
    # the profile must actually drive the core families, not no_data them
    for name in ("attestation_admit_apply_p95", "ingest_lane_wait_p95",
                 "ingest_sched_p99", "api_request_p99"):
        assert by_name[name]["count"] > 0, f"{name} got no data"
        assert by_name[name]["status"] == "ok"
    assert by_name["block_arrival_offset_p95"]["count"] == 8
    # the undriveable SLO is loudly UNCHECKED, never silently green
    assert report["unchecked"] == ["gossip_drain_p95"]
    assert "UNCHECKED gossip_drain_p95" in out.stderr
    # every gate API request answered 200 (availability is first-class)
    prof = report["profile"]
    assert prof["api_requests_ok"] == prof["api_requests_expected"]


def test_slo_check_empty_exercised_family_fails_the_gate():
    """A broken profile stage (here: zero pipeline duration) must fail
    as a structured no_data violation, not read as green."""
    out = _run_gate("--duration", "0")
    assert out.returncode == 1
    report = json.loads(out.stdout)
    no_data = [v for v in report["violations"] if v.get("observed") is None]
    assert any(v["slo"] == "attestation_admit_apply_p95" for v in no_data)
    assert all(v["count"] == 0 for v in no_data)
    assert "no_data" in out.stderr


def test_slo_check_tightened_budget_exits_nonzero():
    out = _run_gate("--budget", "ingest_lane_wait_p95=0.000001")
    assert out.returncode == 1
    report = json.loads(out.stdout)
    assert report["ok"] is False
    # its own violation, picked out of the list: a loaded machine adds
    # others (the duty deadlines) that this test says nothing about
    (v,) = [v for v in report["violations"]
            if v["series"] == "ingest_flush_wait_seconds"]
    assert v["window"] == "cumulative"
    assert v["observed"] > v["budget"]
    assert "SLO VIOLATION" in out.stderr
    assert "ingest_flush_wait_seconds" in out.stderr


def test_slo_check_unknown_budget_name_is_usage_error():
    out = _run_gate("--budget", "nope_p95=1.0")
    assert out.returncode == 2
    assert "unknown SLO" in out.stderr


# ------------------------------------------------------------- engine race


def test_engine_concurrent_evaluate_is_safe():
    """The node tick loop and the /debug/slo worker thread evaluate the
    same engine concurrently."""
    import threading

    m = Metrics(enabled=True)
    for _ in range(100):
        m.observe("x_seconds", 0.01)
    eng = _engine((SloDef("x_p95", "x_seconds", 0.95, 1.0),), m)
    errors = []

    def spin():
        try:
            for _ in range(200):
                eng.evaluate()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=spin) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
