"""Device cost & memory observatory (round 18): cost-table accounting,
plane-registry register/release/watermark semantics, the /debug/profile
and /debug/compile surfaces, capture-budget enforcement, and the
bench_compare trend/regression gate."""

import json
import os
import sys
import time
from types import SimpleNamespace

import pytest

from lambda_ethereum_consensus_tpu.api.beacon_api import BeaconApiServer
from lambda_ethereum_consensus_tpu.node.telemetry import Metrics
from lambda_ethereum_consensus_tpu.ops import aot, profile
from lambda_ethereum_consensus_tpu.tracing import get_recorder

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))

import bench_compare  # noqa: E402


@pytest.fixture
def no_disk(monkeypatch):
    monkeypatch.setenv("BLS_NO_AOT", "1")


# ------------------------------------------------------------- cost table


def test_cost_table_accounts_real_jitted_entry(no_disk):
    """A real jax.jit toy through the AOT wrapper lands in the cost
    table with non-zero FLOP/byte attribution pulled at compile time."""
    import jax
    import jax.numpy as jnp

    call = aot.aot_jit(jax.jit(lambda x: x @ x), "prof18_toy")
    call(jnp.ones((32, 32), jnp.float32))
    call(jnp.ones((32, 32), jnp.float32))
    rows = [r for r in profile.cost_table() if r["entry"] == "prof18_toy"]
    assert len(rows) == 1
    row = rows[0]
    assert row["flops"] > 0
    assert row["bytes_accessed"] > 0
    assert row["signature"].count("(32, 32)") == 1
    # the /debug/compile join resolves the same row by (entry, sig)
    assert profile.cost_for("prof18_toy", row["signature"])["flops"] == row["flops"]
    assert profile.cost_for("prof18_toy", "nope") is None


class _FakeExecutable:
    """Executable stand-in answering the two compile-time analyses."""

    def __init__(self, flops=2.0e9, bytes_accessed=4.0e8, code=4096, temp=512):
        self._flops, self._bytes = flops, bytes_accessed
        self._code, self._temp = code, temp

    def __call__(self, *args):
        return ("ran", args)

    def cost_analysis(self):
        # the list-of-dicts shape some jax versions return
        return [{"flops": self._flops, "bytes accessed": self._bytes}]

    def memory_analysis(self):
        return SimpleNamespace(
            generated_code_size_in_bytes=self._code,
            temp_size_in_bytes=self._temp,
            argument_size_in_bytes=64,
            output_size_in_bytes=64,
        )


class _FakeLowered:
    def __init__(self, executable):
        self._executable = executable

    def compile(self):
        return self._executable


class _FakeJitted:
    def __init__(self, executable):
        self._executable = executable

    def lower(self, *args):
        return _FakeLowered(self._executable)


def test_entry_report_joins_cost_calls_span_family_and_slo(no_disk):
    """One row per entry: cost-table FLOPs times call counts, the span
    family and SLO that govern it, ranked by cumulative FLOPs — and no
    achieved rate or share of a peak (those come from a device trace)."""
    m = Metrics(enabled=True)
    # a duty_sign-prefixed entry maps onto duty_sign_seconds
    call = aot.aot_jit(_FakeJitted(_FakeExecutable()), "duty_sign_t18")
    call(1.0)
    call(1.0)
    call(1.0)  # 3 calls x 2 GFLOP
    m.observe("duty_sign_seconds", 1.0)
    m.observe("duty_sign_seconds", 1.0)  # 2 s total span time
    report = profile.entry_report(metrics=m)
    row = next(e for e in report if e["entry"] == "duty_sign_t18")
    assert row["calls"] == 3
    assert row["flops_total"] == pytest.approx(6.0e9)
    assert row["bytes_total"] == pytest.approx(1.2e9)
    assert row["code_bytes"] == 4096 and row["temp_bytes"] == 512
    assert row["span_family"] == "duty_sign_seconds"
    assert row["span_seconds"] == pytest.approx(2.0) and row["span_count"] == 2
    # the governing SLO rides along (duty_sign_p95 budgets this family)
    assert row["slo"]["name"] == "duty_sign_p95"
    for gone in ("achieved_gflops", "compute_ratio", "memory_ratio",
                 "roofline_ratio", "headroom"):
        assert gone not in row
    # ranking: most cumulative FLOPs first, ranks are 1..n
    assert [e["rank"] for e in report] == list(range(1, len(report) + 1))
    totals = [e["flops_total"] for e in report]
    assert totals == sorted(totals, reverse=True)


def test_device_peaks_table_and_calibration(monkeypatch):
    """The published-peaks table holds the v5e with its source; a device
    it does not hold has none — neither another chip's nor a host
    placeholder's — unless the deployment calibrates both numbers."""
    monkeypatch.delenv("PROFILE_PEAK_GFLOPS", raising=False)
    monkeypatch.delenv("PROFILE_PEAK_GBS", raising=False)
    peaks = profile.device_peaks("TPU v5 lite")
    assert peaks["source"].startswith("Google Cloud documentation")
    assert (peaks["gflops"], peaks["gbs"]) == (197000.0, 819.0)
    for kind in ("cpu", "TPU v9 imaginary", None):
        assert profile.device_peaks(kind) is None
    # one override alone is not a calibration; both are
    monkeypatch.setenv("PROFILE_PEAK_GFLOPS", "100")
    assert profile.device_peaks("cpu") is None
    monkeypatch.setenv("PROFILE_PEAK_GBS", "10")
    assert profile.device_peaks("cpu")["source"] == "env"


def test_emit_entry_metrics_publishes_counter_deltas(no_disk):
    m = Metrics(enabled=True)
    call = aot.aot_jit(_FakeJitted(_FakeExecutable(flops=1.0e6)), "duty_sign_t18b")
    call(2.0)
    m.observe("duty_sign_seconds", 0.5)
    profile.emit_entry_metrics(m)
    first = m.get("ops_entry_flops_total", entry="duty_sign_t18b")
    assert first > 0
    # a second emission with no new calls adds nothing (delta cursors)
    profile.emit_entry_metrics(m)
    assert m.get("ops_entry_flops_total", entry="duty_sign_t18b") == first
    # another call advances the counter by one program's flops
    call(2.0)
    profile.emit_entry_metrics(m)
    assert m.get(
        "ops_entry_flops_total", entry="duty_sign_t18b"
    ) == pytest.approx(first + 1.0e6)
    assert "ops_entry_roofline_ratio" not in m.family_names()


# ---------------------------------------------------------- plane registry


def test_plane_registry_register_release_watermark():
    reg = profile.PlaneRegistry()
    held = {"a": 1000.0, "b": 500.0}
    reg.register("plane_a", lambda: held["a"])
    reg.register("plane_b", lambda: held["b"])
    reg.register("host_plane", lambda: 10_000.0, device=False)
    snap = reg.snapshot(total_bytes=4000.0)
    # unattributed = total - DEVICE planes only (host planes report but
    # never join the remainder arithmetic)
    assert snap["plane_a"] == 1000.0 and snap["plane_b"] == 500.0
    assert snap["host_plane"] == 10_000.0
    assert snap["unattributed"] == 2500.0
    assert reg.watermark == 4000.0
    # release: an unregistered plane vanishes from later snapshots
    reg.unregister("plane_b")
    snap = reg.snapshot(total_bytes=3000.0)
    assert "plane_b" not in snap
    assert snap["unattributed"] == 2000.0
    # watermark is a high watermark: a smaller total never lowers it
    assert reg.watermark == 4000.0
    # a raising provider reports 0, never breaks the snapshot
    reg.register("broken", lambda: 1 / 0)
    assert reg.snapshot(total_bytes=100.0)["broken"] == 0.0
    # remainder clamps at 0 when providers over-claim
    held["a"] = 99_999.0
    assert reg.snapshot(total_bytes=100.0)["unattributed"] == 0.0
    # no total -> no remainder series, watermark untouched
    assert "unattributed" not in reg.snapshot()


def test_default_registry_carries_the_shipped_planes():
    # importing the subsystems registers their planes; the witness and
    # duty/registry/resident planes are wired at import time
    import lambda_ethereum_consensus_tpu.ops.bls_batch  # noqa: F401
    import lambda_ethereum_consensus_tpu.ops.bls_sign  # noqa: F401
    import lambda_ethereum_consensus_tpu.state_transition.resident  # noqa: F401
    import lambda_ethereum_consensus_tpu.witness.service  # noqa: F401

    snap = profile.plane_bytes(1 << 20)
    named = set(snap) - {"unattributed"}
    assert {
        "aot_executables", "registry_planes", "resident_epoch",
        "witness_buffers", "duty_sign_ladders",
    } <= named
    assert "unattributed" in snap


def test_witness_service_reports_retained_bytes():
    from lambda_ethereum_consensus_tpu.witness.service import WitnessService

    svc = WitnessService()
    assert svc.retained_bytes() == 0  # no planners yet, empty cache


def test_duty_sign_plane_claims_its_executables(no_disk):
    call = aot.aot_jit(
        _FakeJitted(_FakeExecutable(code=2048, temp=256)), "duty_sign_t18c"
    )
    call(3.0)
    assert profile.entry_plane_bytes("duty_sign_t18c") == 2048 + 256
    # claimed prefixes are excluded from the shared executables plane
    assert "duty_sign" in profile._ENTRY_PLANES.values()
    unclaimed = profile._unclaimed_executable_bytes()
    claimed = profile.entry_plane_bytes("duty_sign")
    total = sum(
        r["code_bytes"] + r["temp_bytes"] for r in profile.cost_table()
    )
    assert unclaimed + claimed == total


# ------------------------------------------------------------ API surface


def test_debug_profile_route_shape(no_disk):
    m = Metrics(enabled=True)  # noqa: F841  (report reads the default)
    call = aot.aot_jit(_FakeJitted(_FakeExecutable()), "duty_sign_t18d")
    call(4.0)
    api = BeaconApiServer(store=None, spec=None)
    status, ctype, body = api._route("GET", "/debug/profile")
    assert status == "200 OK" and ctype == "application/json"
    data = json.loads(body)["data"]
    assert set(data) >= {
        "device_kind", "peaks", "entries", "planes",
        "plane_watermark_bytes", "capture",
    }
    # tests run on the CPU, which the published-peaks table does not hold
    assert data["peaks"] is None
    entries = {e["entry"] for e in data["entries"]}
    assert "duty_sign_t18d" in entries
    for e in data["entries"]:
        assert {"rank", "calls", "flops_total", "span_family"} <= set(e)
    assert "unattributed" not in data["planes"] or data["planes"][
        "unattributed"
    ] >= 0
    assert {"max_seconds", "max_mb", "running", "last"} <= set(data["capture"])


def test_debug_compile_gains_cost_columns(no_disk):
    call = aot.aot_jit(_FakeJitted(_FakeExecutable(flops=7.0)), "duty_sign_t18e")
    call(5.0)
    api = BeaconApiServer(store=None, spec=None)
    _status, _ctype, body = api._route("GET", "/debug/compile")
    rows = [
        r for r in json.loads(body)["data"]["executables"]
        if r["entry"] == "duty_sign_t18e"
    ]
    assert rows and rows[0]["flops"] == 7.0
    assert rows[0]["bytes_accessed"] > 0
    # entries without recorded cost still carry the columns (as null)
    aot.aot_jit(_FakeJitted(lambda *a: None), "prof18_plain")(1)
    _s, _c, body = api._route("GET", "/debug/compile")
    plain = [
        r for r in json.loads(body)["data"]["executables"]
        if r["entry"] == "prof18_plain"
    ]
    assert plain and plain[0]["flops"] is None


# --------------------------------------------------------------- capture


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: books what the
    program's spans write into the capture."""

    seen: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.seen.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.seen.append(("exit", self.name))


class _FakeTracer:
    TraceAnnotation = _FakeAnnotation

    def __init__(self, write_bytes=64, fail_start=False, during=None):
        self.started = self.stopped = 0
        self.write_bytes = write_bytes
        self.fail_start = fail_start
        self.during = during  # called once the trace is open
        self._dir = None

    def start_trace(self, path):
        if self.fail_start:
            raise RuntimeError("profiler refused to start")
        self.started += 1
        self._dir = path
        with open(os.path.join(path, "trace.pb"), "wb") as fh:
            fh.write(b"x" * self.write_bytes)
        if self.during is not None:
            self.during()

    def stop_trace(self):
        self.stopped += 1


def test_capture_refuses_oversized_window_before_tracing(monkeypatch, tmp_path):
    monkeypatch.setenv("PROFILE_CAPTURE_MAX_S", "2")
    tracer = _FakeTracer()
    with pytest.raises(ValueError, match="PROFILE_CAPTURE_MAX_S"):
        profile.capture_trace(5.0, out_dir=str(tmp_path), tracer=tracer)
    assert tracer.started == 0  # refused BEFORE any tracing
    with pytest.raises(ValueError, match="positive"):
        profile.capture_trace(0.0, out_dir=str(tmp_path), tracer=tracer)


def test_capture_runs_within_budget_and_records_instants(monkeypatch, tmp_path):
    monkeypatch.setenv("PROFILE_CAPTURE_MAX_S", "2")
    monkeypatch.setenv("PROFILE_CAPTURE_MAX_MB", "1")
    tracer = _FakeTracer(write_bytes=128)
    report = profile.capture_trace(0.01, out_dir=str(tmp_path), tracer=tracer)
    assert tracer.started == 1 and tracer.stopped == 1
    assert report["bytes"] == 128
    assert report["seconds"] >= 0.01
    assert os.path.isdir(report["dir"])
    # start/stop instants land in the flight recorder for Perfetto
    names = [e["name"] for e in get_recorder().snapshot()]
    assert "profile_capture_start" in names
    assert "profile_capture_stop" in names
    assert profile.capture_state()["last"]["bytes"] == 128


def test_capture_annotates_spans_for_its_length_only(monkeypatch, tmp_path):
    """The device-clock bridge: spans entered while a capture is open
    (``_Span`` and ``BoundSpan`` timings alike) write ``span:<name>``
    annotations and still record their histograms; before and after it,
    and after a ``start_trace`` that raised, they write none."""
    from lambda_ethereum_consensus_tpu import telemetry

    monkeypatch.setenv("PROFILE_CAPTURE_MAX_S", "2")
    m = Metrics(enabled=True)
    bound = m.bound_span("ssz_hash_tree_root")
    _FakeAnnotation.seen = []

    def spans():
        with m.span("gossip_decode", topic="t"):
            with bound.time():
                pass

    spans()  # before: no capture, no annotation
    assert _FakeAnnotation.seen == [] and telemetry._ANNOTATION is None
    tracer = _FakeTracer(during=spans)
    profile.capture_trace(0.01, out_dir=str(tmp_path), tracer=tracer)
    assert _FakeAnnotation.seen == [
        ("enter", "span:gossip_decode"), ("enter", "span:ssz_hash_tree_root"),
        ("exit", "span:ssz_hash_tree_root"), ("exit", "span:gossip_decode"),
    ]
    assert telemetry._ANNOTATION is None  # off again after stop_trace
    spans()
    assert len(_FakeAnnotation.seen) == 4
    with pytest.raises(RuntimeError, match="refused to start"):
        profile.capture_trace(
            0.01, out_dir=str(tmp_path), tracer=_FakeTracer(fail_start=True)
        )
    assert telemetry._ANNOTATION is None  # also when start_trace raised
    spans()
    assert len(_FakeAnnotation.seen) == 4
    assert m.get_histogram("gossip_decode_seconds", topic="t")[3] == 4
    assert m.get_histogram("ssz_hash_tree_root_seconds")[3] == 4


def test_capture_over_byte_budget_deletes_trace(monkeypatch, tmp_path):
    monkeypatch.setenv("PROFILE_CAPTURE_MAX_S", "2")
    # ~0.0001 MB budget: the 64-byte fake trace blows it
    monkeypatch.setenv("PROFILE_CAPTURE_MAX_MB", "0.00001")
    tracer = _FakeTracer(write_bytes=64)
    with pytest.raises(ValueError, match="PROFILE_CAPTURE_MAX_MB"):
        profile.capture_trace(0.01, out_dir=str(tmp_path), tracer=tracer)
    assert tracer.stopped == 1
    assert not os.path.isdir(tracer._dir)  # over-budget trace deleted


def test_capture_route_budgets_to_400(monkeypatch, tmp_path):
    monkeypatch.setenv("PROFILE_CAPTURE_MAX_S", "1")
    api = BeaconApiServer(store=None, spec=None)
    status, _ctype, body = api._route(
        "POST", "/debug/profile/capture",
        body=json.dumps({"seconds": 99}).encode(), ctype="application/json",
    )
    assert status.startswith("400")
    assert "PROFILE_CAPTURE_MAX_S" in json.loads(body)["message"]
    status, _c, body = api._route(
        "POST", "/debug/profile/capture", body=b"{}",
        ctype="application/json",
    )
    assert status.startswith("400")  # seconds is required


# ------------------------------------------------------------ bench_compare


def _write_lines(path, records):
    path.write_text("\n".join(json.dumps(r) for r in records) + "\n")


def test_bench_compare_parses_all_three_artifact_forms(tmp_path):
    rec1 = {"metric": "toy_per_sec", "value": 100.0}
    rec2 = {"metric": "toy_per_sec", "value": 110.0}
    wrapper = tmp_path / "BENCH_r01.json"
    wrapper.write_text(json.dumps({
        "rc": 0, "tail": json.dumps(rec1) + "\n", "parsed": rec1,
    }))
    as_list = tmp_path / "BENCH_r02.json"
    as_list.write_text(json.dumps([rec2]))
    as_lines = tmp_path / "BENCH_r03.json"
    _write_lines(as_lines, [{"metric": "toy_per_sec", "value": 120.0}])
    report = bench_compare.evaluate(
        [str(wrapper), str(as_list), str(as_lines)]
    )
    row = report["metrics"]["toy_per_sec"]
    assert [p["value"] for p in row["points"]] == [100.0, 110.0, 120.0]
    assert row["status"] == "ok" and report["ok"] is True
    assert [a["label"] for a in report["artifacts"]] == ["r01", "r02", "r03"]


def test_bench_compare_flags_regression_and_gates(tmp_path):
    a = tmp_path / "BENCH_r01.json"
    b = tmp_path / "BENCH_r02.json"
    _write_lines(a, [{"metric": "toy_per_sec", "value": 100.0}])
    _write_lines(b, [{"metric": "toy_per_sec", "value": 50.0}])
    report = bench_compare.evaluate([str(a), str(b)])
    assert report["metrics"]["toy_per_sec"]["status"] == "regressed"
    assert not report["ok"]
    # the CLI gates (rc 1) unless --report-only
    assert bench_compare.main([str(a), str(b)]) == 1
    assert bench_compare.main([str(a), str(b), "--report-only"]) == 0


def test_bench_compare_noise_band_and_overrides(tmp_path):
    a = tmp_path / "BENCH_r01.json"
    b = tmp_path / "BENCH_r02.json"
    _write_lines(a, [{"metric": "toy_per_sec", "value": 100.0}])
    _write_lines(b, [{"metric": "toy_per_sec", "value": 90.0}])
    # -10% sits inside the default +-15% band
    assert bench_compare.evaluate([str(a), str(b)])["ok"] is True
    # a tighter per-metric override flips it to a regression
    report = bench_compare.evaluate(
        [str(a), str(b)], overrides={"toy_per_sec": 0.05}
    )
    assert report["metrics"]["toy_per_sec"]["status"] == "regressed"
    # a looser global band stays green
    assert bench_compare.evaluate([str(a), str(b)], band=0.5)["ok"] is True


def test_bench_compare_directions_and_null_rounds(tmp_path):
    a = tmp_path / "BENCH_r01.json"
    b = tmp_path / "BENCH_r02.json"
    c = tmp_path / "BENCH_r03.json"
    _write_lines(a, [
        {"metric": "toy_root_s", "value": 1.0},
        {"metric": "toy_mystery", "value": 5.0},
    ])
    # an empty round (honest absence) does not participate
    _write_lines(b, [{"metric": "toy_root_s", "value": None}])
    _write_lines(c, [
        {"metric": "toy_root_s", "value": 2.0},
        {"metric": "toy_mystery", "value": 1.0},
    ])
    report = bench_compare.evaluate([str(a), str(b), str(c)])
    # latency doubled: lower-is-better metric regresses over the gap
    assert report["metrics"]["toy_root_s"]["status"] == "regressed"
    # unknown direction never gates
    assert report["metrics"]["toy_mystery"]["status"] == "informational"
    assert [r["metric"] for r in report["regressions"]] == ["toy_root_s"]
    md = bench_compare.to_markdown(report)
    assert "toy_root_s" in md and "Regressions" in md


def test_bench_compare_runs_over_checked_in_trajectory():
    """The `make test` smoke: the checked-in artifacts parse and produce
    a trend report; historical data never gates CI (the --report-only
    knob), and the known headliners appear.  (PR 21 deleted the records
    taken behind the gone remote plug-in; two artifacts remain.)"""
    paths = bench_compare.default_artifacts()
    assert len(paths) >= 2
    report = bench_compare.evaluate(paths)
    assert "ssz_merkle_node_hashes_per_sec" in report["metrics"]
    assert "aggregate_bls_verifications_per_sec" in report["metrics"]
    assert bench_compare.main(["--report-only", *paths]) == 0


def test_bench_compare_synthetic_regression_gates(tmp_path):
    """Acceptance: fed a synthetically regressed artifact on top of the
    real trajectory, the gate exits non-zero.  The regressed values are
    derived from the trajectory's own latest points (half of each
    higher-is-better headliner) so re-anchored artifacts — e.g. a
    cpu-backend bench run recording a far lower absolute number — can't
    quietly turn the synthetic regression into an improvement."""
    paths = bench_compare.default_artifacts()
    report = bench_compare.evaluate(paths)

    def latest(name):
        return [p["value"] for p in report["metrics"][name]["points"]
                if p["value"] is not None][-1]

    bad = tmp_path / "BENCH_r99.json"
    _write_lines(bad, [
        {"metric": name, "value": latest(name) * 0.5}
        for name in ("ssz_merkle_node_hashes_per_sec",
                     "aggregate_bls_verifications_per_sec")
    ])
    rc = bench_compare.main([*paths, str(bad)])
    assert rc == 1


def test_bench_compare_needs_two_artifacts(tmp_path):
    only = tmp_path / "BENCH_r01.json"
    _write_lines(only, [{"metric": "toy_per_sec", "value": 1.0}])
    assert bench_compare.main([str(only)]) == 2
    assert bench_compare.main([str(only), str(tmp_path / "missing.json")]) == 2
    assert bench_compare.main(
        [str(only), str(only), "--override", "bad-spec"]
    ) == 2
