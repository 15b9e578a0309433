"""Compile/retrace profiler (ops/aot.py round 12): attribution table,
process-wide counters, per-entry-point histograms, flight-recorder
retrace events, and the /debug/compile + /debug/slo API routes."""

import json
import time

import pytest

from lambda_ethereum_consensus_tpu.api.beacon_api import BeaconApiServer
from lambda_ethereum_consensus_tpu.ops import aot
from lambda_ethereum_consensus_tpu.telemetry import get_metrics
from lambda_ethereum_consensus_tpu.tracing import get_recorder


class _FakeLowered:
    def __init__(self, compiled, compile_s=0.0):
        self._compiled = compiled
        self._compile_s = compile_s

    def compile(self):
        if self._compile_s:
            time.sleep(self._compile_s)
        return self._compiled


class _FakeJitted:
    """Shape-polymorphic stand-in for a jax.jit function: lower() returns
    a compilable whose executable records invocations."""

    def __init__(self, compile_s=0.0):
        self.lowers = 0
        self.compile_s = compile_s

    def lower(self, *args):
        self.lowers += 1
        return _FakeLowered(lambda *a: ("ran", a), self.compile_s)

    def __call__(self, *args):  # direct-call fallback path
        return ("direct", args)


@pytest.fixture
def no_disk(monkeypatch):
    """Keep the cache purely in-memory: the profiler paths under test
    are hit/miss/lower/compile, not serialization."""
    monkeypatch.setenv("BLS_NO_AOT", "1")


def _counter(name, **labels):
    return get_metrics().get(name, **labels)


def test_profiler_records_miss_compile_then_hits(no_disk):
    before_retraces = _counter("aot_retraces_total")
    before_compiles = _counter("aot_compiles_total")
    fake = _FakeJitted(compile_s=0.002)
    call = aot.aot_jit(fake, "prof_entry")

    assert call(1.0, 2.0)[0] == "ran"
    assert call(1.0, 2.0)[0] == "ran"
    assert call(1.0, 2.0)[0] == "ran"

    assert fake.lowers == 1  # one retrace, then in-memory hits
    assert _counter("aot_retraces_total") == before_retraces + 1
    assert _counter("aot_compiles_total") == before_compiles + 1

    rows = [e for e in aot.compile_profile() if e["entry"] == "prof_entry"]
    assert len(rows) == 1
    row = rows[0]
    assert row["misses"] == 1 and row["hits"] == 2
    assert row["compiles"] == 1 and row["loads"] == 0
    assert row["source"] == "compile"
    assert row["compile_seconds"] >= 0.002
    assert row["lower_seconds"] >= 0.0
    assert row["last_use"] >= row["created"]
    assert row["context"] == "live"
    # the causing call site is THIS test file
    assert "test_aot_profile.py" in row["caller"]
    # shapes are part of the signature string
    assert "float" in row["signature"] or "()" in row["signature"]


def test_profiler_separates_shape_signatures(no_disk):
    import numpy as np

    fake = _FakeJitted()
    call = aot.aot_jit(fake, "prof_shapes")
    call(np.zeros((4,), np.int32))
    call(np.zeros((8,), np.int32))  # new shape -> second retrace
    call(np.zeros((8,), np.int32))
    assert fake.lowers == 2
    rows = [e for e in aot.compile_profile() if e["entry"] == "prof_shapes"]
    assert len(rows) == 2
    assert {r["misses"] for r in rows} == {1}
    assert sorted(r["hits"] for r in rows) == [0, 1]


def test_profiler_emits_per_entry_histograms(no_disk):
    m = get_metrics()
    call = aot.aot_jit(_FakeJitted(compile_s=0.001), "prof_hist")
    call(3.0)
    hist = m.get_histogram("aot_compile_seconds", entry="prof_hist")
    assert hist is not None
    _bounds, _counts, h_sum, h_count = hist
    assert h_count >= 1 and h_sum >= 0.001


def test_retrace_event_lands_in_flight_recorder_and_chrome_export(no_disk):
    rec = get_recorder()
    call = aot.aot_jit(_FakeJitted(), "prof_trace")
    call(7.0)
    events = [
        e for e in rec.snapshot()
        if e["name"] == "retrace" and (e["args"] or {}).get("entry") == "prof_trace"
    ]
    assert events, "retrace instant missing from the recorder ring"
    args = events[-1]["args"]
    assert "test_aot_profile.py" in args["caller"]
    assert args["context"] == "live"
    # and it renders in the Perfetto export as a global instant
    chrome = rec.chrome()
    named = [e for e in chrome["traceEvents"] if e.get("name") == "retrace"]
    assert named and named[-1]["ph"] == "i"


def test_compile_context_attributes_warmup(no_disk):
    call = aot.aot_jit(_FakeJitted(), "prof_ctx")
    with aot.compile_context("warmup:test"):
        call(11.0)
    row = [e for e in aot.compile_profile() if e["entry"] == "prof_ctx"][0]
    assert row["context"] == "warmup:test"
    assert aot._ctx_label() == "live"  # context restored


def test_unlowerable_function_raises(no_disk):
    """A program that does not lower is an error, not a quiet uncached
    run: nothing is cached for it and every call raises."""
    def plain(x):
        return x + 1

    call = aot.aot_jit(plain, "prof_plain")
    assert call.jitted is plain  # the wrapped function stays reachable
    for _ in range(2):
        with pytest.raises(AttributeError):
            call(1)
    row = [e for e in aot.compile_profile() if e["entry"] == "prof_plain"][0]
    assert row["source"] is None and row["hits"] == 0


def test_load_failure_counts_error_and_falls_back_to_compile(
    monkeypatch, tmp_path
):
    """A corrupt cache file must surface as aot_errors_total{stage=load}
    and a fresh compile, never a wrong result."""
    monkeypatch.delenv("BLS_NO_AOT", raising=False)
    # the one cache root placed from outside: the AOT tier is its aot/
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert aot.aot_dir() == str(tmp_path / "aot")
    (tmp_path / "aot").mkdir()
    fake = _FakeJitted()
    call = aot.aot_jit(fake, "prof_corrupt")

    # plant a corrupt pickle at the exact path the wrapper will probe
    import hashlib
    import os

    sig = aot._sig((5.0,))
    key = hashlib.sha256(
        f"prof_corrupt||{aot._env_tag()}||{sig}||{aot._src_version()}".encode()
    ).hexdigest()[:32]
    path = os.path.join(aot.aot_dir(), f"prof_corrupt-{key}.aot")
    with open(path, "wb") as fh:
        fh.write(b"not a pickle")

    before = _counter("aot_errors_total", stage="load")
    assert call(5.0)[0] == "ran"
    assert _counter("aot_errors_total", stage="load") == before + 1
    row = [e for e in aot.compile_profile() if e["entry"] == "prof_corrupt"][0]
    assert row["errors"] >= 1 and row["source"] == "compile"


def test_disk_load_keeps_single_device_program_single_device(
    monkeypatch, tmp_path
):
    """Save an executable, load it back in this 8-virtual-device process
    and call it with single-device arguments.  ``deserialize_and_load``
    defaults to every device of the backend, which made the loaded
    program expect 8 shards ("Expected args to
    execute_sharded_on_local_devices to have 8 shards") — every second
    process on a multi-device host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    assert len(jax.devices()) > 1, "precondition: a multi-device process"
    monkeypatch.delenv("BLS_NO_AOT", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    x = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    want = np.asarray(x) * 3 + 1

    first = aot.aot_jit(jax.jit(lambda v: v * 3 + 1), "prof_disk_roundtrip")
    np.testing.assert_array_equal(np.asarray(first(x)), want)
    saved = list((tmp_path / "aot").glob("prof_disk_roundtrip-*.aot"))
    assert len(saved) == 1

    # a fresh wrapper has an empty in-memory tier, as a new process would
    loads_before = _counter("aot_loads_total")
    errors_before = _counter("aot_errors_total", stage="load")
    second = aot.aot_jit(jax.jit(lambda v: v * 3 + 1), "prof_disk_roundtrip")
    got = second(x)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert len(got.sharding.device_set) == 1
    np.testing.assert_array_equal(np.asarray(second(x)), want)  # memory hit
    assert _counter("aot_loads_total") == loads_before + 1
    assert _counter("aot_errors_total", stage="load") == errors_before
    row = [
        e for e in aot.compile_profile()
        if e["entry"] == "prof_disk_roundtrip"
    ][0]
    assert row["source"] == "disk" and row["loads"] == 1


# ------------------------------------------------------------- API routes


def test_debug_compile_route_serves_attribution_table(no_disk):
    call = aot.aot_jit(_FakeJitted(), "prof_route")
    call(13.0)
    api = BeaconApiServer(store=None, spec=None)
    status, ctype, body = api._debug_compile()
    assert status == "200 OK" and ctype == "application/json"
    data = json.loads(body)["data"]
    assert "retraces" in data["stats"]
    rows = [e for e in data["executables"] if e["entry"] == "prof_route"]
    assert rows and rows[0]["misses"] == 1
    assert "signature" in rows[0] and "caller" in rows[0]
    assert "attestation_entries" in data["warmed_buckets"]


def test_debug_slo_route_serves_engine_report():
    api = BeaconApiServer(store=None, spec=None)
    status, _ctype, body = api._debug_slo()
    assert status == "200 OK"
    data = json.loads(body)["data"]
    assert {row["slo"] for row in data["slos"]} == {
        s.name for s in __import__(
            "lambda_ethereum_consensus_tpu.slo", fromlist=["DEFAULT_SLOS"]
        ).DEFAULT_SLOS
    }
    assert "violations" in data and "windows" in data


def test_debug_slo_route_is_read_only():
    """Polling /debug/slo must not inflate the evaluation counters or
    append burn-rate snapshots (a fast poller would otherwise shorten
    the snapshot deque's window past the slow burn window)."""
    from lambda_ethereum_consensus_tpu.slo import get_engine

    api = BeaconApiServer(store=None, spec=None)
    engine = get_engine()
    evals_before = get_metrics().get("slo_evaluations_total")
    snaps_before = len(engine._snaps)
    for _ in range(5):
        status, _ctype, _body = api._debug_slo()
        assert status == "200 OK"
    assert get_metrics().get("slo_evaluations_total") == evals_before
    assert len(engine._snaps) == snaps_before


def test_api_request_seconds_recorded_per_route():
    m = get_metrics()
    api = BeaconApiServer(store=None, spec=None)
    before = m.get_histogram("api_request_seconds", route="/eth/v1/node/health")
    n_before = before[3] if before else 0
    status, _, _ = api._route_inline("GET", "/eth/v1/node/health")
    assert status == "200 OK"
    after = m.get_histogram("api_request_seconds", route="/eth/v1/node/health")
    assert after is not None and after[3] == n_before + 1
    # offloaded dispatch records too, under the readable pattern label
    api._route("GET", "/debug/compile")
    hist = m.get_histogram("api_request_seconds", route="/debug/compile")
    assert hist is not None and hist[3] >= 1
