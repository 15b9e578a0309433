"""Device cost & memory observatory (round 18).

The round-12 attribution table (ops/aot.py) says which entry points
compile and retrace; the span histograms (telemetry.py) say how long
their dispatches take.  Neither says what the programs *cost* — so
ROADMAP item 1's "move the SHA-256 round body and the Miller-loop
einsum into hand-written Pallas kernels where XLA leaves throughput on
the table" had no way to locate *where*.  This module closes that loop
with three planks:

- **Cost attribution** (:func:`record_entry_cost` / :func:`entry_report`):
  every executable the AOT cache resolves — compiled or deserialized —
  contributes its compile-time ``cost_analysis()`` FLOPs/bytes-accessed
  and ``memory_analysis()`` footprint, keyed ``(entry, shape signature)``
  like the attribution table, and is joined with the per-entry call
  counts and the span-histogram family and SLO that govern the entry.
  ``/debug/profile`` serves that table, ranked by cumulative FLOPs,
  beside the published peaks of the device (:data:`PEAKS`, keyed by
  jax's ``device_kind``, each row citing its source;
  ``PROFILE_PEAK_GFLOPS``/``PROFILE_PEAK_GBS`` calibrate a device the
  table does not hold); ``ops_entry_flops_total`` /
  ``ops_entry_bytes_total`` expose the same counts to Prometheus.  No
  share of a peak is computed here: compile-time FLOPs over host span
  seconds are not a device rate, and the BLS kernels are u32 limb
  arithmetic for which the published bf16 peak is no ceiling — a device
  rate comes from the device's own timeline (a capture window).
- **Per-plane HBM accounting** (:class:`PlaneRegistry`): the subsystems
  that pin device memory (registry planes, the resident epoch plane,
  witness buffers, AOT executables, duty-sign ladders) register byte
  providers; :func:`plane_bytes` resolves them against the
  ``jax.live_arrays()`` total into ``device_plane_bytes{plane}`` series
  with an ``unattributed`` remainder (so the old single total is the
  sum of the live-array planes plus the remainder) and a high-watermark
  gauge.  Providers registered ``device=False`` report retained bytes
  that are NOT part of the live-array total — host buffers (the witness
  planners' tree rows) and compiled program code/temps (the executable
  planes) — emitted for budget visibility but excluded from the
  remainder arithmetic.
- **Capture windows** (:func:`capture_trace`): a bounded on-demand
  ``jax.profiler`` trace (``POST /debug/profile/capture``) — refused
  BEFORE tracing when the requested window exceeds
  ``PROFILE_CAPTURE_MAX_S``, deleted (and errored) when the written
  trace exceeds ``PROFILE_CAPTURE_MAX_MB``.  Start/stop instants land in
  the PR-4 flight recorder so Perfetto exports line up with the node's
  own timeline, and for the length of the capture every ``telemetry``
  span writes a ``span:<name>`` annotation into the trace — the host's
  stages on the device's clock.

No jax import at module scope: a pure-host node can import (and
register planes with) this module for free; everything device-touching
is deferred behind the same ``sys.modules`` gating the node tick uses.
"""

from __future__ import annotations

import os
import shutil
import sys
import threading
import time

from ..telemetry import annotate_spans, get_metrics
from ..tracing import get_recorder

__all__ = [
    "PEAKS",
    "PlaneRegistry",
    "device_peaks",
    "capture_budget",
    "capture_state",
    "capture_trace",
    "cost_for",
    "cost_table",
    "emit_entry_metrics",
    "entry_report",
    "entry_plane_bytes",
    "live_device_bytes",
    "plane_bytes",
    "plane_shard_devices",
    "plane_watermark",
    "profile_report",
    "record_entry_cost",
    "register_entry_plane",
    "register_plane",
    "unregister_plane",
]

_LOCK = threading.Lock()

# ------------------------------------------------------- cost attribution

# (entry, signature) -> cost row.  Filled by ops/aot.py the moment an
# executable is compiled or deserialized (both carry the analyses), so
# the table needs no tracing of its own and is exactly as warm as the
# attribution table it joins against.
_COSTS: dict[tuple[str, str], dict] = {}


def record_entry_cost(entry: str, sig: str, compiled) -> dict | None:
    """Pull ``cost_analysis()``/``memory_analysis()`` off one resolved
    executable into the cost table.  Returns the stored row, or ``None``
    when the executable answers neither analysis (non-XLA fallbacks) —
    a fault here must never break the dispatch path, so every probe is
    guarded."""
    flops = bytes_accessed = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            flops = float(ca.get("flops", 0.0) or 0.0)
            bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)
    except Exception:
        pass
    code_bytes = temp_bytes = arg_bytes = out_bytes = None
    try:
        ma = compiled.memory_analysis()
        code_bytes = int(getattr(ma, "generated_code_size_in_bytes", 0) or 0)
        temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        arg_bytes = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
        out_bytes = int(getattr(ma, "output_size_in_bytes", 0) or 0)
    except Exception:
        pass
    if flops is None and code_bytes is None:
        return None
    row = {
        "entry": entry,
        "signature": sig,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "code_bytes": code_bytes or 0,
        "temp_bytes": temp_bytes or 0,
        "arg_bytes": arg_bytes or 0,
        "out_bytes": out_bytes or 0,
        "recorded": time.time(),
    }
    with _LOCK:
        _COSTS[(entry, sig)] = row
    return row


def cost_table() -> list[dict]:
    """Every recorded cost row (copies — callers may mutate)."""
    with _LOCK:
        return [dict(r) for r in _COSTS.values()]


def cost_for(entry: str, sig: str) -> dict | None:
    """One (entry, signature) row, or None — the /debug/compile join."""
    with _LOCK:
        row = _COSTS.get((entry, sig))
        return dict(row) if row is not None else None


# Published peaks by jax ``device_kind``: (peak GFLOP/s, peak GB/s,
# source).  A device that is not here has none — neither another chip's
# peaks nor a host placeholder stands in for it.  Calibrate such a
# deployment with PROFILE_PEAK_GFLOPS / PROFILE_PEAK_GBS.
PEAKS: dict[str, tuple[float, float, str]] = {
    "TPU v5 lite": (
        197000.0,
        819.0,
        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "819 GB/s HBM per chip",
    ),
}


def device_peaks(device_kind: str | None) -> dict | None:
    """``{"device_kind", "gflops", "gbs", "source"}`` for one device
    kind with the env overrides applied, or ``None`` when the kind is not
    in the table and not fully calibrated from the environment."""
    gflops, gbs, source = PEAKS.get(device_kind or "", (None, None, None))
    # each override parses independently: a typo in one must not
    # silently discard the other valid calibration
    try:
        env_gf = os.environ.get("PROFILE_PEAK_GFLOPS")
        if env_gf:
            gflops, source = float(env_gf), "env"
    except ValueError:
        pass
    try:
        env_gb = os.environ.get("PROFILE_PEAK_GBS")
        if env_gb:
            gbs, source = float(env_gb), "env"
    except ValueError:
        pass
    if gflops is None or gbs is None:
        return None
    return {
        "device_kind": device_kind, "gflops": gflops, "gbs": gbs,
        "source": source,
    }


# Entry-prefix -> span-histogram family: the span (and through it the
# SLO) whose wall time covers the entry's dispatches.  Several chain
# stages share one drain span.
_ENTRY_SPANS: tuple[tuple[str, str], ...] = (
    ("duty_sign", "duty_sign_seconds"),
    ("witness_verify", "witness_verify_seconds"),
    ("transition_", "epoch_transition_seconds"),
    ("chain_", "attestation_batch_verify_seconds"),
    ("pair_", "attestation_batch_verify_seconds"),
    ("shard_", "ops_shard_combine_seconds"),
)


def _span_family(entry: str) -> str | None:
    for prefix, family in _ENTRY_SPANS:
        if entry.startswith(prefix):
            return family
    return None


def _family_totals(metrics, family: str) -> tuple[float, int]:
    """Cumulative (seconds, observations) over every series of one
    histogram family."""
    total_s = 0.0
    total_n = 0
    for _labels, _bounds, _counts, h_sum, h_count in metrics.histogram_series(
        family
    ):
        total_s += h_sum
        total_n += h_count
    return total_s, total_n


def _default_device_kind() -> str | None:
    if "jax" not in sys.modules:
        return None
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:
        return None


def entry_report(metrics=None) -> list[dict]:
    """One row per entry point: FLOP/byte attribution from the cost table
    times the call counts, code/temp bytes, and the span family and SLO
    that govern its dispatches.  Ranked by cumulative FLOPs, most first.
    No achieved rate and no ratio to a peak: compile-time FLOPs over host
    span seconds say nothing about the device — its timeline does (a
    ``capture_trace`` window, reduced as ``benchmark/tracered.py`` does)."""
    from ..slo import slos_for_family
    from .aot import compile_profile

    m = metrics if metrics is not None else get_metrics()

    calls: dict[tuple[str, str], int] = {}
    for row in compile_profile():
        calls[(row["entry"], row["signature"])] = row["hits"] + row["misses"]

    with _LOCK:
        costs = [dict(r) for r in _COSTS.values()]
    entries: dict[str, dict] = {}
    for c in costs:
        key = (c["entry"], c["signature"])
        n = calls.get(key, 0)
        e = entries.setdefault(
            c["entry"],
            {
                "entry": c["entry"],
                "signatures": 0,
                "calls": 0,
                "flops_total": 0.0,
                "bytes_total": 0.0,
                "flops_per_call_max": 0.0,
                "code_bytes": 0,
                "temp_bytes": 0,
            },
        )
        e["signatures"] += 1
        e["calls"] += n
        e["flops_total"] += (c["flops"] or 0.0) * n
        e["bytes_total"] += (c["bytes_accessed"] or 0.0) * n
        e["flops_per_call_max"] = max(e["flops_per_call_max"], c["flops"] or 0.0)
        e["code_bytes"] += c["code_bytes"]
        e["temp_bytes"] += c["temp_bytes"]

    span_cache: dict[str, tuple[float, int]] = {}
    for e in entries.values():
        family = _span_family(e["entry"])
        e["span_family"] = family
        e["span_seconds"] = e["span_count"] = None
        e["slo"] = None
        if family is None:
            continue
        slos = slos_for_family(family)
        if slos:
            e["slo"] = {"name": slos[0].name, "budget": slos[0].budget}
        if family not in span_cache:
            span_cache[family] = _family_totals(m, family)
        span_s, span_n = span_cache[family]
        e["span_seconds"] = round(span_s, 6)
        e["span_count"] = span_n

    ranked = sorted(entries.values(), key=lambda e: -e["flops_total"])
    for i, e in enumerate(ranked, 1):
        e["rank"] = i
    return ranked


# the process-wide counter cursors: ops_entry_*_total must expose as
# counters (rate() semantics), so emission publishes deltas against the
# last emitted cumulative value instead of re-setting a gauge
_EMITTED_TOTALS: dict[str, tuple[float, float]] = {}


def emit_entry_metrics(metrics=None) -> None:
    """Publish the per-entry counter deltas ``ops_entry_flops_total`` /
    ``ops_entry_bytes_total``.  Called from the node tick (gated on this
    module already being imported) — idempotent across co-resident nodes
    because the cursors are process-wide."""
    m = metrics if metrics is not None else get_metrics()
    if not m.enabled:
        return
    for e in entry_report(metrics=m):
        name = e["entry"]
        # cursor read-modify-write under _LOCK: co-resident node ticks
        # share the process-wide cursors, and an unlocked race would
        # publish the same delta twice (counters overstate dispatched
        # work by the number of racing ticks)
        with _LOCK:
            prev_f, prev_b = _EMITTED_TOTALS.get(name, (0.0, 0.0))
            d_flops = max(0.0, e["flops_total"] - prev_f)
            d_bytes = max(0.0, e["bytes_total"] - prev_b)
            # monotonic cursor: a tick holding a STALE report (computed
            # before a concurrent tick's newer emission) must not rewind
            # the cursor, or the next tick would re-publish the newer
            # tick's already-emitted delta
            _EMITTED_TOTALS[name] = (
                max(prev_f, e["flops_total"]),
                max(prev_b, e["bytes_total"]),
            )
        if d_flops > 0:
            m.inc("ops_entry_flops_total", d_flops, entry=name)
        if d_bytes > 0:
            m.inc("ops_entry_bytes_total", d_bytes, entry=name)


# --------------------------------------------------- per-plane accounting


class PlaneRegistry:
    """Named byte providers for everything that retains device (or
    host-pinned) buffers.  ``snapshot(total)`` resolves every provider
    and derives the ``unattributed`` remainder from the device-flagged
    planes, tracking the total's high watermark.  The ``device`` flag
    means "these bytes are part of the ``jax.live_arrays()`` total the
    remainder is derived from" — planes holding memory OUTSIDE that
    total (host numpy rows, compiled program code/temps) register
    ``device=False`` so they report as their own series without
    corrupting the remainder arithmetic.  A provider that raises
    reports 0 for that snapshot — accounting must never take down the
    tick loop."""

    def __init__(self):
        self._lock = threading.Lock()
        # name -> (provider, device, devices); ``devices`` is an optional
        # callable answering how many mesh devices the plane's buffers
        # are SPREAD over (1 = replicated/unsharded) — read from live
        # buffer shardings, so the round-21 per-device accounting never
        # claims a split that placement fell back from
        self._planes: dict[str, tuple] = {}
        self._watermark = 0.0

    def register(self, name: str, provider, device: bool = True,
                 devices=None) -> None:
        if not callable(provider):
            raise TypeError(f"plane {name!r} provider must be callable")
        if devices is not None and not callable(devices):
            raise TypeError(f"plane {name!r} devices must be callable")
        with self._lock:
            self._planes[name] = (provider, bool(device), devices)

    def unregister(self, name: str) -> None:
        with self._lock:
            self._planes.pop(name, None)

    def names(self) -> tuple[str, ...]:
        with self._lock:
            return tuple(sorted(self._planes))

    def snapshot(self, total_bytes: float | None = None) -> dict[str, float]:
        with self._lock:
            items = list(self._planes.items())
        out: dict[str, float] = {}
        attributed = 0.0
        for name, (provider, device, _devices) in items:
            try:
                nbytes = float(provider() or 0.0)
            except Exception:
                nbytes = 0.0
            out[name] = nbytes
            if device:
                attributed += nbytes
        if total_bytes is not None:
            total = float(total_bytes)
            out["unattributed"] = max(0.0, total - attributed)
            with self._lock:
                self._watermark = max(self._watermark, total)
        return out

    def shard_devices(self) -> dict[str, int]:
        """name -> live device spread for every plane that registered a
        ``devices`` provider (others report 1).  A provider that raises
        reports 1 — same never-take-down-the-tick contract as byte
        providers."""
        with self._lock:
            items = list(self._planes.items())
        out: dict[str, int] = {}
        for name, (_provider, _device, devices) in items:
            n = 1
            if devices is not None:
                try:
                    n = max(1, int(devices() or 1))
                except Exception:
                    n = 1
            out[name] = n
        return out

    @property
    def watermark(self) -> float:
        with self._lock:
            return self._watermark


_REGISTRY = PlaneRegistry()

# entry-prefix planes: an AOT entry family whose executables are
# accounted as their own plane (the duty-sign ladders) instead of under
# the shared "aot_executables" remainder
_ENTRY_PLANES: dict[str, str] = {}  # plane name -> entry prefix


def register_plane(name: str, provider, device: bool = True,
                   devices=None) -> None:
    """Register a retained-bytes provider on the default registry;
    ``devices`` optionally reports how many mesh devices the plane's
    buffers are spread over (round-21 sharded residency)."""
    _REGISTRY.register(name, provider, device=device, devices=devices)


def unregister_plane(name: str) -> None:
    _REGISTRY.unregister(name)


def entry_plane_bytes(prefix: str) -> int:
    """Device footprint (program code + preallocated temps) of every
    cost-table executable whose entry starts with ``prefix``."""
    with _LOCK:
        return sum(
            r["code_bytes"] + r["temp_bytes"]
            for (entry, _sig), r in _COSTS.items()
            if entry.startswith(prefix)
        )


def register_entry_plane(name: str, prefix: str) -> None:
    """Account one AOT entry family as its own named plane; its rows are
    excluded from the shared ``aot_executables`` plane so nothing
    double-counts.  Program code/temp bytes live in device memory but
    are NOT ``jax.live_arrays()`` entries, so executable planes register
    ``device=False`` — subtracting them from the live-array total would
    under-report (or zero-clamp) the ``unattributed`` remainder."""
    _ENTRY_PLANES[name] = prefix
    register_plane(name, lambda: entry_plane_bytes(prefix), device=False)


def _unclaimed_executable_bytes() -> int:
    prefixes = tuple(_ENTRY_PLANES.values())
    with _LOCK:
        return sum(
            r["code_bytes"] + r["temp_bytes"]
            for (entry, _sig), r in _COSTS.items()
            if not (prefixes and entry.startswith(prefixes))
        )


def plane_bytes(total_bytes: float | None = None) -> dict[str, float]:
    """Resolve every registered plane (plus ``unattributed`` when the
    live total is supplied) — the node tick's ``device_plane_bytes``
    source."""
    return _REGISTRY.snapshot(total_bytes)


def plane_shard_devices() -> dict[str, int]:
    """name -> live mesh-device spread per plane (1 = unsharded) — the
    shard-aware ``device_plane_bytes`` divisor."""
    return _REGISTRY.shard_devices()


def plane_watermark() -> float:
    """High watermark of the live-total bytes ever snapshotted."""
    return _REGISTRY.watermark


def live_device_bytes() -> float | None:
    """Total bytes pinned by live device arrays, or ``None`` when jax
    was never imported (a pure-host node must not pay the import for an
    accounting sample)."""
    if "jax" not in sys.modules:
        return None
    try:
        import jax

        return float(
            sum(getattr(a, "nbytes", 0) for a in jax.live_arrays())
        )
    except Exception:
        return None


# --------------------------------------------------------- trace capture

_CAPTURE_LOCK = threading.Lock()  # one capture at a time, process-wide
_CAPTURE_STATE: dict = {"running": False, "last": None}


def capture_budget() -> tuple[float, float]:
    """(max seconds, max MB) for one on-demand capture —
    ``PROFILE_CAPTURE_MAX_S`` (default 10) / ``PROFILE_CAPTURE_MAX_MB``
    (default 128)."""
    try:
        max_s = float(os.environ.get("PROFILE_CAPTURE_MAX_S", "") or 10.0)
    except ValueError:
        max_s = 10.0
    try:
        max_mb = float(os.environ.get("PROFILE_CAPTURE_MAX_MB", "") or 128.0)
    except ValueError:
        max_mb = 128.0
    return max_s, max_mb


def capture_state() -> dict:
    max_s, max_mb = capture_budget()
    with _LOCK:
        last = (
            dict(_CAPTURE_STATE["last"])
            if _CAPTURE_STATE["last"] is not None
            else None
        )
        running = _CAPTURE_STATE["running"]
    return {
        "max_seconds": max_s,
        "max_mb": max_mb,
        "running": running,
        "last": last,
    }


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(path):
        for fname in files:
            try:
                total += os.path.getsize(os.path.join(root, fname))
            except OSError:
                pass
    return total


def _default_capture_dir() -> str:
    d = os.environ.get("PROFILE_CAPTURE_DIR")
    if d:
        return d
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(repo, ".profile_captures")


def capture_trace(seconds: float, out_dir: str | None = None, tracer=None) -> dict:
    """One budgeted ``jax.profiler`` capture window.

    Refuses BEFORE tracing when ``seconds`` exceeds the time budget (an
    oversized window must not start eating the device), deletes the
    capture and raises when the written trace exceeds the byte budget.
    Runs synchronously — callers own the threading (the API route runs
    it on a worker thread per the round-10 executor discipline).  For the
    length of the capture every ``telemetry`` span also writes a
    ``span:<name>`` annotation into the trace (``telemetry.annotate_spans``),
    so the device's idle gaps can be laid to host work.
    ``tracer`` is a test seam defaulting to ``jax.profiler``."""
    max_s, max_mb = capture_budget()
    m = get_metrics()
    seconds = float(seconds)
    if not seconds > 0.0:
        raise ValueError(f"capture seconds must be positive, got {seconds!r}")
    if seconds > max_s:
        m.inc("profile_captures_total", result="refused")
        raise ValueError(
            f"capture of {seconds:g}s exceeds the PROFILE_CAPTURE_MAX_S="
            f"{max_s:g} budget — refused before tracing"
        )
    if not _CAPTURE_LOCK.acquire(blocking=False):
        m.inc("profile_captures_total", result="busy")
        raise ValueError("a profiler capture is already running")
    try:
        with _LOCK:
            _CAPTURE_STATE["running"] = True
        if tracer is None:
            import jax.profiler as tracer  # deferred: host nodes stay jax-free
        path = os.path.join(
            out_dir or _default_capture_dir(),
            time.strftime("capture-%Y%m%d-%H%M%S")
            + f"-{int(time.time() * 1e3) % 1000:03d}",
        )
        os.makedirs(path, exist_ok=True)
        rec = get_recorder()
        rec.record(
            "inst", 0, "profile_capture_start",
            {"dir": path, "budget_s": round(seconds, 3)},
        )
        t0 = time.perf_counter()
        try:
            # the program's spans ride the capture as ``span:<name>``
            # annotations: on before the trace starts, off after it stops
            annotate_spans(tracer.TraceAnnotation)
            try:
                tracer.start_trace(path)
                try:
                    time.sleep(seconds)
                finally:
                    tracer.stop_trace()
            finally:
                annotate_spans(None)
        except Exception:
            m.inc("profile_captures_total", result="error")
            # close the window on the /debug/trace timeline even on a
            # failed capture — a dangling start instant would render as
            # a capture that never ends in the Perfetto export
            rec.record(
                "inst", 0, "profile_capture_stop",
                {"dir": path, "error": True,
                 "seconds": round(time.perf_counter() - t0, 3)},
            )
            raise
        dt = time.perf_counter() - t0
        rec.record(
            "inst", 0, "profile_capture_stop",
            {"dir": path, "seconds": round(dt, 3)},
        )
        m.observe("profile_capture_seconds", dt)
        nbytes = _dir_bytes(path)
        if nbytes > max_mb * (1 << 20):
            shutil.rmtree(path, ignore_errors=True)
            m.inc("profile_captures_total", result="over_budget")
            raise ValueError(
                f"capture wrote {nbytes} bytes, over the "
                f"PROFILE_CAPTURE_MAX_MB={max_mb:g} budget — trace deleted"
            )
        m.inc("profile_captures_total", result="ok")
        last = {
            "dir": path,
            "seconds": round(dt, 3),
            "bytes": nbytes,
            "at": time.time(),
        }
        with _LOCK:
            _CAPTURE_STATE["last"] = last
        return dict(last)
    finally:
        with _LOCK:
            _CAPTURE_STATE["running"] = False
        _CAPTURE_LOCK.release()


# -------------------------------------------------------------- reporting


def profile_report(metrics=None, total_bytes: float | None = None) -> dict:
    """The ``/debug/profile`` payload: the per-entry cost table, plane
    accounting, the device's published peaks and capture state in one
    snapshot."""
    kind = _default_device_kind()
    if total_bytes is None:
        total_bytes = live_device_bytes()
    return {
        "device_kind": kind,
        "peaks": device_peaks(kind),
        "entries": entry_report(metrics=metrics),
        "planes": plane_bytes(total_bytes),
        "live_device_bytes": total_bytes,
        "plane_watermark_bytes": plane_watermark(),
        "capture": capture_state(),
    }


# the shared-executables plane: every cost-table program not claimed by
# a named entry plane (duty-sign registers its own) — registered at
# import so any process that compiles through ops/aot.py accounts its
# program footprint without further wiring.  device=False: program
# code/temp bytes are device-resident but never appear in the
# jax.live_arrays() total the unattributed remainder is derived from.
register_plane("aot_executables", _unclaimed_executable_bytes, device=False)

# The rest of the shipped plane set starts as zero-byte placeholders so
# the device_plane_bytes cardinality is stable from the first tick: a
# subsystem that never loaded retains nothing, and the moment it DOES
# load it re-registers the same name with its real provider (bls_batch,
# state_transition/resident, witness/service, ops/bls_sign).  Dashboards
# and the acceptance contract therefore always resolve the full named
# set plus the unattributed remainder.
register_plane("registry_planes", lambda: 0.0)
register_plane("resident_epoch", lambda: 0.0)
register_plane("witness_buffers", lambda: 0.0, device=False)
register_entry_plane("duty_sign_ladders", "duty_sign")
