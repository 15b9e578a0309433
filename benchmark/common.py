"""benchmark/common.py — what every generator and reader shares on the
main process's side: the run's failure type, the earlier output lines, the
compile clock, the host-side workers, and snapshots of the program's span
histograms and counters."""

from __future__ import annotations

import json
import os
import pickle
import queue
import struct
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchFailure(Exception):
    """The run cannot show what the configuration guarantees."""


def expect(cond, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


def hold(compared: dict, name: str, over, what: str) -> None:
    """An exact comparison: book ``over`` (how far the run is off) beside its
    limit 0 among the numbers compared, then hold the run to it."""
    compared[name] = [over, 0]
    expect(over == 0, what)


def note(**fields) -> None:
    """One earlier line of standard output (never the last one)."""
    print(json.dumps({"note": fields}, default=str), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def quantile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank quantile of an already sorted list."""
    i = min(len(sorted_values) - 1, max(0, int(round(q * (len(sorted_values) - 1)))))
    return sorted_values[i]


class CompileClock:
    """JAX's own trace/lower/compile events (every jit, AOT-wrapped or
    not), persistent-cache hits, and the AOT tier's loads and lowers — so
    set-up can be split, and a compile inside the window is counted.
    Copied from ``chip_smoke.py`` with the event counts added."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __init__(self):
        import jax.monitoring as mon

        self.t = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0}
        self.n = {"backend_compiles": 0, "jax_cache_hits": 0, "jax_cache_misses": 0}
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._evt)

    def _dur(self, name, secs, **_kw):
        key = self.EVENTS.get(name)
        if key:
            self.t[key] += secs
            if key == "compile_s":
                self.n["backend_compiles"] += 1

    def _evt(self, name, **_kw):
        if name.endswith("/cache_hits"):
            self.n["jax_cache_hits"] += 1
        elif name.endswith("/cache_misses"):
            self.n["jax_cache_misses"] += 1

    def snapshot(self) -> dict:
        from lambda_ethereum_consensus_tpu.ops.aot import aot_stats, compile_profile

        stats = aot_stats()
        return {
            **self.t, **self.n,
            "aot_loads": stats["loads"],
            "aot_lowers": stats["retraces"],
            "aot_saves": stats["saves"],
            "aot_load_s": sum(r["load_seconds"] for r in compile_profile()),
        }

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in b}


class Worker:
    """One ``hostside.py`` child: JSON commands in, pickled frames out.  A
    reader thread empties the pipe as fast as the child fills it, so the
    child never waits on this process's event loop."""

    def __init__(self, role: str, config: str, traffic: str, seed: int,
                 genesis_time: int, rehearse: bool):
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        argv = [sys.executable, os.path.join(HERE, "hostside.py"), "--role", role,
                "--config", config, "--traffic", traffic, "--seed", str(seed),
                "--genesis-time", str(genesis_time)]
        if rehearse:
            argv.append("--rehearse")
        self.role = role
        self.proc = subprocess.Popen(
            argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, cwd=ROOT)
        self.frames: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self) -> None:
        out = self.proc.stdout
        while True:
            head = out.read(8)
            if len(head) < 8:
                self.frames.put({"kind": "eof"})
                return
            (size,) = struct.unpack("<Q", head)
            frame = pickle.loads(out.read(size))
            frame["received"] = time.perf_counter()
            self.frames.put(frame)

    def send(self, **cmd) -> None:
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        self.proc.stdin.flush()

    def take(self, kind: str, timeout: float) -> dict:
        """The next frame, which has to be of ``kind``."""
        try:
            frame = self.frames.get(timeout=timeout)
        except queue.Empty:
            raise BenchFailure(
                f"{self.role} worker: no {kind!r} frame within {timeout:.0f} s") from None
        expect(frame["kind"] == kind,
               f"{self.role} worker sent {frame['kind']!r}, {kind!r} expected"
               + (f" (exit code {self.proc.poll()})" if frame["kind"] == "eof" else ""))
        return frame

    def close(self) -> None:
        """Stop the child and wait until it has ended."""
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self._thread.join(timeout=5)


def histogram_totals(*registries) -> dict:
    """``{family: [sum, count]}`` over every label set of every histogram
    family of the program's registries.  Read as ``_sum``/``_count`` only:
    the bucket quantiles are too coarse for a tail."""
    totals: dict[str, list[float]] = {}
    for reg in registries:
        for name in reg.family_names():
            for _labels, _bounds, _counts, total, count in reg.histogram_series(name):
                row = totals.setdefault(name, [0.0, 0])
                row[0] += total
                row[1] += count
    return totals


def counter_total(name: str, *registries) -> float:
    """One counter family summed over its series, read off the Prometheus
    exposition the node serves (as ``chip_smoke.py`` reads it)."""
    total = 0.0
    for reg in registries:
        for line in reg.render_prometheus(self_scrape=False).splitlines():
            if line.startswith(name) and line[len(name)] in " {":
                total += float(line.rsplit(" ", 1)[1])
    return total
