"""benchmark/session.py — one run: boot the node, hand it to the traffic
generator, keep the window's books, hold the run to the configuration's
guarantees and put the contract's last line together.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is data found by name (``configs/``, ``traffic/``,
``layers/``) or a module found by name (``generators/``, ``readers/``).
"""

from __future__ import annotations

import asyncio
import contextlib
import importlib
import os
import shutil
import tempfile
import threading
import time

import hostside
import tracered
from common import (
    HERE, ROOT, BenchFailure, CompileClock, Worker, counter_total, expect,
    histogram_totals, hold, load_json, note,
)

ERROR_COUNTERS = ("gossip_batch_error_count", "aot_errors_total", "device_fault_total")


class Window:
    """The measured window's books: when it opened and closed, what the
    program's spans and the compile clock read at both ends, and — in a
    traced run — the profiler's trace of its first stretch, cut at an item
    boundary so that the traced stretch holds whole items."""

    def __init__(self, ctx: "Context"):
        self.ctx = ctx
        self.setup_s = self.t_open = self.t_close = None
        self.spans0 = self.spans1 = self.clock0 = self.clock1 = None
        self.trace = None  # tracered.reduce_trace's result
        self.trace_dir = None
        self.traced = None  # {"t0","t1","items"}: the traced stretch
        self._tracing = False
        self._window_span = None

    def open(self) -> None:
        ctx = self.ctx
        if ctx.args.trace:
            self._start_trace()
        self.spans0 = histogram_totals(*ctx.registries())
        self.clock0 = ctx.clock.snapshot()
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - ctx.t_process

    def close(self) -> None:
        self.t_close = time.perf_counter()
        self.spans1 = histogram_totals(*self.ctx.registries())
        self.clock1 = self.ctx.clock.snapshot()
        if self._tracing:
            self.stop_trace(items=None)
        if self.trace_dir is not None:
            self._reduce_trace()

    def over(self) -> bool:
        return time.perf_counter() - self.t_open >= self.ctx.args.seconds

    # ----------------------------------------------------------- tracing

    def _start_trace(self) -> None:
        import jax

        annotate_spans()
        self.trace_dir = os.path.join(self.ctx.workdir, "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the program's spans are annotated instead
        opts.host_tracer_level = 1  # TraceAnnotations, not the runtime's own events
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True
        self._window_span = jax.profiler.TraceAnnotation(tracered.WINDOW_SPAN)
        self._window_span.__enter__()
        self.traced = {"t0": time.perf_counter(), "t1": None, "items": None}

    def item_boundary(self, items: int) -> None:
        """The generator calls this when an item (a burst, a block) is
        whole and nothing is in flight: the trace stops at the first such
        moment after the mix's ``trace_seconds``."""
        if self._tracing and (time.perf_counter() - self.traced["t0"]
                              >= float(self.ctx.mix.get("trace_seconds", 5))):
            self.stop_trace(items)

    def stop_trace(self, items: int | None) -> None:
        import jax

        self._window_span.__exit__(None, None, None)
        self.traced["t1"] = time.perf_counter()
        self.traced["items"] = items
        jax.profiler.stop_trace()
        self._tracing = False

    def _reduce_trace(self) -> None:
        """After the window has closed: parsing a trace takes seconds."""
        t0 = time.perf_counter()
        path = tracered.find_xplane(self.trace_dir)
        expect(path is not None, "the profiler wrote no .xplane.pb")
        self.trace = tracered.reduce_trace(path)
        note(trace={"file_bytes": os.path.getsize(path),
                    "reduce_s": time.perf_counter() - t0,
                    "traced_s": self.traced["t1"] - self.traced["t0"],
                    "traced_items": self.traced["items"], "devices": self.trace["devices"],
                    "busy_s": self.trace["busy_s"], "planes": self.trace["planes"],
                    "modules": self.trace["modules"][:12]})

    # ------------------------------------------------------------- reads

    def span_delta(self, family: str) -> tuple[float, float]:
        """``(sum, count)`` a histogram family gained inside the window."""
        a = self.spans0.get(family, (0.0, 0))
        b = self.spans1.get(family, (0.0, 0))
        return b[0] - a[0], b[1] - a[1]


_ANNOTATED = False


def annotate_spans() -> None:
    """Traced runs only: every program span (``telemetry.span``) also
    writes a ``TraceAnnotation`` named ``bench:<span>`` into the profiler's
    trace, so an idle gap on the device can be laid to what the host was
    doing.  Done from here, around the program's own span class; the
    program is not changed and untraced runs are not touched."""
    global _ANNOTATED
    if _ANNOTATED:
        return
    _ANNOTATED = True
    import jax

    from lambda_ethereum_consensus_tpu import telemetry

    span_cls = telemetry._Span
    enter, leave = span_cls.__enter__, span_cls.__exit__
    local = threading.local()

    def traced_enter(self):
        ann = jax.profiler.TraceAnnotation(tracered.SPAN_PREFIX + self._name)
        ann.__enter__()
        stack = getattr(local, "stack", None)
        if stack is None:
            stack = local.stack = []
        stack.append(ann)
        return enter(self)

    def traced_exit(self, exc_type, exc, tb):
        out = leave(self, exc_type, exc, tb)
        stack = getattr(local, "stack", None)
        if stack:
            stack.pop().__exit__(None, None, None)
        return out

    span_cls.__enter__, span_cls.__exit__ = traced_enter, traced_exit


class Context:
    """What a generator and a reader may touch."""

    def __init__(self, args, bench: dict, cell: dict, cfg: dict, mix: dict,
                 t_process: float):
        self.args, self.bench, self.cell, self.cfg = args, bench, cell, cfg
        self.t_process = t_process
        self.rehearse = bool(args.rehearse)
        # the rehearsal's sizes lie beside the real ones, in the same files
        self.mix = {**mix, **mix.get("rehearse", {})} if self.rehearse else mix
        self.size = cfg["rehearse"] if self.rehearse else cfg
        self.spec, self.n_validators = hostside.chain_spec(cfg, self.rehearse)
        self.sec_per_slot = int(self.spec.SECONDS_PER_SLOT)
        self.genesis_time = (int(time.time())
                             - int(cfg["genesis_slots_back"]) * self.sec_per_slot)
        self.workdir = tempfile.mkdtemp(prefix="bench_")
        self.clock = CompileClock()
        self.window = Window(self)
        self.workers: list[Worker] = []
        self.node = self.store = self.anchor = None
        self.verdicts: dict[bytes, tuple[int, float]] = {}
        self.setup_split: dict[str, float] = {}
        # every number `correct` compares, beside its limit: {name: [value, limit]}
        self.compared: dict[str, list] = {}
        self.config_path = os.path.join(ROOT, self.config_entry()["file"])
        self.traffic_path = os.path.join(HERE, "traffic", cell["traffic"] + ".json")

    def config_entry(self) -> dict:
        return next(c for c in self.bench["configs"] if c["name"] == self.cell["config"])

    def worker(self, role: str) -> Worker:
        w = Worker(role, self.config_path, self.traffic_path, self.args.seed,
                   self.genesis_time, self.rehearse)
        self.workers.append(w)
        return w

    def registries(self):
        from lambda_ethereum_consensus_tpu import telemetry

        return (telemetry.get_metrics(), self.node.metrics)

    def current_slot(self) -> int:
        return self.store.current_slot(self.spec)

    def mark(self, name: str, since: float) -> float:
        """Book ``name`` in the set-up split; returns now."""
        now = time.perf_counter()
        self.setup_split[name] = round(now - since, 3)
        return now

    def subscription(self, label: str):
        return next(s for s in self.node._subs if s.topic_label == label)

    def hook_verdicts(self) -> None:
        """Time every verdict as the node hands it to the sidecar — the
        client's side of feed -> verdict."""
        port_validate = self.node.port.validate_message
        verdicts = self.verdicts

        async def record_verdict(msg_id, verdict):
            verdicts[msg_id] = (verdict, time.perf_counter())
            await port_validate(msg_id, verdict)

        self.node.port.validate_message = record_verdict


async def boot(ctx: Context) -> None:
    """Process start -> a started node on the configuration's registry,
    its anchor root held to the host's ``hashlib`` root."""
    from lambda_ethereum_consensus_tpu.node import BeaconNode, NodeConfig
    from lambda_ethereum_consensus_tpu.node.warmup import DrainShapes

    t = time.perf_counter()
    ctx.setup_split["imports_native_s"] = round(t - ctx.t_process, 3)
    spec, n = ctx.spec, ctx.n_validators
    _spec, _keys, genesis = hostside.build_genesis(
        ctx.cfg, ctx.args.seed, ctx.genesis_time, ctx.rehearse)
    t = ctx.mark("state_build_s", t)
    spe = int(spec.SLOTS_PER_EPOCH)
    cps = hostside.committees_per_slot(spec, n)
    ctx.committees_per_slot, ctx.committee_size = cps, n // (spe * cps)
    shapes = None
    warm = ctx.size.get("warm_drain")
    if warm:  # only a cell whose traffic drains gossip warms the drain programs
        shapes = DrainShapes(
            n_validators=n, n_committees=cps * spe, committee=ctx.committee_size,
            entries=cps * int(warm["aggregators_per_committee"]), groups=cps)
    node = BeaconNode(NodeConfig(
        db_path=os.path.join(ctx.workdir, "beacon.wal"), genesis_state=genesis,
        enable_range_sync=False, warm_drain_shapes=shapes))
    ctx.node = node
    await node.start()
    t = ctx.mark("node_start_s", t)
    expect(ctx.rehearse or node.device_backend is not None, "device paths are not ON")
    if shapes is not None and node._warmer is not None:
        await asyncio.get_running_loop().run_in_executor(None, node._warmer.join)
        expect("error" not in node.warmer_stats,
               f"warmer failed: {node.warmer_stats.get('error')}")
        t = ctx.mark("warmer_wait_s", t)
    expect(node.kv.native, "KV store fell back to the Python engine")
    ctx.store = node.store
    ctx.anchor_root = next(iter(ctx.store.blocks))
    ctx.hook_verdicts()


def check_anchor(ctx: Context, anchor: dict) -> None:
    device_root = bytes(ctx.store.blocks[ctx.anchor_root].state_root)
    expect(device_root == anchor["state_root"],
           "anchor state root: device != hashlib (host lineage)")
    expect(ctx.anchor_root == anchor["block_root"], "anchor block root differs")


def no_quiet_fallback(ctx: Context) -> dict:
    """Fault latch clear, error counters zero, every AOT row loaded or
    compiled, device committee caches built (``chip_smoke.py`` phase 5)."""
    from lambda_ethereum_consensus_tpu import telemetry
    from lambda_ethereum_consensus_tpu.fork_choice import attestation as FA
    from lambda_ethereum_consensus_tpu.ops import aot

    fault = telemetry.device_fault_state()
    hold(ctx.compared, "device_fault_latched", int(bool(fault["faulted"])),
         f"device fault latched: {fault}")
    for name in ERROR_COUNTERS:
        total = counter_total(name, *ctx.registries())
        hold(ctx.compared, name, total, f"{name} = {total}")
    rows = aot.compile_profile()
    expect(all(r["source"] in ("disk", "compile") for r in rows),
           "an AOT row was neither loaded nor compiled")
    called: dict[str, int] = {}
    for r in rows:
        called[r["entry"]] = called.get(r["entry"], 0) + r["hits"] + r["misses"]
    if not ctx.rehearse:  # interpret mode jits no chain
        for fam in ctx.mix.get("device_families", ()):
            expect(any(e.startswith(fam) and c > 0 for e, c in called.items()),
                   f"no {fam}* program was dispatched: the device path did not run")
    ctxs = list(ctx.store.attestation_contexts.values()) + list(FA._STATE_CTX.values())
    expect(all(c._device_cache is not None for c in ctxs) or ctx.rehearse,
           "an attestation context has no device committee cache")
    return {"aot_rows": len(rows),
            "aot_entries_called": sorted(e for e, c in called.items() if c)}


def device_info(ctx: Context) -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}
    w = ctx.window
    if ctx.args.trace and w.trace is not None:
        info["busy_s"] = w.trace["busy_s"]
        info["window_s"] = w.traced["t1"] - w.traced["t0"]
    return info


def layer_metrics(ctx: Context, facts: dict) -> dict:
    """Every per-layer metric of this cell: ``layers/<metric>.json`` names a
    reader module and its arguments; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for entry in ctx.bench["per_layer"]:
        cells = entry.get("workloads")
        if cells is not None and ctx.cell["name"] not in cells:
            continue
        spec = load_json("layers", entry["name"] + ".json")
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(ctx.window, facts, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


async def run(args, bench: dict, cell: dict, cfg: dict, mix: dict, t_process: float) -> dict:
    from lambda_ethereum_consensus_tpu.config import use_chain_spec

    ctx = Context(args, bench, cell, cfg, mix, t_process)
    generator = importlib.import_module("generators." + mix["generator"])
    try:
        with use_chain_spec(ctx.spec):
            lineage = ctx.worker("lineage")
            generator.start_workers(ctx)
            await boot(ctx)
            t = time.perf_counter()
            ctx.anchor = lineage.take("anchor", 900)
            ctx.mark("anchor_wait_s", t)
            check_anchor(ctx, ctx.anchor)
            facts = await generator.run(ctx, lineage)
            checks = no_quiet_fallback(ctx)
            w = ctx.window
            split = CompileClock.delta(w.clock0, w.clock1)
            note(setup_split=ctx.setup_split, setup_compile_clock=w.clock0,
                 window_compile_clock=split, checks=checks,
                 worker_host={"state_build_s": ctx.anchor["build_s"],
                              "hashlib_root_s": ctx.anchor["root_s"]})
            e2e = dict(facts.pop("end_to_end"))
            e2e["setup_s"] = w.setup_s
            units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
            if args.trace:
                metrics = layer_metrics(ctx, facts)
            else:
                metrics = {k: {"value": v, "unit": units[k]} for k, v in e2e.items()}
            result = {
                "correct": True, "attempted": facts["attempted"],
                "failed": facts["failed"], "metrics": metrics,
                "device": device_info(ctx),
            }
            if args.trace and w.trace is not None:
                result["breakdown"] = {"device_ops": w.trace["ops"][:10],
                                       "idle_gaps": w.trace["gaps"][:10]}
            if ctx.rehearse:  # a CPU number never stands under a device metric's name
                result["rehearsal"] = True
                for m in metrics.values():
                    m["value"] = None
            if facts["failed"]:
                result["correct"] = False
            ctx.compared["failed"] = [facts["failed"], 0]
            result["compared"] = ctx.compared  # last in the line
            return result
    except BenchFailure as e:
        e.compared = ctx.compared  # what was compared before the run failed
        raise
    finally:
        for w in ctx.workers:
            w.close()
        if ctx.node is not None:
            with contextlib.suppress(Exception):
                await asyncio.wait_for(ctx.node.stop(), 60)
        shutil.rmtree(ctx.workdir, ignore_errors=True)
