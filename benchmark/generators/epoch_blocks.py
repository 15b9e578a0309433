"""generators/epoch_blocks.py — range delivery of signed blocks across an
epoch boundary.

``range_blocks``' traffic (its ``RangePeer``, ``Frames``, ``block_plan`` and
the count of cached-chain entries, by import) with the round's first block
the first of a new epoch, so that the node runs ``process_epoch`` inside the
window.  Parameters as ``range_blocks``': ``warmup_slots`` (the first crosses
the boundaries above the anchor and pays what a process pays once; the last
is the window's parent), ``first_slot`` (the first slot of an epoch) and
``blocks``, ``attestation_slots_back``, ``participation``, ``trace_seconds``.

What differs from ``range_blocks``:

- the plain reference is ``plainref_epoch.py`` (``plainref.py`` with
  ``process_epoch`` followed), a child that imports nothing of the program;
  every window block's root and post-state root, the boundary block's
  included, are held to it before the window opens, and the persisted
  post-state after it;
- a run is also held to the resident epoch plane: inside the window the
  lineage's plane gains one sweep per boundary crossed and no fallback
  (``epoch_not_through_resident_plane``), the program books one
  ``epoch_transition`` span per boundary, and nothing is compiled, lowered
  or loaded (``window_compiles``);
- the counters ``state_root_rebuilt_chunks_total`` and
  ``state_encode_fields_total`` are noted block by block (no metric reads
  them: PERF.md section 3).
"""

from __future__ import annotations

import os
import queue
import subprocess
import sys
import threading
import time

from common import HERE, CompileClock, expect, hold, note
from generators.range_blocks import (
    Frames, PlainReference, RangePeer, block_plan, count_cached_entries,
)

NOTED_COUNTERS = ("state_root_rebuilt_chunks_total", "state_encode_fields_total")


class EpochReference(PlainReference):
    """``plainref_epoch.py`` as a child, spoken to as ``PlainReference``."""

    def __init__(self, preset: str, seconds_per_slot: int):
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "plainref_epoch.py"), preset,
             str(seconds_per_slot)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, cwd=HERE)
        self.answers: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()


def require_program_support() -> None:
    """A program that cannot keep the configuration's guarantees fails here,
    at once and with another exit code than 0, before anything is booted: a
    node that is given no drain shapes has to warm the resident plane's
    programs itself (else the window's boundary compiles its delta scatters),
    and the boundary's stages have to be spans (``correct`` and the cell's
    per-layer metrics read them)."""
    from lambda_ethereum_consensus_tpu import telemetry
    from lambda_ethereum_consensus_tpu.node import warmup

    lacks = [name for name, there in (
        ("node.warmup.start_transition_warmer", hasattr(warmup, "start_transition_warmer")),
        ("span epoch_plane_sync", "epoch_plane_sync_seconds" in telemetry._HELP),
        ("span state_root_incremental", "state_root_incremental_seconds" in telemetry._HELP),
    ) if not there]
    if lacks:
        raise RuntimeError(
            f"this program cannot run the configuration mainnet-1m-catchup-epochs: no {lacks}")


def start_workers(ctx) -> None:
    """As ``range_blocks.start_workers``, with the reference that follows
    the boundary."""
    require_program_support()
    lineage = next(w for w in ctx.workers if w.role == "lineage")
    lineage.send(cmd="blocks", blocks=block_plan(ctx.mix),
                 participation=ctx.mix["participation"],
                 attestation_slots_back=ctx.mix["attestation_slots_back"],
                 prestate_after=int(ctx.mix["warmup_slots"][-1]))
    ctx.workers.append(EpochReference(ctx.size["preset"], ctx.sec_per_slot))


def noted_counters(registries) -> dict:
    """``{"family{labels}": value}`` of the counters this generator notes."""
    out: dict[str, float] = {}
    for reg in registries:
        for line in reg.render_prometheus(self_scrape=False).splitlines():
            if line.startswith(NOTED_COUNTERS) and "{" in line:
                key, value = line.rsplit(" ", 1)
                out[key] = out.get(key, 0.0) + float(value)
    return out


def gained(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v != before.get(k, 0.0)}


async def run(ctx, lineage) -> dict:
    from lambda_ethereum_consensus_tpu.fork_choice import get_head
    from lambda_ethereum_consensus_tpu.node.sync import SyncBlocks
    from lambda_ethereum_consensus_tpu.state_transition.core import state_root
    from lambda_ethereum_consensus_tpu.store import state_store

    mix, spec, store, node = ctx.mix, ctx.spec, ctx.store, ctx.node
    n_blocks = int(mix["blocks"])
    warm_slots = [int(s) for s in mix["warmup_slots"]]
    plan = block_plan(mix)  # asked of the lineage worker in start_workers
    spe = int(spec.SLOTS_PER_EPOCH)
    boundaries = plan[-1][1] // spe - warm_slots[-1] // spe
    expect(int(mix["first_slot"]) % spe == 0 and boundaries >= 1,
           "the window's first block does not open an epoch")

    peer = RangePeer(spec)
    sync = SyncBlocks(store, node.pending, peer, spec)
    applied: list[tuple[bytes, float]] = []
    counters: list[dict] = []  # the noted counters after each window block
    window = ctx.window
    node_applied = node.pending.on_applied
    paused = 0.0  # seconds the harness itself held the loop (trace, counters)

    def on_applied(root, signed):
        nonlocal paused
        node_applied(root, signed)  # the node's own: persist block and state
        t_a = time.perf_counter()
        applied.append((root, t_a - paused))
        if window.t_open is not None:
            counters.append(noted_counters(ctx.registries()))
            window.item_boundary(len(applied))
            paused += time.perf_counter() - t_a

    node.pending.on_applied = on_applied
    cached = count_cached_entries()
    if getattr(node, "_warmer", None) is not None:
        # a node without drain shapes warms its transition programs alone
        # (node/warmup.py start_transition_warmer); done long before this
        import asyncio

        t = time.perf_counter()
        await asyncio.get_running_loop().run_in_executor(None, node._warmer.join)
        expect("error" not in node.warmer_stats,
               f"warmer failed: {node.warmer_stats.get('error')}")
        ctx.mark("transition_warmer_wait_s", t)
        note(warmer=node.warmer_stats)

    # ---- warm-up rounds: the first block crosses the epoch boundaries above
    # the anchor (plane attach, tracing, lowering, first dispatches: what a
    # process pays once), the last is a plain block, the window's parent
    plain = next(w for w in ctx.workers if w.role == "plain")
    feed = Frames(lineage, plain)
    t = time.perf_counter()
    warm = []
    for i in range(len(warm_slots)):
        warm.append(feed.take("block", 1200))
        t = ctx.mark(f"warmup_block{i}_wait_s", t)
        expect(ctx.current_slot() >= plan[-1][1],
               "the window's blocks are from the future")
        peer.serve(warm[-1])
        feed.forward()
        await sync.run()
        feed.forward()
        expect(warm[-1]["root"] in store.blocks
               and warm[-1]["root"] not in node.pending.invalid,
               "a warm-up block was not imported")
        t = ctx.mark(f"warmup_block{i}_import_s", t)
    pre = feed.take("prestate", 1200)
    frames = [feed.take("block", 1200) for _ in range(n_blocks)]
    host = feed.take("lineage", 1200)
    t = ctx.mark("window_blocks_wait_s", t)
    lineage.close()

    # ---- the plain reference's answers, in hand before the window opens:
    # every block the window will import is valid by it, root for root, the
    # block across the boundary too
    compared = ctx.compared
    held = plain.take("state", 600)
    expect(held["slot"] == pre["slot"] == warm_slots[-1],
           f"the plain reference holds the state of slot {held['slot']}")
    plain_blocks = [plain.take("block", 900) for _ in frames]
    t = ctx.mark("plain_reference_wait_s", t)
    hold(compared, "plain_block_roots_differ",
         sum(a["block_root"] != f["root"].hex() for a, f in zip(plain_blocks, frames)),
         "a block's root: plain reference != the lineage that built it")
    hold(compared, "plain_state_roots_differ",
         sum(not a["post_state_root"] == a["claimed_state_root"]
             == f["post_state_root"].hex() for a, f in zip(plain_blocks, frames)),
         "a block's post-state root: plain reference != the block's state_root")
    expect(sum(a["epochs_processed"] for a in plain_blocks) == boundaries,
           "the plain reference did not follow the window's epoch boundaries")
    note(host_build_s=[round(f["build_s"], 2) for f in warm + frames],
         attestations_per_block=[f["attestations"] for f in frames],
         sync_members=frames[0]["sync_members"], slots=[f["slot"] for f in frames],
         epoch_boundaries=boundaries,
         plain_reference={"prestate_encode_s": round(pre["encode_s"], 2),
                          "state_s": round(held["seconds"], 2),
                          "block_s": [round(a["seconds"], 2) for a in plain_blocks],
                          "epochs_processed": [a["epochs_processed"] for a in plain_blocks],
                          "validators": held["validators"]})

    # ---- the window: one range round delivers every block
    for f in frames:
        peer.serve(f)
    seconds = float(ctx.args.seconds)
    del applied[:]
    cached0 = dict(cached)
    parent_plane = getattr(store.block_states[warm[-1]["root"]], "_resident_plane", None)
    plane0 = dict(parent_plane.stats) if parent_plane is not None else None
    counters0 = noted_counters(ctx.registries())
    window.open()
    fetched = await sync.run()
    window.close()
    cached = {k: cached[k] - cached0[k] for k in cached}

    t_end = window.t_open + seconds
    done = [t_a for _root, t_a in applied]
    inside = [t_a for t_a in done if t_a <= t_end]
    roots = [f["root"] for f in frames]
    failed = sum(r not in store.blocks or r in node.pending.invalid for r in roots)
    expect([r for r, _ in applied] == roots[: len(applied)], "blocks applied out of order")
    ends = [window.t_open] + done
    facts = {
        "attempted": n_blocks, "failed": failed, "blocks": len(done),
        "epoch_boundaries": boundaries,
        "ms_per_block": (done[-1] - window.t_open) / len(done) * 1e3 if done else None,
        "end_to_end": {
            # n over the time to the n-th completion inside the window, so
            # the rate is not quantised to whole blocks per window
            "blocks_imported_per_s": (len(inside) / (inside[-1] - window.t_open)
                                      if inside else None),
        },
    }
    note(window={"seconds": seconds, "blocks_inside": len(inside), "fetched": fetched,
                 "range_requests": peer.requests,
                 "completion_s": [round(t_a - window.t_open, 3) for t_a in done],
                 "block_s": [round(b - a, 3) for a, b in zip(ends, ends[1:])],
                 "returned_after_s": window.t_close - window.t_open},
         counters_by_block=[gained(after, before) for before, after
                            in zip([counters0] + counters, counters)])

    # ---- the device chain ran inside the window: every number compared is
    # booked beside its limit (``ctx.compared``) as it is held
    compared["blocks_not_imported"] = [failed, 0]
    expect(done, "no block was applied")
    _sum, chains = window.span_delta("bls_dispatch_seconds")
    hold(compared, "blocks_without_bls_dispatch", max(0, len(done) - int(chains)),
         f"{len(done)} blocks applied but {int(chains)} bls_dispatch spans inside the "
         "window: the blocks' attestation batches did not take the device chain")
    carried = sum(f["attestations"] for f in frames[: len(done)])
    hold(compared, "attestations_not_through_cached_chain",
         max(0, carried - cached["entries"]),
         f"the applied blocks carry {carried} attestations, {cached['entries']} went "
         "through batch_verify_each_cached inside the window: the host RLC branch ran")

    # ---- head, post-state root and the persisted post-state == the plain
    # reference's (and the host lineage's, which built the blocks)
    if not failed:
        hold(compared, "head_is_not_last_block", int(get_head(store, spec) != roots[-1]),
             "the head is not the last imported block")
        post = store.block_states[roots[-1]]
        same = (state_root(post, spec).hex() == plain_blocks[-1]["post_state_root"]
                == host["post_state_root"].hex())
        hold(compared, "post_state_root_differs", int(not same),
             "post-state root: the node's != the plain reference's")
        t = time.perf_counter()
        raw = node.kv.get(state_store._STATE + roots[-1])
        expect(raw is not None, "the last block's post-state was not persisted")
        plain.send("readback", raw)
        back = plain.take("readback", 300)
        hold(compared, "persisted_state_fields_differ", len(back["fields_differ"]),
             f"the post-state read back from the store differs from the plain "
             f"reference's in {back['fields_differ']}")

        # ---- the boundary went through the resident plane, once, and the
        # window compiled nothing (after the roots: a run on the host
        # fallback has every root right and still is not this cell's run)
        plane = getattr(post, "_resident_plane", None)
        stats = dict(plane.stats) if plane is not None else None
        sweeps = 0 if not (stats and plane0) else stats["sweeps"] - plane0["sweeps"]
        fallbacks = (boundaries if not (stats and plane0)
                     else stats["fallbacks"] - plane0["fallbacks"])
        _sum, epochs = window.span_delta("epoch_transition_seconds")
        note(checked={"head_slot": frames[-1]["slot"],
                      "post_state_root": plain_blocks[-1]["post_state_root"],
                      "persisted_bytes": len(raw), "readback_fields": back["fields"],
                      "readback_s": round(time.perf_counter() - t, 2),
                      "resident_plane": {"at_open": plane0, "at_close": stats,
                                         "same_plane": plane is parent_plane},
                      "epoch_transition_spans": int(epochs),
                      "bls_dispatch_spans": int(chains), "cached_chain": cached})
        hold(compared, "epoch_not_through_resident_plane",
             abs(sweeps - boundaries) + fallbacks,
             f"{boundaries} epoch boundary inside the window, the lineage's resident "
             f"plane gained {sweeps} sweeps and {fallbacks} fallbacks (plane at the "
             f"window's opening: {plane0})")
        hold(compared, "epoch_transition_spans_differ", abs(int(epochs) - boundaries),
             f"{int(epochs)} epoch_transition spans inside the window, {boundaries} due")
        clock = CompileClock.delta(window.clock0, window.clock1)
        hold(compared, "window_compiles",
             clock["backend_compiles"] + clock["aot_lowers"],
             f"compiled or lowered inside the window: {clock}")
    # (a traced run is slowed by the profiler and reports no end-to-end metric)
    expect(inside or ctx.args.trace, "no block was applied inside the window")
    return facts
