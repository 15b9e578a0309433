"""generators/range_blocks.py — range delivery of signed blocks.

The node's own ``node/sync.py:SyncBlocks.run()`` -> ``PendingBlocks`` ->
``on_block``, with this module's ``RangePeer`` standing for the peer that
answers ``request_blocks_by_range``.  Parameters (the traffic mix's file):
``warmup_slots`` (blocks delivered one round each before the window; the
first crosses the epoch boundaries above the anchor), ``first_slot`` and
``blocks`` (the measured round: consecutive slots), ``attestation_slots_back``,
``participation``, ``trace_seconds``.

The event loop is blocked inside ``process_once``, so completion times are
taken in a wrapper around the node's ``on_applied`` callback, never by a
polling coroutine.
"""

from __future__ import annotations

import time

from common import expect, note


class RangePeer:
    """The peer's side of ``beacon_blocks_by_range``: blocks as wire bytes
    by slot, decoded on request as the node's downloader decodes a chunk."""

    def __init__(self, spec):
        self.spec = spec
        self.by_slot: dict[int, bytes] = {}
        self.requests = 0

    def serve(self, frame: dict) -> None:
        self.by_slot[int(frame["slot"])] = frame["wire"]

    async def request_blocks_by_range(self, start: int, count: int) -> list:
        from lambda_ethereum_consensus_tpu.compression.snappy import decompress
        from lambda_ethereum_consensus_tpu.types.beacon import SignedBeaconBlock

        self.requests += 1
        return [SignedBeaconBlock.decode(decompress(self.by_slot[s]), self.spec)
                for s in range(start, start + count) if s in self.by_slot]

    async def request_blocks_by_root(self, roots: list) -> list:
        return []


def start_workers(ctx) -> None:
    """The lineage worker, which every run has, builds the blocks."""


async def run(ctx, lineage) -> dict:
    from lambda_ethereum_consensus_tpu.fork_choice import get_head
    from lambda_ethereum_consensus_tpu.node.sync import SyncBlocks
    from lambda_ethereum_consensus_tpu.state_transition.core import state_root

    mix, spec, store, node = ctx.mix, ctx.spec, ctx.store, ctx.node
    n_blocks = int(mix["blocks"])
    warm_slots = [int(s) for s in mix["warmup_slots"]]
    plan = [["warmup", s] for s in warm_slots] + [
        ["window", int(mix["first_slot"]) + i] for i in range(n_blocks)]
    lineage.send(cmd="blocks", blocks=plan, participation=mix["participation"],
                 attestation_slots_back=mix["attestation_slots_back"])

    peer = RangePeer(spec)
    sync = SyncBlocks(store, node.pending, peer, spec)
    applied: list[tuple[bytes, float]] = []
    window = ctx.window
    node_applied = node.pending.on_applied

    def on_applied(root, signed):
        node_applied(root, signed)  # the node's own: persist block and state
        applied.append((root, time.perf_counter()))
        if window.t_open is not None:
            window.item_boundary(len(applied))

    node.pending.on_applied = on_applied

    # ---- warm-up rounds: the first block crosses the epoch boundaries above
    # the anchor, the next is a plain block of the window's own shape
    t = time.perf_counter()
    warm = []
    for i in range(len(warm_slots)):
        warm.append(lineage.take("block", 1200))
        t = ctx.mark(f"warmup_block{i}_wait_s", t)
        expect(ctx.current_slot() >= plan[-1][1],
               "the window's blocks are from the future")
        peer.serve(warm[-1])
        await sync.run()
        expect(warm[-1]["root"] in store.blocks
               and warm[-1]["root"] not in node.pending.invalid,
               "a warm-up block was not imported")
        t = ctx.mark(f"warmup_block{i}_import_s", t)
    frames = [lineage.take("block", 1200) for _ in range(n_blocks)]
    host = lineage.take("lineage", 1200)
    t = ctx.mark("window_blocks_wait_s", t)
    lineage.close()
    note(host_build_s=[round(f["build_s"], 2) for f in warm + frames],
         attestations_per_block=[f["attestations"] for f in frames],
         sync_members=frames[0]["sync_members"], slots=[f["slot"] for f in frames])

    # ---- the window: one range round delivers every block
    for f in frames:
        peer.serve(f)
    seconds = float(ctx.args.seconds)
    del applied[:]
    window.open()
    fetched = await sync.run()
    window.close()

    t_end = window.t_open + seconds
    done = [t_a for _root, t_a in applied]
    inside = [t_a for t_a in done if t_a <= t_end]
    # (a traced run is slowed by the profiler and reports no end-to-end metric)
    expect(inside or ctx.args.trace, "no block was applied inside the window")
    roots = [f["root"] for f in frames]
    failed = sum(r not in store.blocks or r in node.pending.invalid for r in roots)
    expect([r for r, _ in applied] == roots[: len(applied)], "blocks applied out of order")
    facts = {
        "attempted": n_blocks, "failed": failed, "blocks": len(done),
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
                 "returned_after_s": window.t_close - window.t_open})

    # ---- head and post-state root == the host lineage
    if not failed:
        head = get_head(store, spec)
        expect(head == roots[-1], "the head is not the last imported block")
        post = store.block_states[roots[-1]]
        expect(state_root(post, spec) == host["post_state_root"]
               == frames[-1]["post_state_root"],
               "post-state root: device lineage != host lineage")
        plane = getattr(post, "_resident_plane", None)
        expect(plane is not None and plane.stats["sweeps"] > 0,
               "the resident epoch plane did not run")
        note(checked={"head_slot": frames[-1]["slot"],
                      "post_state_root": host["post_state_root"].hex(),
                      "resident_sweeps": plane.stats["sweeps"]})
    return facts
