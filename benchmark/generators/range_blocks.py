"""generators/range_blocks.py — range delivery of signed blocks.

The node's own ``node/sync.py:SyncBlocks.run()`` -> ``PendingBlocks`` ->
``on_block``, with this module's ``RangePeer`` standing for the peer that
answers ``request_blocks_by_range``.  Parameters (the traffic mix's file):
``warmup_slots`` (blocks delivered one round each before the window; the
first crosses the epoch boundaries above the anchor), ``first_slot`` and
``blocks`` (the measured round: consecutive slots), ``attestation_slots_back``,
``participation``, ``trace_seconds``.

The plain reference is ``plainref.py``, a child that imports nothing of the
program: it is given the host lineage's state under the window's first block
and the window's blocks, as SSZ, follows them by the spec on ``hashlib`` and
answers each block's root and post-state root; after the window it is given
the last post-state as the node persisted it and compares it field by field
with its own.  Its answers are in hand before the window opens.

Besides head and post-state root, a run is held to the device path: inside
the window ``bls_dispatch_seconds`` gains at least one span a block applied,
and every attestation the applied blocks carry is verified through
``crypto.bls.batch.batch_verify_each_cached`` (committee sums on the device),
counted from here in a wrapper around that function.  The host RLC branch of
``state_transition/operations.py`` — aggregate keys summed member by member
on the host — would otherwise be a quiet way to a cell that times Python;
it books ``bls_dispatch`` spans too (its ``verify_points`` takes the uncached
chain from 128 entries on), so the span count alone does not show it.

The event loop is blocked inside ``process_once``, so completion times are
taken in a wrapper around the node's ``on_applied`` callback, never by a
polling coroutine.
"""

from __future__ import annotations

import json
import os
import queue
import struct
import subprocess
import sys
import threading
import time

from common import HERE, BenchFailure, expect, hold, note


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


class PlainReference:
    """``plainref.py`` as a child: SSZ messages in, one JSON line out for
    each.  It gets no ``PYTHONPATH``: it cannot import the program."""

    role = "plain"

    def __init__(self, preset: str, seconds_per_slot: int):
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "plainref.py"), preset,
             str(seconds_per_slot)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, cwd=HERE)
        self.answers: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self.answers.put(json.loads(line))
        self.answers.put({"kind": "eof"})

    def send(self, cmd: str, ssz: bytes) -> None:
        header = json.dumps({"cmd": cmd, "bytes": len(ssz)}).encode()
        try:
            self.proc.stdin.write(struct.pack("<Q", len(header)) + header)
            self.proc.stdin.write(ssz)
            self.proc.stdin.flush()
        except OSError as e:
            raise BenchFailure(f"the plain reference went away: {e}") from None

    def take(self, kind: str, timeout: float) -> dict:
        try:
            answer = self.answers.get(timeout=timeout)
        except queue.Empty:
            raise BenchFailure(
                f"the plain reference gave no {kind!r} answer in {timeout:.0f} s") from None
        expect(answer["kind"] == kind,
               f"the plain reference answered {answer} where {kind!r} was due")
        return answer

    def close(self) -> None:
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


class Frames:
    """The lineage worker's frames in their order, with what the plain
    reference follows passed on to it the moment this process sees it (the
    event loop is held during an import, so ``forward`` is called around
    each): the state under the window's first block, then the window's
    blocks."""

    def __init__(self, lineage, plain: PlainReference):
        self.lineage, self.plain = lineage, plain
        self.seen: list[dict] = []
        self.sent = 0  # blocks passed on

    def _pass_on(self, frame: dict) -> dict:
        if frame["kind"] == "prestate":
            self.plain.send("state", frame.pop("ssz"))
        elif frame["kind"] == "block" and frame["role"] == "window":
            self.plain.send("block", frame.pop("ssz"))
            self.sent += 1
        return frame

    def forward(self) -> None:
        """Whatever has arrived, without waiting."""
        while True:
            try:
                self.seen.append(self._pass_on(self.lineage.frames.get_nowait()))
            except queue.Empty:
                return

    def take(self, kind: str, timeout: float) -> dict:
        frame = self.seen.pop(0) if self.seen else self._pass_on(
            self.lineage.take(kind, timeout))
        expect(frame["kind"] == kind,
               f"lineage worker sent {frame['kind']!r}, {kind!r} expected")
        return frame


def block_plan(mix: dict) -> list[list]:
    return [["warmup", int(s)] for s in mix["warmup_slots"]] + [
        ["window", int(mix["first_slot"]) + i] for i in range(int(mix["blocks"]))]


def start_workers(ctx) -> None:
    """The lineage worker, which every run has, builds the blocks.  It is
    asked for them at once, so that it builds them on the host's lineage
    while this process boots the node, not after (35 s for the block across
    the epoch boundary, 9 s for each of the others, at 2^20 validators)."""
    lineage = next(w for w in ctx.workers if w.role == "lineage")
    lineage.send(cmd="blocks", blocks=block_plan(ctx.mix),
                 participation=ctx.mix["participation"],
                 attestation_slots_back=ctx.mix["attestation_slots_back"],
                 prestate_after=int(ctx.mix["warmup_slots"][-1]))
    ctx.workers.append(PlainReference(ctx.size["preset"], ctx.sec_per_slot))


def count_cached_entries() -> dict:
    """Count the entries that go through the device chain's cached entry
    point (``operations.py`` looks the function up on its module at each
    call): ``{"calls", "entries"}``, running totals."""
    from lambda_ethereum_consensus_tpu.crypto.bls import batch

    seen = {"calls": 0, "entries": 0}
    verify = batch.batch_verify_each_cached

    def counted(cache, entries, *args, **kwargs):
        seen["calls"] += 1
        seen["entries"] += len(entries)
        return verify(cache, entries, *args, **kwargs)

    batch.batch_verify_each_cached = counted
    return seen


async def run(ctx, lineage) -> dict:
    from lambda_ethereum_consensus_tpu.fork_choice import get_head
    from lambda_ethereum_consensus_tpu.node.sync import SyncBlocks
    from lambda_ethereum_consensus_tpu.state_transition.core import state_root
    from lambda_ethereum_consensus_tpu.store import state_store

    mix, spec, store, node = ctx.mix, ctx.spec, ctx.store, ctx.node
    n_blocks = int(mix["blocks"])
    warm_slots = [int(s) for s in mix["warmup_slots"]]
    plan = block_plan(mix)  # asked of the lineage worker in start_workers

    peer = RangePeer(spec)
    sync = SyncBlocks(store, node.pending, peer, spec)
    applied: list[tuple[bytes, float]] = []
    window = ctx.window
    node_applied = node.pending.on_applied
    paused = 0.0  # seconds the harness itself held the loop (stopping the trace)

    def on_applied(root, signed):
        nonlocal paused
        node_applied(root, signed)  # the node's own: persist block and state
        t_a = time.perf_counter()
        applied.append((root, t_a - paused))
        if window.t_open is not None:
            window.item_boundary(len(applied))
            paused += time.perf_counter() - t_a

    node.pending.on_applied = on_applied
    cached = count_cached_entries()

    # ---- warm-up rounds: the first block crosses the epoch boundaries above
    # the anchor, the next is a plain block of the window's own shape
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
    # every block the window will import is valid by it, root for root
    compared = ctx.compared
    held = plain.take("state", 600)
    expect(held["slot"] == pre["slot"] == warm_slots[-1],
           f"the plain reference holds the state of slot {held['slot']}")
    plain_blocks = [plain.take("block", 600) for _ in frames]
    t = ctx.mark("plain_reference_wait_s", t)
    hold(compared, "plain_block_roots_differ",
         sum(a["block_root"] != f["root"].hex() for a, f in zip(plain_blocks, frames)),
         "a block's root: plain reference != the lineage that built it")
    hold(compared, "plain_state_roots_differ",
         sum(not a["post_state_root"] == a["claimed_state_root"]
             == f["post_state_root"].hex() for a, f in zip(plain_blocks, frames)),
         "a block's post-state root: plain reference != the block's state_root")
    note(host_build_s=[round(f["build_s"], 2) for f in warm + frames],
         attestations_per_block=[f["attestations"] for f in frames],
         sync_members=frames[0]["sync_members"], slots=[f["slot"] for f in frames],
         plain_reference={"prestate_encode_s": round(pre["encode_s"], 2),
                          "state_s": round(held["seconds"], 2),
                          "block_s": [round(a["seconds"], 2) for a in plain_blocks],
                          "validators": held["validators"]})

    # ---- the window: one range round delivers every block
    for f in frames:
        peer.serve(f)
    seconds = float(ctx.args.seconds)
    del applied[:]
    cached0 = dict(cached)
    window.open()
    fetched = await sync.run()
    window.close()
    cached = {k: cached[k] - cached0[k] for k in cached}

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
        plane = getattr(post, "_resident_plane", None)
        note(checked={"head_slot": frames[-1]["slot"],
                      "post_state_root": plain_blocks[-1]["post_state_root"],
                      "persisted_bytes": len(raw), "readback_fields": back["fields"],
                      "readback_s": round(time.perf_counter() - t, 2),
                      "resident_sweeps_since_start":
                          None if plane is None else int(plane.stats["sweeps"]),
                      "bls_dispatch_spans": int(chains), "cached_chain": cached})
    return facts
