"""generators/invalid_bursts.py — ``aggregate_bursts`` with a peer that sends
invalid aggregates: one aggregate of every burst signed with a wrong secret,
every burst pushed in an order of its own, held to the plain reference of the
aggregate channel.

The loop and the books are ``aggregate_bursts``'s, run as they are: closed
loop, one burst (one slot's 1,024 ``SignedAggregateAndProof``) in flight,
warm-up, guard and primer bursts before the window.  The minters sign a burst
grouped by committee; on the wire a slot's aggregates arrive interleaved from
64 committees' aggregators over many peers, so each burst's items and their
columns are permuted here, before the push, by an order drawn from ``(seed,
burst id)`` alone.  The mix's ``invalid_per_burst`` 1 makes every window
flush fail its first check and blame its one bad aggregate by bisection.  The
plain reference's child (``plainref_agg.py``) and the minters' pacing are
``sparse_bursts``'s, with two changes that keep the harness off the node's
cores inside the window: the reference's child has answered the anchor state
before the window opens, and the bank covers the window before it opens — at
the guard burst's speed (one invalid aggregate, as every window burst), one
burst a minter to spare, ``min_banked_bursts`` the least — so every minter
rests from the window's first burst (``Feeder.pace``) instead of minting
through its first seconds.  What this module adds is what the configuration
guarantees beyond the head deployment's:

* the invalid aggregate is REJECTed and none of its votes applied, every
  valid aggregate of its flush ACCEPTed and applied: every verdict and
  ``store.latest_messages`` against the plain reference, exact, and one
  REJECT a window burst;
* inside the window ``bls_bisect_checks_total`` gains, a burst, the flush's
  bisection depth (10 at 1,024 entries) of ``result="pass"`` and as many of
  ``result="fail"``;
* **no chain program is compiled or loaded inside the window**:
  ``bls_chain_layouts_total{layout="own"}`` gains 0 and backend compiles + AOT
  lowers (``readers/compile_count.py``) are 0 — held after every window burst,
  so the run stops with ``correct: false`` at the first burst that compiled;
* the bank is not empty when the window closes (``banked_at_close >= 1``).

Parameters: ``aggregate_bursts``'s.  A program that warms no bisection ladder
cannot run the cell and says so at once, with an exit code of its own.
"""

from __future__ import annotations

import asyncio
import math
import os
import random
import signal
import time
from types import SimpleNamespace

import numpy as np

from common import BenchFailure, expect, hold, note
from generators import aggregate_bursts as base
from generators import sparse_bursts as sparse
from generators.subnet_votes import FullCollections
from readers import compile_count

BISECT, LAYOUTS = "bls_bisect_checks_total", "bls_chain_layouts_total"
SETTLE_TIMEOUT_S = 600  # the reference roots a 2^20-validator state in pure Python


def start_workers(ctx) -> None:
    """``sparse_bursts``'s workers: the lineage worker's anchor state for the
    plain reference, the minters, the reference's child."""
    from lambda_ethereum_consensus_tpu.node.warmup import DrainShapes

    if not hasattr(DrainShapes, "bisection_layouts"):
        # a program from before the bisection ladder: say so
        # at once, with an exit code of its own, not after minutes of set-up
        raise SystemExit("benchmark: this program warms no bisection ladder "
                         "(DrainShapes.bisection_layouts): the cell cannot run on it")
    sparse.start_workers(ctx)


def books(ctx) -> dict:
    """The counters the guarantee reads, at one moment."""
    regs = ctx.registries()

    def total(name: str, **labels) -> float:
        return sum(reg.get(name, **labels) for reg in regs)

    return {"pass": total(BISECT, result="pass"), "fail": total(BISECT, result="fail"),
            "own": total(LAYOUTS, layout="own"), "warmed": total(LAYOUTS, layout="warmed")}


def depth(n: int, at: int) -> int:
    """Bisection levels after a flush's first check that blame entry ``at``
    of ``n`` alone, halving as ``crypto.bls.batch`` halves (the first half
    holds ``len // 2``): each level re-checks one range that passes and one
    that fails."""
    lo, hi, levels = 0, n, 0
    while hi - lo > 1:
        mid = lo + (hi - lo) // 2
        lo, hi = (lo, mid) if at < mid else (mid, hi)
        levels += 1
    return levels


def shuffle(burst: dict, seed: int) -> None:
    """The push order: the burst's items and every column beside them
    permuted by an order drawn from ``(seed, burst id)`` alone."""
    order = list(range(len(burst["items"])))
    random.Random(f"{seed}:{burst['id']}:push-order").shuffle(order)
    burst["items"] = [burst["items"][i] for i in order]
    for key in ("index", "bad", "bits"):
        burst[key] = burst[key][order]
    if burst["oracle"] is not None:
        burst["oracle"] = [burst["oracle"][i] for i in order]


class Feeder(sparse.Feeder):
    """``sparse_bursts``'s feeder (the reference's clock, the minters'
    pacing), pushing each burst in its own order, settling the harness
    before the window and holding the window to its books after every
    window burst."""

    def __init__(self, ctx):
        super().__init__(ctx)
        self.opened: dict | None = None  # books at the window's opening
        self.due = 0  # bisection depth summed over the window's bursts so far
        self.guard_s = math.inf  # the guard burst, push to last verdict
        self.state_answer: dict | None = None  # the reference's, to the anchor state
        self.burst_times: list[float] = []  # the window's bursts, in order

    async def feed(self, burst: dict) -> float:
        shuffle(burst, self.ctx.args.seed)
        t_pushed = await super().feed(burst)
        took = burst["t_done"] - self.pushed[burst["items"][0][0]]
        if burst["role"] == "guard":
            self.guard_s = took
        elif burst["role"] == "primer":
            await self.settle()
        elif burst["role"] == "window":
            self.burst_times.append(took)
            try:
                self.hold_window(burst)
            except BenchFailure:
                self.wake_all()  # a resting minter reads no stop and does not end
                raise
        return t_pushed

    def share(self, left: float, burst_s: float) -> int:
        """Bursts a minter holds for what the window can still take at
        ``burst_s`` a burst: one more for the one begun before the end, one
        a minter to spare; never more than a minter mints in all."""
        ctx, w = self.ctx, len(self.ctx.minters)
        return min(math.ceil((left / burst_s + 1) / w) + 1, int(ctx.mix["max_bursts"]) // w)

    async def settle(self) -> None:
        """Between the primer bursts, before the window: the reference's
        child has answered the anchor state (it works beside the node no
        more), and every minter holds its share of the whole window at the
        guard burst's speed."""
        ctx = self.ctx
        deadline = time.perf_counter() + SETTLE_TIMEOUT_S
        while self.state_answer is None:
            if not ctx.plain.answers.empty():
                self.state_answer = ctx.plain.take("state", 1)
                continue
            expect(time.perf_counter() < deadline,
                   "the plain reference did not answer the anchor state in time")
            await asyncio.sleep(0.01)
        share = self.share(float(ctx.args.seconds), self.guard_s)
        while min(m.frames.qsize() for m in ctx.minters) < share:
            expect(time.perf_counter() < deadline,
                   f"the minters did not bank {share} bursts each in time")
            await asyncio.sleep(0.01)

    def pace(self) -> None:
        """``sparse_bursts``'s pacing at this module's share: a minter whose
        queue covers its share of what the window can still take, at the
        fastest burst seen (before the first, the guard's), rests
        (``SIGSTOP``); one short of it by two mints again."""
        ctx, now = self.ctx, time.perf_counter()
        t_open = ctx.window.t_open if ctx.window.t_open is not None else now
        left = max(0.0, t_open + float(ctx.args.seconds) - now)
        share = self.share(left, min(self.burst_s, self.guard_s))
        for i, m in enumerate(ctx.minters):
            banked = m.frames.qsize()
            if m not in self.resting and banked >= share:
                os.kill(m.proc.pid, signal.SIGSTOP)
                self.resting[m] = now
            elif m in self.resting and banked < share - 1:
                self.wake(i, m)

    def hold_window(self, burst: dict) -> None:
        ctx, compared = self.ctx, self.ctx.compared
        bad = np.flatnonzero(burst["bad"])
        expect(len(bad) == 1, f"burst {burst['id']}: {len(bad)} invalid aggregates minted, 1 due")
        self.due += depth(len(burst["items"]), int(bad[0]))
        now = books(ctx)
        gained = {k: now[k] - v for k, v in self.opened.items()}
        compiles = compile_count.read(
            SimpleNamespace(clock0=ctx.window.clock0, clock1=ctx.clock.snapshot()), {})
        hold(compared, "window_compiles", compiles,
             f"burst {burst['id']}: {compiles} program(s) compiled or lowered inside the window")
        hold(compared, "own_layouts_in_window", gained["own"],
             f"burst {burst['id']}: {gained['own']} chained verify(s) at a layout no "
             "warmer loaded inside the window")
        off = abs(gained["pass"] - self.due) + abs(gained["fail"] - self.due)
        hold(compared, "bisect_checks_off", off,
             f"burst {burst['id']}: bls_bisect_checks_total gained {gained['pass']} pass + "
             f"{gained['fail']} fail inside the window, {self.due} + {self.due} due")


async def run(ctx, lineage) -> dict:
    from lambda_ethereum_consensus_tpu.compression.snappy import decompress
    from lambda_ethereum_consensus_tpu.network.port import (
        VERDICT_ACCEPT, VERDICT_IGNORE, VERDICT_REJECT,
    )

    plain, compared, window = ctx.plain, ctx.compared, ctx.window
    t = time.perf_counter()
    frame = lineage.take("lineage", 600)
    plain.send({"cmd": "state"}, frame.pop("ssz"))
    ctx.mark("anchor_state_s", t)

    # ---- the window's own books, taken where aggregate_bursts opens and closes it
    ends: dict[str, dict] = {}
    open_window, close_window = window.open, window.close
    collector = FullCollections()

    def opened():
        ctx.feeder.pace()  # the bank covers the window: the minters rest from its start
        ctx.feeder.opened = ends["open"] = books(ctx)
        ends["full_collections"] = collector.read()
        open_window()

    def closed():
        ends["close"] = books(ctx)
        full = collector.read()
        ends["full_collections"] = [full[0] - ends["full_collections"][0],
                                    round(full[1] - ends["full_collections"][1], 3)]
        ends["banked"] = sum(m.frames.qsize() for m in ctx.minters)
        ctx.feeder.wake_all()
        close_window()

    window.open, window.close = opened, closed
    feeder_class, base.Feeder = base.Feeder, Feeder
    try:
        facts = await base.run(ctx, lineage)
    finally:
        base.Feeder = feeder_class
    feeder = ctx.feeder

    # ---- the window's books (blame and layouts are held after every burst:
    # Feeder.hold_window), compiles to its close, REJECTs, the bank
    gained = {k: ends["close"][k] - v for k, v in ends["open"].items()}
    in_window = [b for b in feeder.fed if b["role"] == "window"]
    compiles = compile_count.read(window, facts)
    hold(compared, "window_compiles", compiles,
         f"{compiles} program(s) compiled or lowered inside the window")
    rejects = sum(ctx.verdicts.get(m, (None,))[0] == VERDICT_REJECT
                  for b in in_window for m, _ in b["items"])
    hold(compared, "window_rejects_not_bursts", abs(rejects - len(in_window)),
         f"{rejects} REJECTs inside the window, one a burst due over {len(in_window)}")
    hold(compared, "bank_empty_at_close", int(ends["banked"] < 1),
         "the minters' bank was empty when the window closed: the rate is the supply's")

    # ---- every verdict and the latest-message table == the plain reference
    t0 = time.perf_counter()
    letter = {VERDICT_ACCEPT: "A", VERDICT_REJECT: "R", VERDICT_IGNORE: "I"}
    held = feeder.state_answer or plain.take("state", 600)
    hold(compared, "plain_anchor_root_differs",
         int(bytes.fromhex(held["anchor_root"]) != ctx.anchor_root),
         "the plain reference roots the anchor block differently")
    for burst in feeder.fed:
        ssz = [decompress(payload) for _, payload in burst["items"]]
        plain.send({"cmd": "aggregates", "sums": False,
                    "pushed_slots": [feeder.pushed_slot[burst["id"]]] * len(ssz),
                    "valid": (~burst["bad"]).astype(int).tolist(),
                    "sizes": [len(s) for s in ssz]}, b"".join(ssz))
    plain.send({"cmd": "table"})
    differ = 0
    for burst in feeder.fed:
        answer = plain.take("aggregates", 900)
        got = "".join(letter.get(ctx.verdicts.get(m, (None,))[0], "?")
                      for m, _ in burst["items"])
        expect(len(answer["verdicts"]) == len(got), "the plain reference lost an aggregate")
        differ += sum(a != b for a, b in zip(answer["verdicts"], got))
    hold(compared, "plain_verdicts_differ", differ,
         f"{differ} verdict(s) differ from the plain reference's")
    table = plain.take("table", 600)
    validators = np.frombuffer(bytes.fromhex(table["validators"]), "<u4")
    epochs = np.frombuffer(bytes.fromhex(table["epochs"]), "<u4")
    root_ids = np.frombuffer(bytes.fromhex(table["root_ids"]), "<u4")
    roots = [bytes.fromhex(r) for r in table["roots"]]
    latest = ctx.store.latest_messages
    differ = len(set(latest) ^ set(validators.tolist()))
    for v, e, r in zip(validators.tolist(), epochs.tolist(), root_ids.tolist()):
        m = latest.get(v)
        differ += m is not None and (int(m.epoch) != e or bytes(m.root) != roots[r])
    hold(compared, "plain_latest_messages_differ", differ,
         f"{differ} latest message(s) differ from the plain reference's table")
    note(invalid={"gained": gained, "due_per_result": feeder.due, "rejects": rejects,
                  "banked_at_close": ends["banked"],
                  "minters_rested_s": [round(r, 3) for r in feeder.rested_s],
                  "guard_s": feeder.guard_s, "plain_state_s": held.get("seconds"),
                  "burst_s": [round(t, 4) for t in feeder.burst_times],
                  "full_collections": ends["full_collections"],
                  "latest_messages": len(latest), "plain_seen": len(validators),
                  "plain_wait_s": time.perf_counter() - t0})
    facts["bisect_checks"] = gained["pass"] + gained["fail"]
    return {**facts, "end_to_end": {
        "agg_verified_per_s": facts["end_to_end"]["agg_verified_per_s"],
        "agg_verdict_p95_ms": facts["end_to_end"]["agg_verdict_p95_ms"]}}
