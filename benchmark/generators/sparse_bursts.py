"""generators/sparse_bursts.py — ``aggregate_bursts`` on a network below two
thirds participation, held to the plain reference of the aggregate channel.

The traffic, the loop and the books are ``aggregate_bursts``'s, run as they
are: closed loop, one burst (one slot's 1,024 ``SignedAggregateAndProof``) in
flight, warm-up, guard and primer bursts before the window; the mix's
``participation`` band [0.60, 0.66] makes every aggregate miss 174-205 of its
512 members, more than the committee cache's narrowest correction holds.  What
this module adds is what the configuration guarantees beyond the sibling's:

* **no aggregate of the window is summed on the host**: inside the window
  ``bls_chain_entries_total{shape="points"}`` gains 0 and
  ``bls_agg_entries_total`` over the widths above the narrowest gains at least
  the aggregates accepted;
* the **plain reference** ``plainref_agg.py`` (a child without ``PYTHONPATH``:
  it cannot import the program) is given the anchor state and every message
  fed, in push order, as raw SSZ with the slot it was pushed at and the
  minter's validity bit; its verdicts, its latest-message table and — for the
  window's first burst — its 1,024 aggregate public keys are compared, exact,
  with the node's verdicts, ``store.latest_messages`` and the program's own
  aggregation of that burst (``DeviceCommitteeCache.aggregate`` on the entries
  the drain builds, fetched after the window has closed);
* the bank is not empty when the window closes (``banked_at_close >= 1``): the
  rate is the node's, not the minters'.

Parameters: ``aggregate_bursts``'s.  The reference works after the window has
closed: it shares the host's cores.  So do the minters, 8 of them, each busy
on a core of the host's 13 — so each mints only until its queue covers its
share of what the window can still take and rests from then on (``pace``): the
bank of 96 is refilled inside the window as the traffic says, and the harness
is off the node's cores for the rest of it.
"""

from __future__ import annotations

import math
import os
import queue
import signal
import subprocess
import sys
import threading
import time

import numpy as np

from common import HERE, expect, hold, note
from generators import aggregate_bursts as base
from generators.subnet_votes import SubnetReference, labelled_counters

CHAIN, AGG = "bls_chain_entries_total", "bls_agg_entries_total"


class AggregateReference(SubnetReference):
    """``plainref_agg.py`` as a child without ``PYTHONPATH`` (it cannot
    import the program); pipes, threads and framing are the subnet cell's."""

    def __init__(self, preset: str, seconds_per_slot: int):
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "plainref_agg.py"), preset,
             str(seconds_per_slot)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, cwd=HERE)
        self.answers: queue.Queue = queue.Queue()
        self.outbox: queue.Queue = queue.Queue()
        self._threads = [threading.Thread(target=f, daemon=True)
                         for f in (self._pump, self._write)]
        for t in self._threads:
            t.start()


def agg_widths(ctx) -> tuple[int, ...]:
    """The committee cache's gather widths at this configuration's committee
    size, narrowest first, as the program derives them; none where the
    program has one width only (no wide device sum: the counters then gain
    nothing and the comparison says so)."""
    from lambda_ethereum_consensus_tpu.ops.bls_batch import DeviceCommitteeCache

    widths = getattr(DeviceCommitteeCache, "gather_widths", None)
    return widths(ctx.committee_size) if widths else ()


def books(ctx) -> dict:
    """The counters the guarantee reads, at one end of the window."""
    wanted = {"points": (CHAIN, {"shape": "points"}),
              "committee": (CHAIN, {"shape": "committee"})}
    for w in agg_widths(ctx):
        for side in ("missing", "attesting"):
            wanted[f"{w}:{side}"] = (AGG, {"width": str(w), "side": side})
    return labelled_counters(ctx.registries(), wanted)


def start_workers(ctx) -> None:
    """``aggregate_bursts``'s minters; the lineage worker hands over the
    anchor state as SSZ (what the plain reference starts from) while this
    process boots the node."""
    lineage = next(w for w in ctx.workers if w.role == "lineage")
    lineage.send(cmd="blocks", blocks=[], participation=[1.0, 1.0],
                 attestation_slots_back=[], poststate=True)
    base.start_workers(ctx)
    ctx.plain = AggregateReference(ctx.size["preset"], ctx.sec_per_slot)
    ctx.workers.append(ctx.plain)


class Feeder(base.Feeder):
    """``aggregate_bursts``'s feeder, keeping the slot each burst went in at
    (the reference's clock) where this module finds it again, and pacing the
    minters between the window's bursts."""

    def __init__(self, ctx):
        super().__init__(ctx)
        ctx.feeder = self
        self.pushed_slot: dict[int, int] = {}
        self.burst_s = math.inf  # the window's fastest burst so far
        self.resting: dict = {}  # minter -> since when
        self.rested_s = [0.0] * len(ctx.minters)  # per minter, inside the window

    async def feed(self, burst: dict) -> float:
        self.pushed_slot[burst["id"]] = self.ctx.current_slot()
        t_pushed = await super().feed(burst)
        if burst["role"] == "window":
            self.burst_s = min(self.burst_s,
                               burst["t_done"] - self.pushed[burst["items"][0][0]])
            self.pace()
        return t_pushed

    def pace(self) -> None:
        """Between two bursts of the window: a minter whose queue covers its
        share of what the window can still take — at the fastest burst seen,
        one burst more for the one begun before the end, two a minter to
        spare — rests (``SIGSTOP``); one that no longer does mints again."""
        ctx, now = self.ctx, time.perf_counter()
        left = max(0.0, ctx.window.t_open + float(ctx.args.seconds) - now)
        share = math.ceil((left / self.burst_s + 1) / len(ctx.minters)) + 2
        for i, m in enumerate(ctx.minters):
            banked = m.frames.qsize()
            if m not in self.resting and banked >= share:
                os.kill(m.proc.pid, signal.SIGSTOP)
                self.resting[m] = now
            elif m in self.resting and banked < share - 1:
                self.wake(i, m)

    def wake(self, i: int, m) -> None:
        self.rested_s[i] += time.perf_counter() - self.resting.pop(m)
        os.kill(m.proc.pid, signal.SIGCONT)

    def wake_all(self) -> None:
        """A resting minter reads no ``stop`` and does not end."""
        for i, m in enumerate(self.ctx.minters):
            if m in self.resting:
                self.wake(i, m)


def device_sums(ctx, burst: dict, ssz: list[bytes]):
    """The program's own aggregation of one burst, fetched: the entries as
    the drain builds them (``participation`` -> ``smaller_side`` ->
    ``_pack_members``), through ``DeviceCommitteeCache.aggregate``, as
    ``[(x, y) | None]`` integers."""
    from lambda_ethereum_consensus_tpu.fork_choice.attestation import get_attestation_context
    from lambda_ethereum_consensus_tpu.fork_choice.store import checkpoint_key
    from lambda_ethereum_consensus_tpu.ops import bls_batch as BB
    from lambda_ethereum_consensus_tpu.ops.bls_g1 import _ints_batch
    from lambda_ethereum_consensus_tpu.types.validator import SignedAggregateAndProof

    store, spec = ctx.store, ctx.spec
    flat, cache = [], None
    for raw in ssz:
        att = SignedAggregateAndProof.decode(raw, spec).message.aggregate
        target_state = store.checkpoint_states[checkpoint_key(att.data.target)]
        actx = get_attestation_context(store, att.data.target, target_state, spec)
        cache = actx.device_cache()
        cid, attesting, missing = actx.participation(att)
        flat.append((cid, BB.smaller_side(attesting, missing), None, None))
    b, _dead = BB._entry_budget(len(flat), cache._interpret)
    cid, _single, idx, idx_inf, attesting = BB._pack_members(cache, flat, b)
    ax, ay, inf = cache.aggregate(cid, idx, idx_inf, attesting)
    n = len(flat)
    xs = _ints_batch(np.asarray(ax).T[:n].astype(np.int32))
    ys = _ints_batch(np.asarray(ay).T[:n].astype(np.int32))
    dead = np.asarray(inf)[:n]
    return [None if d else (x, y) for x, y, d in zip(xs, ys, dead)], idx.shape[1]


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

    def opened():
        ends["open"] = books(ctx)
        open_window()

    def closed():
        ends["close"] = books(ctx)
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

    # ---- no aggregate of the window was summed on the host
    gained = {k: ends["close"][k] - v for k, v in ends["open"].items()}
    letter = {VERDICT_ACCEPT: "A", VERDICT_REJECT: "R", VERDICT_IGNORE: "I"}
    in_window = [b for b in feeder.fed if b["role"] == "window"]
    accepted = sum(ctx.verdicts.get(m, (None,))[0] == VERDICT_ACCEPT
                   for b in in_window for m, _ in b["items"])
    widths = agg_widths(ctx)
    wide = sum(gained[f"{w}:{side}"] for w in widths[1:] for side in ("missing", "attesting"))
    gathered = sum(w * gained[f"{w}:{side}"] for w in widths for side in ("missing", "attesting"))
    hold(compared, "entries_through_uncached_chain", gained["points"],
         f"{gained['points']} entries went through chain_verify with host-packed points")
    hold(compared, "aggregates_not_through_wide_device_sum", max(0, accepted - wide),
         f"{accepted} aggregates accepted in the window, {wide} entries in the wide widths")
    hold(compared, "bank_empty_at_close", int(ends["banked"] < 1),
         "the minters' bank was empty when the window closed: the rate is the supply's")

    # ---- every verdict, the latest-message table and the first window burst's
    # aggregate keys == the plain reference
    t0 = time.perf_counter()
    held = plain.take("state", 600)
    hold(compared, "plain_anchor_root_differs",
         int(bytes.fromhex(held["anchor_root"]) != ctx.anchor_root),
         "the plain reference roots the anchor block differently")
    first = in_window[0]
    first_ssz = None
    for burst in feeder.fed:
        ssz = [decompress(payload) for _, payload in burst["items"]]
        if burst is first:
            first_ssz = ssz
        plain.send({"cmd": "aggregates", "sums": burst is first,
                    "pushed_slots": [feeder.pushed_slot[burst["id"]]] * len(ssz),
                    "valid": (~burst["bad"]).astype(int).tolist(),
                    "sizes": [len(s) for s in ssz]}, b"".join(ssz))
    plain.send({"cmd": "table"})
    try:
        ours, width = device_sums(ctx, first, first_ssz)  # while the reference adds its keys
    except (AttributeError, TypeError) as e:
        # a program without the device sum over the smaller side has nothing
        # to fetch: every key of the burst counts as differing
        ours, width = [], None
        note(device_sums_unavailable=f"{type(e).__name__}: {e}")
    differ, plain_sums = 0, None
    for burst in feeder.fed:
        answer = plain.take("aggregates", 900)
        got = "".join(letter.get(ctx.verdicts.get(m, (None,))[0], "?")
                      for m, _ in burst["items"])
        expect(len(answer["verdicts"]) == len(got), "the plain reference lost an aggregate")
        differ += sum(a != b for a, b in zip(answer["verdicts"], got))
        if burst is first:
            plain_sums = answer["sums"]
    hold(compared, "plain_verdicts_differ", differ,
         f"{differ} verdict(s) differ from the plain reference's")
    theirs = [None if not any(raw := bytes.fromhex(h)) else
              (int.from_bytes(raw[:48], "big"), int.from_bytes(raw[48:], "big"))
              for h in plain_sums]
    differ = sum(a != b for a, b in zip(ours, theirs)) + abs(len(ours) - len(theirs))
    hold(compared, "device_sums_differ_from_plain", differ,
         f"{differ} of the first window burst's {len(ours)} aggregate keys differ "
         "from the plain reference's")
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
    aggregates = max(1, sum(gained[f"{w}:{side}"] for w in widths
                            for side in ("missing", "attesting")))
    note(sparse={"gained": gained, "accepted_in_window": accepted,
                 "wide_entries": wide, "banked_at_close": ends["banked"],
                 "minters_rested_s": [round(r, 3) for r in feeder.rested_s],
                 # gathered registry columns (= G1 additions of the aggregation
                 # program, padding within a row included) per entry, from the counter
                 "g1_adds_per_agg": gathered / aggregates,
                 "first_burst_width": width, "first_burst_sums": len(ours),
                 "latest_messages": len(latest), "plain_seen": len(validators),
                 "plain_wait_s": time.perf_counter() - t0})
    return {**facts, "end_to_end": {
        "agg_verified_per_s": facts["end_to_end"]["agg_verified_per_s"],
        "agg_verdict_p95_ms": facts["end_to_end"]["agg_verdict_p95_ms"]}}
