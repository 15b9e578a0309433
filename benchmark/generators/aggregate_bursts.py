"""generators/aggregate_bursts.py — bursts of gossip aggregates, closed loop.

A burst is one slot's aggregate channel: ``committees x aggregators``
``SignedAggregateAndProof`` (snappy+SSZ as on the wire), pushed through the
node's own ``TopicSubscription._on_gossip``; the next burst goes in when the
last verdict of this one is back.  Parameters (the traffic mix's file):
``aggregators_per_committee``, ``participation`` [lo, hi],
``invalid_per_burst`` (inside the window), ``guard_invalid`` (the guard
burst before it), ``mint_workers``, ``min_banked_bursts``, ``max_bursts``,
``slot_lookback``, ``window_slots``, ``trace_seconds``.  One burst is in
flight, always: the loop is closed.

Set-up, in order: a warm-up burst (the first drain: checkpoint state,
registry planes, committee cache, program loads), a guard burst with
``guard_invalid`` wrong-secret signatures (REJECT and the bisection shape
sets exercised, checked and warm), then one primer burst per window slot.
The window's slots are picked from the wall clock only then, so that they
are timely however long the compiles before them took.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

import hostside
from common import expect, note, quantile

TOPIC = "beacon_aggregate_and_proof"
PEER = b"bench-peer"
BURST_TIMEOUT_S = 900  # a cold first drain compiles for minutes


def start_workers(ctx) -> None:
    ctx.minters = [ctx.worker("mint") for _ in range(int(ctx.mix["mint_workers"]))]


def window_slots(current: int, lookback: int, most: int, slots_per_epoch: int) -> list[int]:
    """The most recent slots of ``[current - lookback, current - 1]`` that
    share one epoch (one target, so one checkpoint state), at most ``most``:
    timely now, and still inside the propagation range and the
    current-or-previous-epoch rule for ``32 - lookback`` slots to come."""
    candidates = [s for s in range(current - lookback, current) if s >= 1]
    by_epoch: dict[int, list[int]] = {}
    for s in candidates:
        by_epoch.setdefault(s // slots_per_epoch, []).append(s)
    best = max(by_epoch.values(), key=lambda g: (len(g), g[-1]))
    return best[-most:]


class Feeder:
    def __init__(self, ctx):
        self.ctx = ctx
        self.sub = ctx.subscription(TOPIC)
        self.pushed: dict[bytes, float] = {}
        self.fed: list[dict] = []  # every burst pushed, in order

    async def feed(self, burst: dict) -> float:
        """Push one burst and wait for its last verdict; returns when the
        push ended."""
        sub, topic, pushed, verdicts = self.sub, self.sub.topic, self.pushed, self.ctx.verdicts
        want = len(verdicts) + len(burst["items"])
        for msg_id, payload in burst["items"]:
            pushed[msg_id] = time.perf_counter()
            await sub._on_gossip(topic, msg_id, payload, PEER)
        t_pushed = time.perf_counter()
        self.fed.append(burst)
        deadline = t_pushed + BURST_TIMEOUT_S
        while len(verdicts) < want:
            expect(time.perf_counter() < deadline,
                   f"burst {burst['id']}: verdicts did not come back")
            await asyncio.sleep(0.0005)
        burst["t_done"] = time.perf_counter()
        return t_pushed


async def take_burst(worker, timeout: float) -> dict:
    """The worker's next burst, without stalling the node's loop."""
    deadline = time.perf_counter() + timeout
    while worker.frames.empty():
        expect(time.perf_counter() < deadline, "mint worker: no burst in time")
        await asyncio.sleep(0.001)
    return worker.take("burst", 1)


async def run(ctx, lineage) -> dict:
    from lambda_ethereum_consensus_tpu.network.port import (
        VERDICT_ACCEPT, VERDICT_REJECT,
    )

    mix, anchor, minters = ctx.mix, ctx.anchor, ctx.minters
    spe = int(ctx.spec.SLOTS_PER_EPOCH)
    params = dict(
        block_root=anchor["block_root"].hex(),
        genesis_validators_root=anchor["genesis_validators_root"].hex(),
        seeds={str(e): s.hex() for e, s in anchor["seeds"].items()})
    for m in minters:
        m.take("ready", 300)
        m.send(cmd="params", **params)
    lineage.close()  # its state is not needed again: free the memory
    feeder = Feeder(ctx)

    # ---- warm-up and guard bursts, on the newest timely slot
    t = time.perf_counter()
    slot = ctx.current_slot() - 1
    minters[0].send(cmd="mint", bursts=[
        {"id": 0, "role": "warmup", "slot": slot, "reuse": 0, "invalid": 0,
         "oracle": False},
        {"id": 1, "role": "guard", "slot": slot, "reuse": 1,
         "invalid": int(mix["guard_invalid"]), "oracle": True}])
    await feeder.feed(await take_burst(minters[0], 600))
    t = ctx.mark("warmup_burst_s", t)
    await feeder.feed(await take_burst(minters[0], 600))
    t = ctx.mark("guard_burst_s", t)

    # ---- the window's slots, then a primer burst on each while the bank fills
    await asyncio.sleep(0.05)  # a tick overdue since the last drain runs first
    slots = window_slots(ctx.current_slot(), int(mix["slot_lookback"]),
                         int(mix["window_slots"]), spe)
    first = 2 + len(slots)
    W = len(minters)
    for w, m in enumerate(minters):
        primers = [{"id": 2 + i, "role": "primer", "slot": s, "reuse": 2,
                    "invalid": 0, "oracle": False}
                   for i, s in enumerate(slots) if i % W == w]
        m.send(cmd="mint", bursts=primers)
        m.send(cmd="mint_window", slots=slots, first=first, start=first + w,
               stride=W, limit=first + int(mix["max_bursts"]), reuse_base=3,
               oracle_ids=[first])
    for i in range(len(slots)):
        await feeder.feed(await take_burst(minters[i % W], 600))
    t = ctx.mark("primer_bursts_s", t)
    while sum(m.frames.qsize() for m in minters) < int(mix["min_banked_bursts"]):
        await asyncio.sleep(0.01)
    t = ctx.mark("bank_wait_s", t)
    banked = sum(m.frames.qsize() for m in minters)

    # ---- the window: closed loop, one burst in flight
    window, seconds = ctx.window, float(ctx.args.seconds)
    n_setup = len(feeder.fed)
    late, next_id, verified = [], first, 0
    window.open()
    t_ready = window.t_open
    while not window.over():
        burst = await take_burst(minters[(next_id - first) % W], 600)
        expect(burst["id"] == next_id, "bursts out of order")
        next_id += 1
        t_pushed = await feeder.feed(burst)
        late.append(t_pushed - t_ready)  # previous verdict back -> fully pushed
        t_ready = burst["t_done"]
        verified += len(burst["items"])
        window.item_boundary(verified)
    window.close()
    for m in minters:
        m.send(cmd="stop")

    # ---- the books
    t_end = window.t_open + seconds
    in_window = feeder.fed[n_setup:]
    verdicts, pushed = ctx.verdicts, feeder.pushed
    lat, t_last, attempted, failed = [], window.t_open, 0, 0
    for burst in feeder.fed:
        counted = burst["role"] == "window"
        for (msg_id, _), bad in zip(burst["items"], burst["bad"]):
            verdict, t_v = verdicts.get(msg_id, (None, None))
            right = verdict == (VERDICT_REJECT if bad else VERDICT_ACCEPT)
            if counted:
                attempted += 1
                failed += not right
                if right and t_v <= t_end:
                    lat.append(t_v - pushed[msg_id])
                    t_last = max(t_last, t_v)
            else:
                expect(right, f"{burst['role']} burst: verdict of {msg_id!r} is "
                              f"{verdict}, minted {'bad' if bad else 'good'}")
    # (a traced run is slowed by the profiler and reports no end-to-end metric)
    expect(lat or ctx.args.trace, "no aggregate was verified inside the window")
    lat.sort()
    lat = lat or [float("nan")]
    facts = {
        "attempted": attempted, "failed": failed,
        "bursts": len(in_window),
        "aggregates": sum(len(b["items"]) for b in in_window),
        "end_to_end": {
            # all the work and all the time from the window's opening to the
            # last verdict inside it: verdicts come back a burst at a time, so
            # dividing by the nominal length would quantise by a burst (~2 %)
            "agg_verified_per_s": len(lat) / max(t_last - window.t_open, 1e-9),
            "agg_verdict_p95_ms": quantile(lat, 0.95) * 1e3,
        },
    }
    late_ms = sorted(x * 1e3 for x in late)
    note(window={
        "seconds": seconds, "slots": slots, "bursts_whole": len(in_window),
        "aggregates_in_window": len(lat), "to_last_verdict_s": t_last - window.t_open,
        "agg_verdict_p50_ms": quantile(lat, 0.5) * 1e3,
        "agg_verdict_p95_ms": quantile(lat, 0.95) * 1e3,
        "burst_s_p50": quantile(sorted(
            b["t_done"] - pushed[b["items"][0][0]] for b in in_window), 0.5)},
         generator={
        # previous verdict back -> next burst fully pushed, per burst
        "late_ms_p50": quantile(late_ms, 0.5), "late_ms_max": late_ms[-1],
        "banked_at_open": banked,
        "banked_at_close": sum(m.frames.qsize() for m in minters),
        "mint_s_per_burst_p50": quantile(sorted(b["mint_s"] for b in in_window), 0.5)})

    # ---- minted truth == the native library (guard burst, one window burst)
    checked = [b for b in feeder.fed if b["oracle"] is not None]
    expect({b["role"] for b in checked} >= {"guard", "window"},
           "the host oracle did not run over a guard and a window burst")
    rejects = 0
    for b in checked:
        for (msg_id, _), bad, ok in zip(b["items"], b["bad"], b["oracle"]):
            expect(ok != bool(bad),
                   f"host route disagrees with the minting of {msg_id!r}")
            rejects += not ok
    expect(rejects >= int(mix["guard_invalid"]), "no REJECT was exercised")

    # ---- every attesting member of an accepted aggregate is in the store
    t0 = time.perf_counter()
    chain = hostside.Chain(ctx.spec, ctx.n_validators, anchor)
    voted: dict[int, np.ndarray] = {}
    for b in feeder.fed:
        k = int(b["committee_size"])
        bits = np.unpackbits(b["bits"], axis=1)[:, :k].astype(bool)
        mask = voted.setdefault(b["slot"] // spe, np.zeros(ctx.n_validators, bool))
        for j, (index, bad) in enumerate(zip(b["index"], b["bad"])):
            verdict = verdicts.get(b["items"][j][0], (None,))[0]
            if not bad and verdict == VERDICT_ACCEPT:
                mask[chain.committee(b["slot"], int(index))[bits[j]]] = True
    latest, votes = ctx.store.latest_messages, 0
    for epoch, mask in voted.items():
        for v in np.flatnonzero(mask).tolist():
            m = latest.get(v)
            expect(m is not None and m.epoch >= epoch and m.root == anchor["block_root"],
                   f"validator {v}: accepted vote of epoch {epoch} is not in the store")
            votes += 1
    note(checked={"oracle_bursts": [b["role"] for b in checked], "rejects": rejects,
                  "voters_read_back": votes,
                  "read_back_s": time.perf_counter() - t0})
    return facts
