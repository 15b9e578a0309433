"""generators/slot_paced.py — a slot's aggregates as they arrive: open loop,
on the node's own slot clock.

Every mainnet node receives this: two thirds into each 12 s slot
(phase0 ``validator.md``: aggregates are broadcast ``2/3`` of the way through
the slot) the slot's ``committees x aggregators`` ``SignedAggregateAndProof``
(snappy+SSZ as on the wire) come in over about a second.  The generator
pushes them through the node's own ``TopicSubscription._on_gossip`` evenly
spaced over ``spread_seconds`` starting ``offset_seconds`` into the wall
clock's slot, **whether or not earlier verdicts are back**: the offered rate
is fixed (1,024 / 12 s = 85.3 aggregates/s), far under what the closed loop
sustains, so what the cell shows is the verdict time of a message that meets
the lane's deadline and coalescing, the chain at small batches and a slot's
signing roots hashed to G2 cold.  Parameters (the traffic mix's file):
``aggregators_per_committee``, ``participation`` [lo, hi], ``guard_invalid``,
``mint_workers``, ``window_slots``, ``offset_seconds``, ``spread_seconds``,
``straggler_seconds``, ``mint_lead_seconds``, ``trace_seconds``.

The departure from the wire, written in the mix's ``limits``: the aggregates
pushed in wall slot S carry ``data.slot = S - 1``.  ``validate_on_attestation``
applies a vote only from the slot after its own, and the program has no queue
that holds a current-slot vote until then; each slot's aggregates are still a
distinct slot's.

Set-up, in order: a warm-up burst and a guard burst with ``guard_invalid``
wrong-secret signatures, both closed loop (``aggregate_bursts``'s feeder);
then one whole paced slot that is not measured and has to be served as the
measured ones are: its last verdict back ``straggler_seconds`` after its last
push (the warm-up burst has dispatched every program a flush may use; a
program that answers a slot's aggregates later than that cannot serve the
slot's pace, and the run ends there).
Each paced round picks its wall slots from the clock when it starts, so that
its votes share one target epoch, with a closed-loop primer where the node
holds no checkpoint state for that epoch yet.  The window opens at the start
of the measured round's first slot and holds ``window_slots`` slots.
A verdict's time runs from the instant its message was **due** to the moment
the node hands the verdict to the sidecar: where a flush holds the loop, the
messages due meanwhile go in late, and that wait is the message's, as it is on
a socket.  ``failed`` counts verdicts that differ from the minted truth or are
not back ``straggler_seconds`` after the last push.

Held to ``hostside.py`` as ``head.agg-dense`` is: the minted truth against the
native library (guard burst, the first measured slot), every verdict against
the minted truth, every attester of an accepted aggregate read back from
``store.latest_messages``.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

import hostside
from common import expect, note, quantile
from generators.aggregate_bursts import PEER, Feeder, take_burst


def start_workers(ctx) -> None:
    ctx.minters = [ctx.worker("mint") for _ in range(int(ctx.mix["mint_workers"]))]


def first_paced_slot(earliest: int, slots: int, slots_per_epoch: int) -> int:
    """The first wall slot S0 >= ``earliest`` from which ``slots`` paced
    slots' votes (``data.slot`` = S0 - 1 ... S0 + ``slots`` - 2) share one
    target epoch, so that one checkpoint state serves them all."""
    s0 = max(earliest, 2)
    if (s0 - 1) // slots_per_epoch != (s0 + slots - 2) // slots_per_epoch:
        s0 = ((s0 + slots - 2) // slots_per_epoch) * slots_per_epoch + 1
    return s0


class Pacer:
    """Pushes one burst's messages at their due instants, open loop."""

    def __init__(self, ctx, sub):
        self.ctx, self.sub = ctx, sub
        self.due: dict[bytes, float] = {}
        self.pushed: dict[bytes, float] = {}

    async def push(self, burst: dict, t_first: float, spread: float) -> float:
        """Every item of ``burst`` at ``t_first + i * spread / n`` (perf
        clock), never before; returns when the last one went in."""
        sub, topic, n = self.sub, self.sub.topic, len(burst["items"])
        for i, (msg_id, payload) in enumerate(burst["items"]):
            due = t_first + i * spread / n
            wait = due - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            self.due[msg_id] = due
            self.pushed[msg_id] = time.perf_counter()
            await sub._on_gossip(topic, msg_id, payload, PEER)
        return time.perf_counter()

    async def settle(self, burst: dict, patience: float) -> None:
        """Wait until every verdict of ``burst`` is back, ``patience``
        seconds at most."""
        verdicts, deadline = self.ctx.verdicts, time.perf_counter() + patience
        while (any(m not in verdicts for m, _ in burst["items"])
               and time.perf_counter() < deadline):
            await asyncio.sleep(0.002)


async def run(ctx, lineage) -> dict:
    from lambda_ethereum_consensus_tpu.network.port import VERDICT_ACCEPT, VERDICT_REJECT

    mix, anchor, minters = ctx.mix, ctx.anchor, ctx.minters
    spe, sps = int(ctx.spec.SLOTS_PER_EPOCH), ctx.sec_per_slot
    params = dict(
        block_root=anchor["block_root"].hex(),
        genesis_validators_root=anchor["genesis_validators_root"].hex(),
        seeds={str(e): s.hex() for e, s in anchor["seeds"].items()})
    for m in minters:
        m.take("ready", 300)
        m.send(cmd="params", **params)
    lineage.close()  # its state is not needed again: free the memory
    feeder = Feeder(ctx)  # the closed-loop bursts of set-up
    pacer = Pacer(ctx, feeder.sub)
    W = len(minters)

    # ---- warm-up and guard bursts, closed loop, on the newest timely slot
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
    primed = {slot // spe}

    # ---- paced rounds, from the wall clock: wall slot S carries data.slot S - 1
    seconds = float(ctx.args.seconds)
    n_window = max(1, min(int(mix["window_slots"]), int(seconds // sps)))
    wall = lambda: (time.time() - ctx.genesis_time) / sps  # noqa: E731
    lead = float(mix["mint_lead_seconds"]) / sps  # minting a round's bursts
    offset, spread = float(mix["offset_seconds"]), float(mix["spread_seconds"])
    patience = float(mix["straggler_seconds"])
    window = ctx.window
    to_perf = time.perf_counter() - time.time()  # wall clock -> perf clock
    slot_start = lambda s: ctx.genesis_time + s * sps + to_perf  # noqa: E731
    next_id = 2

    async def paced_round(n_slots: int, role: str, oracle: bool, on_first_slot):
        """Mint and push ``n_slots`` consecutive slots' bursts, open loop;
        returns when the last push went in."""
        nonlocal next_id, t
        s0 = first_paced_slot(int(wall() + lead) + 1, n_slots, spe)
        if (s0 - 1) // spe not in primed:
            # the votes fall into an epoch the node holds no checkpoint state
            # for: a closed-loop primer on its first slot, once that slot is over
            primer_slot = ((s0 - 1) // spe) * spe
            minters[0].send(cmd="mint", bursts=[
                {"id": next_id, "role": "primer", "slot": primer_slot, "reuse": 0,
                 "invalid": 0, "oracle": False}])
            next_id += 1
            primer = await take_burst(minters[0], 600)
            await asyncio.sleep(max(0.0, (primer_slot + 1 - wall()) * sps + 0.5))
            await feeder.feed(primer)
            primed.add(primer_slot // spe)
            s0 = first_paced_slot(int(wall() + lead) + 1, n_slots, spe)
            t = ctx.mark(f"{role}_primer_s", t)
        for k in range(n_slots):
            minters[k % W].send(cmd="mint", bursts=[
                {"id": next_id + k, "role": role, "slot": s0 + k - 1, "reuse": 2,
                 "invalid": 0, "oracle": oracle and k == 0}])
        bursts = [await take_burst(minters[k % W], 600) for k in range(n_slots)]
        next_id += n_slots
        expect(wall() < s0, f"the {role} bursts were minted after wall slot {s0} began")
        t_pushed = None
        for k, burst in enumerate(bursts):
            s = s0 + k
            if k == 0:
                await asyncio.sleep(max(0.0, slot_start(s) - time.perf_counter()))
                on_first_slot()
            burst["wall_slot"] = s
            t_pushed = await pacer.push(burst, slot_start(s) + offset, spread)
            feeder.fed.append(burst)
            if k + 1 < n_slots:
                # between slots the loop is the node's
                await pacer.settle(burst, patience)
        return bursts, t_pushed

    # one whole paced slot that is not measured, held to the measured slots' rule
    warm, t_warm_pushed = await paced_round(1, "warm_slot", False, lambda: None)
    await pacer.settle(warm[0], patience)
    back = [ctx.verdicts[m][1] for m, _ in warm[0]["items"] if m in ctx.verdicts]
    expect(len(back) == len(warm[0]["items"]) and max(back) <= t_warm_pushed + patience,
           f"the unmeasured paced slot: {len(back)} of {len(warm[0]['items'])} verdicts "
           f"back {patience:g} s after its last push "
           "— the program does not serve a slot's aggregates at the slot's pace")
    t = ctx.mark("warm_slot_s", t)
    measured, t_last_push = await paced_round(n_window, "window", True, window.open)
    await pacer.settle(measured[-1], patience)
    # the trace stops after the last slot's verdicts (a slot is this cell's item)
    window.item_boundary(sum(len(b["items"]) for b in measured))
    window.close()
    for m in minters:
        m.send(cmd="stop")

    # ---- the books
    verdicts, due = ctx.verdicts, pacer.due
    t_limit = t_last_push + patience
    lat, lat_pushed, attempted, failed, late_push = [], [], 0, 0, []
    per_slot = []
    for burst in feeder.fed:
        counted = burst["role"] == "window"
        slot_lat = []
        for (msg_id, _), bad in zip(burst["items"], burst["bad"]):
            verdict, t_v = verdicts.get(msg_id, (None, None))
            right = verdict == (VERDICT_REJECT if bad else VERDICT_ACCEPT)
            if counted:
                attempted += 1
                ok = right and t_v <= t_limit
                failed += not ok
                if ok:
                    slot_lat.append(t_v - due[msg_id])
                    lat_pushed.append(t_v - pacer.pushed[msg_id])
                    late_push.append(pacer.pushed[msg_id] - due[msg_id])
            else:
                expect(right, f"{burst['role']} burst: verdict of {msg_id!r} is "
                              f"{verdict}, minted {'bad' if bad else 'good'}")
        if counted:
            lat.extend(slot_lat)
            per_slot.append(quantile(sorted(slot_lat), 0.95) * 1e3 if slot_lat else None)
    expect(lat or ctx.args.trace, "no aggregate was verified inside the window")
    lat.sort()
    lat = lat or [float("nan")]
    in_window = [b for b in feeder.fed if b["role"] == "window"]
    facts = {
        "attempted": attempted, "failed": failed,
        "bursts": len(in_window), "slots": len(in_window),
        "aggregates": sum(len(b["items"]) for b in in_window),
        "end_to_end": {
            "agg_verdict_p95_ms": quantile(lat, 0.95) * 1e3,
        },
    }
    lat_pushed.sort()
    late_push.sort()
    note(window={
        "seconds": seconds, "wall_slots": [b["wall_slot"] for b in in_window],
        "data_slots": [b["slot"] for b in in_window],
        "aggregates_in_window": len(lat),
        "offered_per_s": len(in_window[0]["items"]) / sps if in_window else None,
        "verdict_p50_ms": quantile(lat, 0.5) * 1e3,
        "verdict_p95_ms": quantile(lat, 0.95) * 1e3,
        "verdict_max_ms": lat[-1] * 1e3,
        "verdict_p95_ms_by_slot": per_slot,
        # from the actual push, not the due instant: what the loop's own
        # hold-ups hide (the end-to-end metric does not use it)
        "verdict_p95_ms_from_actual_push": (
            quantile(lat_pushed, 0.95) * 1e3 if lat_pushed else None),
        "push_late_ms_p95": quantile(late_push, 0.95) * 1e3 if late_push else None,
        "push_late_ms_max": late_push[-1] * 1e3 if late_push else None})

    # ---- minted truth == the native library (guard burst, first measured slot)
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
