"""generators/subnet_votes.py — bursts of unaggregated votes over the 64
attestation subnet topics, closed loop.

A burst is one slot's ``beacon_attestation_{subnet_id}`` channel: every
participating member of every committee of the slot sends its own
``Attestation`` with one aggregation bit (snappy+SSZ as on the wire), pushed
through the topics' own ``TopicSubscription._on_gossip`` round-robin over the
subnets; the next burst goes in when every verdict of the last is back.  The
node is put on the configuration's subnets by its own run-time call
(``BeaconNode.set_attestation_subnets``) in set-up.  Parameters (the traffic
mix's file): ``participation`` [lo, hi] per committee, ``mint_workers``,
``min_banked_bursts``, ``max_bursts``, ``slot_lookback``, ``min_epoch_slots``,
``trace_seconds``.

Gossip's first-seen rule makes every burst a distinct slot.  Set-up, in
order: a warm-up burst of a whole slot (checkpoint state, committee cache,
program loads at the window's shapes), a guard burst of one committee with a
wrong-secret signature, a second vote of one attester and a vote on the
wrong topic (REJECT / IGNORE / REJECT and the bisection, checked and warm);
then the window's slots are picked from the wall clock — ``max_bursts``
distinct slots of one target epoch behind the clock, still inside the
propagation range when the window ends; a clock that does not hold
``min_epoch_slots`` of them yet is waited for — and minted, all of them,
before the window opens: inside it
the host runs the node and nothing of the harness but the feeder.

The plain reference is ``plainref_subnet.py``, a child that imports nothing
of the program: it is given the anchor state and, in push order, every
message's subnet, SSZ bytes, pushed slot and minted validity (the window's
after it has closed), and answers each verdict and the latest-message table.  The mint workers
(``subnet_mint.py``) are on the program's host path and give the minted truth
and the native library's ``batch_verify_each_points`` over it.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import queue
import struct
import subprocess
import sys
import threading
import time

import numpy as np

from common import HERE, ROOT, BenchFailure, CompileClock, Worker, expect, hold, note, quantile

TOPIC = "beacon_attestation_%d"
PEER = b"bench-peer"
BURST_TIMEOUT_S = 900  # a cold first drain compiles for minutes
PROPAGATION_SLOTS = 32  # ATTESTATION_PROPAGATION_SLOT_RANGE


class MintWorker(Worker):
    """One ``subnet_mint.py`` child; frames and commands as ``common.Worker``."""

    def __init__(self, ctx):
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        argv = [sys.executable, os.path.join(HERE, "subnet_mint.py"),
                "--config", ctx.config_path, "--traffic", ctx.traffic_path,
                "--seed", str(ctx.args.seed), "--genesis-time", str(ctx.genesis_time)]
        if ctx.rehearse:
            argv.append("--rehearse")
        self.role = "subnet_mint"
        self.proc = subprocess.Popen(
            argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, cwd=ROOT)
        self.frames = queue.Queue()
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()


class SubnetReference:
    """``plainref_subnet.py`` as a child without ``PYTHONPATH``: it cannot
    import the program.  Messages go out on a thread of their own, so a
    burst's megabytes never hold the node's event loop."""

    role = "plain"

    def __init__(self, preset: str, seconds_per_slot: int):
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "plainref_subnet.py"), preset,
             str(seconds_per_slot)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, cwd=HERE)
        self.answers: queue.Queue = queue.Queue()
        self.outbox: queue.Queue = queue.Queue()
        self._threads = [threading.Thread(target=f, daemon=True)
                         for f in (self._pump, self._write)]
        for t in self._threads:
            t.start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self.answers.put(json.loads(line))
        self.answers.put({"kind": "eof"})

    def _write(self) -> None:
        while (msg := self.outbox.get()) is not None:
            header, payload = msg
            head = json.dumps({**header, "bytes": len(payload)}).encode()
            try:
                self.proc.stdin.write(struct.pack("<Q", len(head)) + head)
                self.proc.stdin.write(payload)
                self.proc.stdin.flush()
            except OSError:
                return  # the child went away: take() says so

    def send(self, header: dict, payload: bytes = b"") -> None:
        self.outbox.put((header, payload))

    def send_votes(self, burst: dict, pushed_slot: int) -> None:
        n = len(burst["ssz"])
        self.send({"cmd": "votes", "subnets": burst["subnet"].tolist(),
                   "pushed_slots": [pushed_slot] * n,
                   "valid": (~burst["bad"]).astype(int).tolist(),
                   "sizes": [len(s) for s in burst["ssz"]]}, b"".join(burst["ssz"]))

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
        self.outbox.put(None)
        self._threads[1].join(timeout=5)
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self._threads[0].join(timeout=5)


def start_workers(ctx) -> None:
    """The lineage worker hands over the anchor state as SSZ (what the plain
    reference starts from) while this process boots the node."""
    from lambda_ethereum_consensus_tpu.node import BeaconNode

    if not hasattr(BeaconNode, "set_attestation_subnets"):
        # an older program (the parent of the PR that brought this cell): say so
        # at once, with an exit code of its own, not after minutes of set-up
        raise SystemExit("benchmark: this program cannot change its attestation subnets at "
                         "run time (BeaconNode.set_attestation_subnets): the cell cannot run on it")
    lineage = next(w for w in ctx.workers if w.role == "lineage")
    lineage.send(cmd="blocks", blocks=[], participation=[1.0, 1.0],
                 attestation_slots_back=[], poststate=True)
    ctx.minters = [MintWorker(ctx) for _ in range(int(ctx.mix["mint_workers"]))]
    ctx.plain = SubnetReference(ctx.size["preset"], ctx.sec_per_slot)
    ctx.workers.extend(ctx.minters + [ctx.plain])


def pick_slots(current: int, lookback: int, most: int, slots_per_epoch: int,
               used: set[int], primed: set[int], enough: int) -> list[int]:
    """Distinct slots of one target epoch out of ``[current - lookback,
    current - 1]`` less the slots already used, oldest first, at most
    ``most``: the group of an epoch whose checkpoint state the node already
    holds if it has ``enough`` slots, else the largest group (the newer on a
    tie)."""
    by_epoch: dict[int, list[int]] = {}
    for s in range(max(1, current - lookback), current):
        if s not in used:
            by_epoch.setdefault(s // slots_per_epoch, []).append(s)
    ready = [g for e, g in by_epoch.items() if e in primed and len(g) >= enough]
    best = max(ready or by_epoch.values(), key=lambda g: (len(g), g[-1]))
    return best[-most:]


def labelled_counters(registries, wanted: dict) -> dict:
    """``{key: total}`` for ``wanted = {key: (family, {label: value})}``: each
    counter family summed over the series that carry the labels, read off
    one Prometheus exposition per registry."""
    totals = dict.fromkeys(wanted, 0.0)
    for reg in registries:
        for line in reg.render_prometheus(self_scrape=False).splitlines():
            for key, (name, labels) in wanted.items():
                if line.startswith(name + "{") and all(
                        f'{k}="{v}"' in line for k, v in labels.items()):
                    totals[key] += float(line.rsplit(" ", 1)[1])
    return totals


def host_path_verifies(registries) -> int:
    """``attestation_batch_verify`` spans booked with ``path="host"``."""
    total = 0
    for reg in registries:
        if "attestation_batch_verify_seconds" not in reg.family_names():
            continue
        for labels, _b, _c, _sum, count in reg.histogram_series(
                "attestation_batch_verify_seconds"):
            if dict(labels).get("path") == "host":
                total += count
    return total


def books(ctx) -> dict:
    """What the guarantees read at both ends of the window."""
    regs = ctx.registries()
    chain = "bls_chain_entries_total"
    return {
        **labelled_counters(regs, {
            "shed": ("ingest_shed_count", {"lane": "subnet"}),
            "single": (chain, {"shape": "single"}),
            "committee": (chain, {"shape": "committee"}),
            "points": (chain, {"shape": "points"})}),
        "host_verifies": host_path_verifies(regs),
    }


class FullCollections:
    """The collector's full (generation 2) passes, counted and timed from a
    ``gc.callbacks`` hook: an observer, it changes nothing of the collector."""

    def __init__(self):
        self.count, self.seconds, self._t0 = 0, 0.0, None
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0

    def read(self) -> tuple[int, float]:
        return self.count, self.seconds


class Feeder:
    def __init__(self, ctx, subnets):
        from lambda_ethereum_consensus_tpu.network.port import (
            VERDICT_ACCEPT, VERDICT_IGNORE, VERDICT_REJECT,
        )

        self.ctx = ctx
        self.letter = {VERDICT_ACCEPT: "A", VERDICT_REJECT: "R", VERDICT_IGNORE: "I"}
        self.subs = {i: ctx.subscription(TOPIC % i) for i in subnets}
        self.pushed: dict[bytes, float] = {}
        self.fed: list[dict] = []  # every burst pushed, in order
        self.held: list[tuple] = []  # the window's bursts, for the plain reference

    async def feed(self, burst: dict) -> float:
        """Push one burst, each vote on its subnet's topic, and wait for its
        last verdict; returns when the push ended."""
        ctx, pushed, verdicts, subs = self.ctx, self.pushed, self.ctx.verdicts, self.subs
        slot_now = int(time.time() - ctx.genesis_time) // ctx.sec_per_slot
        expect(burst["slot"] < ctx.current_slot()
               and burst["slot"] + PROPAGATION_SLOTS > slot_now + 1,
               f"burst {burst['id']} of slot {burst['slot']} is not timely at slot {slot_now}")
        want = len(verdicts) + len(burst["items"])
        for (msg_id, payload), subnet in zip(burst["items"], burst["subnet"].tolist()):
            sub = subs[subnet]
            pushed[msg_id] = time.perf_counter()
            await sub._on_gossip(sub.topic, msg_id, payload, PEER)
        t_pushed = time.perf_counter()
        self.fed.append(burst)
        if burst["role"] == "window":
            # after the close: the reference's child must not work beside the
            # node inside the window (it shares the host's cores)
            self.held.append((burst, slot_now))
        else:
            ctx.plain.send_votes(burst, slot_now)
        deadline = t_pushed + BURST_TIMEOUT_S
        while len(verdicts) < want:
            expect(time.perf_counter() < deadline,
                   f"burst {burst['id']}: verdicts did not come back")
            await asyncio.sleep(0.001)
        burst["t_done"] = time.perf_counter()
        burst["got"] = "".join(self.letter.get(verdicts[m][0], "?") for m, _ in burst["items"])
        return t_pushed


async def take_burst(worker, timeout: float) -> dict:
    """The worker's next burst, without stalling the node's loop."""
    deadline = time.perf_counter() + timeout
    while worker.frames.empty():
        expect(time.perf_counter() < deadline, "mint worker: no burst in time")
        await asyncio.sleep(0.001)
    return worker.take("burst", 1)


def check_set_up_burst(burst: dict) -> None:
    expect(burst["got"] == burst["expect"],
           f"{burst['role']} burst: {sum(a != b for a, b in zip(burst['got'], burst['expect']))} "
           f"verdict(s) differ from the minted truth "
           f"(got {burst['got'].count('A')}A/{burst['got'].count('R')}R/"
           f"{burst['got'].count('I')}I, minted {burst['expect'].count('A')}A/"
           f"{burst['expect'].count('R')}R/{burst['expect'].count('I')}I)")


async def run(ctx, lineage) -> dict:
    mix, anchor, minters, plain = ctx.mix, ctx.anchor, ctx.minters, ctx.plain
    compared = ctx.compared
    spe = int(ctx.spec.SLOTS_PER_EPOCH)

    # ---- the anchor state to the plain reference; the node onto its subnets
    t = time.perf_counter()
    frame = lineage.take("lineage", 600)
    plain.send({"cmd": "state"}, frame.pop("ssz"))
    lineage.close()  # its state is not needed again: free the memory
    t = ctx.mark("anchor_state_s", t)
    subnets = [int(i) for i in ctx.cfg["attnet_subnets"]]
    await ctx.node.set_attestation_subnets(subnets)
    feeder = Feeder(ctx, subnets)
    lanes = {lane["name"]: lane for lane in ctx.node.ingest.snapshot()["lanes"]}
    slot_votes = ctx.committees_per_slot * ctx.committee_size
    expect(lanes["subnet"]["capacity"] >= slot_votes
           and ctx.node.ingest.max_items >= slot_votes,
           f"the subnet lane holds {lanes['subnet']['capacity']} of a slot's {slot_votes} votes")
    t = ctx.mark("subscribe_s", t)
    params = dict(
        block_root=anchor["block_root"].hex(),
        genesis_validators_root=anchor["genesis_validators_root"].hex(),
        seeds={str(e): s.hex() for e, s in anchor["seeds"].items()})
    for m in minters:
        m.take("ready", 300)
        m.send(cmd="params", **params)

    # ---- warm-up burst (a whole slot) and guard burst (one committee with the
    # faults), on two slots of one epoch just behind the clock
    current = ctx.current_slot()
    warm_slot = current - 1 if (current - 1) % spe else current - 2
    guard_slot = warm_slot - 1
    expect(guard_slot >= 1, "the clock is too close to genesis for the guard burst")
    W = len(minters)
    minters[0].send(cmd="mint", bursts=[
        {"id": 0, "role": "warmup", "slot": warm_slot, "oracle": False}])
    minters[1 % W].send(cmd="mint", bursts=[
        {"id": 1, "role": "guard", "slot": guard_slot, "committees": [0], "oracle": True,
         "guard": {"invalid": 1, "second_vote": 1, "wrong_subnet": 1}}])
    warmup = await take_burst(minters[0], 600)
    t = ctx.mark("warmup_mint_s", t)
    await feeder.feed(warmup)
    check_set_up_burst(warmup)
    t = ctx.mark("warmup_burst_s", t)
    guard = await take_burst(minters[1 % W], 600)
    await feeder.feed(guard)
    check_set_up_burst(guard)
    hold(compared, "double_vote_evidence_missing",
         int(ctx.node.forensics.evidence_count("double_vote") < 1),
         "the second vote of one attester left no double-vote evidence")
    t = ctx.mark("guard_burst_s", t)

    # ---- the window's slots, from the wall clock; a primer (one committee)
    # where their epoch's checkpoint state is not held yet
    used, primed = {warm_slot, guard_slot}, {warm_slot // spe}
    next_id, enough = 2, int(mix["min_epoch_slots"])
    while True:
        await asyncio.sleep(0.05)  # a tick overdue since the last drain runs first
        slots = pick_slots(ctx.current_slot(), int(mix["slot_lookback"]),
                           int(mix["max_bursts"]), spe, used, primed, enough)
        if len(slots) < enough:
            # too early in the epoch, or split by its boundary: the clock
            # supplies the missing slots (set-up time, never a smaller window)
            expect(time.perf_counter() - t < (spe + 2) * ctx.sec_per_slot,
                   f"only {len(slots)} timely slots in one epoch: {slots}")
            await asyncio.sleep(ctx.sec_per_slot / 4)
            continue
        if slots[0] // spe in primed:
            break
        primer_slot = slots.pop(0)
        minters[0].send(cmd="mint", bursts=[
            {"id": next_id, "role": "primer", "slot": primer_slot, "committees": [0],
             "oracle": False, "guard": {}}])
        next_id += 1
        primer = await take_burst(minters[0], 600)
        await feeder.feed(primer)
        check_set_up_burst(primer)
        used.add(primer_slot)
        primed.add(primer_slot // spe)
    t = ctx.mark("primer_bursts_s", t)
    first = next_id
    for w, m in enumerate(minters):
        m.send(cmd="mint", bursts=[
            {"id": first + k, "role": "window", "slot": s, "oracle": k == 0}
            for k, s in enumerate(slots) if k % W == w])
    while sum(m.frames.qsize() for m in minters) < min(
            int(mix["min_banked_bursts"]), len(slots)) or minters[0].frames.empty():
        expect(time.perf_counter() - t < 600, "the bank did not fill")
        await asyncio.sleep(0.01)
    t = ctx.mark("bank_wait_s", t)
    banked = sum(m.frames.qsize() for m in minters)

    # ---- the window: closed loop, one burst in flight
    window, seconds = ctx.window, float(ctx.args.seconds)
    n_setup = len(feeder.fed)
    late, verified = [], 0
    books0 = books(ctx)
    collector = FullCollections()
    full0 = collector.read()
    window.open()
    t_ready = window.t_open
    for k in range(len(slots)):
        if window.over():
            break
        burst = await take_burst(minters[k % W], 600)
        expect(burst["id"] == first + k, "bursts out of order")
        t_pushed = await feeder.feed(burst)
        late.append(t_pushed - t_ready)  # previous verdict back -> fully pushed
        t_ready = burst["t_done"]
        verified += len(burst["items"])
        window.item_boundary(verified)
    window.close()
    full1 = collector.read()
    books1 = books(ctx)
    for m in minters:
        m.send(cmd="stop")
    for burst, slot_now in feeder.held:
        plain.send_votes(burst, slot_now)

    # ---- the books: every verdict against the minted truth
    t_end = window.t_open + seconds
    in_window = feeder.fed[n_setup:]
    verdicts, pushed = ctx.verdicts, feeder.pushed
    lat, t_last, attempted, failed, accepted = [], window.t_open, 0, 0, 0
    for burst in in_window:
        for (msg_id, _), got, want in zip(burst["items"], burst["got"], burst["expect"]):
            attempted += 1
            failed += got != want
            accepted += got == "A"
            t_v = verdicts[msg_id][1]
            if got == want and t_v <= t_end:
                lat.append(t_v - pushed[msg_id])
                t_last = max(t_last, t_v)
    # (a traced run is slowed by the profiler and reports no end-to-end metric)
    expect(lat or ctx.args.trace, "no vote was verified inside the window")
    lat.sort()
    lat = lat or [float("nan")]
    facts = {
        "attempted": attempted, "failed": failed,
        "bursts": len(in_window),
        "attestations": sum(len(b["items"]) for b in in_window),
        "end_to_end": {
            # all the work and all the time from the window's opening to the
            # last verdict inside it (a flush's verdicts come back together)
            "agg_verified_per_s": len(lat) / max(t_last - window.t_open, 1e-9),
            "agg_verdict_p95_ms": quantile(lat, 0.95) * 1e3,
        },
    }
    late_ms = sorted(x * 1e3 for x in late)
    banked_at_close = sum(m.frames.qsize() for m in minters)
    note(window={
        "seconds": seconds, "slots": slots, "bursts_whole": len(in_window),
        "votes_in_window": len(lat), "to_last_verdict_s": t_last - window.t_open,
        "drained_every_burst": len(in_window) == len(slots),
        "vote_verdict_p50_ms": quantile(lat, 0.5) * 1e3,
        "vote_verdict_p95_ms": quantile(lat, 0.95) * 1e3,
        "burst_s": [round(b["t_done"] - pushed[b["items"][0][0]], 3) for b in in_window]},
         generator={
        "late_ms_p50": quantile(late_ms, 0.5), "late_ms_max": late_ms[-1],
        "banked_at_open": banked, "banked_at_close": banked_at_close,
        # the collector's full passes inside the window: how many, how long
        "full_collections": full1[0] - full0[0],
        "full_collections_s": round(full1[1] - full0[1], 3),
        "mint_s_per_burst_p50": quantile(sorted(b["mint_s"] for b in in_window), 0.5),
        "oracle_s": [round(b["oracle_s"], 1) for b in feeder.fed if b["oracle"] is not None]})

    # ---- the window's guarantees, each an exact comparison
    gained = {k: books1[k] - books0[k] for k in books0}
    hold(compared, "subnet_votes_shed", gained["shed"],
         f"{gained['shed']} vote(s) shed from the subnet lane inside the window")
    hold(compared, "votes_not_through_single_signer_shape",
         max(0, accepted - gained["single"]),
         f"{accepted} votes accepted, {gained['single']} entries in the single-signer shape")
    hold(compared, "entries_through_uncached_chain", gained["points"],
         f"{gained['points']} entries went through chain_verify with host-packed points")
    hold(compared, "host_path_verifies", gained["host_verifies"],
         f"{gained['host_verifies']} batched verifies took the host path")
    clock = CompileClock.delta(window.clock0, window.clock1)
    compiles = clock["backend_compiles"] + clock["aot_lowers"]
    hold(compared, "compiles_inside_window", compiles, f"{compiles} compile(s) inside the window")

    # ---- minted truth == the native library (guard burst, first window burst)
    checked = [b for b in feeder.fed if b["oracle"] is not None]
    expect({b["role"] for b in checked} >= {"guard", "window"},
           "the host oracle did not run over a guard and a window burst")
    differ = sum(ok == bool(bad)
                 for b in checked for ok, bad in zip(b["oracle"], b["bad"]))
    hold(compared, "oracle_differs_from_minting", differ,
         f"the native library disagrees with the minting of {differ} signature(s)")
    expect(any(not ok for b in checked for ok in b["oracle"]), "no REJECT was exercised")

    # ---- every verdict and the latest-message table == the plain reference
    t0 = time.perf_counter()
    held = plain.take("state", 600)
    hold(compared, "plain_anchor_root_differs",
         int(bytes.fromhex(held["anchor_root"]) != ctx.anchor_root),
         "the plain reference roots the anchor block differently")
    differ = 0
    for burst in feeder.fed:
        answer = plain.take("votes", 900)
        expect(len(answer["verdicts"]) == len(burst["got"]), "the plain reference lost a vote")
        differ += sum(a != b for a, b in zip(answer["verdicts"], burst["got"]))
    hold(compared, "plain_verdicts_differ", differ,
         f"{differ} verdict(s) differ from the plain reference's")
    plain.send({"cmd": "table"})
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
    note(checked={"oracle_bursts": [b["role"] for b in checked],
                  "accepted_in_window": accepted, "gained": gained,
                  "latest_messages": len(latest), "plain_seen": len(validators),
                  "plain_wait_s": time.perf_counter() - t0})
    return facts
