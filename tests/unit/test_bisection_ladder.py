"""The bisection ladder the warmer holds and the books of the blame.

A flush whose random-linear-combination check fails is halved level by level
(``crypto.bls.batch``), each level one chained call of two checks.  Two axes
of a level's layout (``ops/bls_batch._chain_layout``) — groups per check and
entries per group — depend on the arrival order, so a level would otherwise
land on a layout of its own.  ``DrainShapes.bisection_layouts`` is the bound
of every draw: the warmer registers it beside the drain's layout, and a flush
with one bad entry lands every level on a rung whatever the order and the
position.  Held here:

* **the layouts and the books**, with the chain replaced by a judge that
  reads the call's layout and each range's truth (no pairing, so the chip's
  shapes are cheap: a slot's 1,024 aggregates over 64 messages), for a bad
  entry first, last, in the middle and at seeded positions of a shuffled
  flush: every level at its rung, ``bls_chain_layouts_total{layout="own"}``
  gains 0, ``bls_bisect_checks_total`` k ``pass`` + k ``fail`` for 2^k
  entries, one ``bls_bisect`` span; an all-valid flush books none;
* **the verdicts**, through the real chain in interpret mode at a minimal
  size: a shuffled flush with one bad entry, none, two in different halves,
  all bad, one whose first call is padded and one of single signers reads
  what ``batch_verify_each_points`` reads on the host;
* **the planes**: every level after a failed first check re-checks its
  ranges on that check's laddered planes (``ops/bls_batch.chain_recheck``),
  so a flush runs its aggregation and both ladders once, and
  ``bls_recheck_planes_total{planes="reused"}`` gains one a level.
"""

import random
from types import SimpleNamespace

import numpy as np
import pytest

from lambda_ethereum_consensus_tpu import telemetry
from lambda_ethereum_consensus_tpu.crypto.bls import batch as batch_mod
from lambda_ethereum_consensus_tpu.crypto.bls import curve as C
from lambda_ethereum_consensus_tpu.crypto.bls.batch import (
    batch_verify_each_cached,
    batch_verify_each_points,
)
from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import DST_POP, hash_to_g2
from lambda_ethereum_consensus_tpu.node.warmup import DrainShapes
from lambda_ethereum_consensus_tpu.ops import bls_batch as BB

L = BB.ChainLayout
# (entries, messages, interpret) -> the drain's layout, then its ladder
LADDERS = {
    # a slot's aggregate channel on the chip: 64 committees x 16 aggregators
    (1024, 64, False): [L(1024, 1, 127, 16, 1024),
                        L(1024, 2, 127, 16, 512), L(1024, 2, 127, 16, 256),
                        L(1024, 2, 127, 16, 128), L(1024, 2, 127, 16, 64),
                        L(1024, 2, 63, 16, 32), L(1024, 2, 31, 16, 16),
                        L(1024, 2, 15, 8, 8), L(1024, 2, 7, 4, 4),
                        L(1024, 2, 3, 2, 2), L(1024, 2, 1, 1, 1)],
    # interpret mode (a quantum of 8 lanes): 4 messages x 4 entries; every
    # rung at the drain's b, the lanes of the planes a level re-checks on
    (16, 4, True): [L(16, 1, 7, 4, 16),
                    L(16, 2, 7, 4, 8), L(16, 2, 7, 4, 4), L(16, 2, 3, 2, 2), L(16, 2, 1, 1, 1)],
    # a flush that is no power of two: the larger half of every range
    (24, 4, True): [L(24, 1, 7, 8, 32),
                    L(24, 2, 7, 8, 16), L(24, 2, 7, 8, 8), L(24, 2, 3, 4, 4),
                    L(24, 2, 3, 2, 2), L(24, 2, 1, 1, 1)],
}


def shapes_of(entries: int, messages: int) -> DrainShapes:
    return DrainShapes(n_validators=1 << 20, n_committees=2048, committee=512,
                       entries=entries, groups=messages)


@pytest.mark.parametrize("key", list(LADDERS), ids=lambda k: f"{k[0]}x{k[1]}-{'cpu' if k[2] else 'chip'}")
def test_the_ladder_is_the_bound_of_every_level(key):
    entries, messages, interpret = key
    shapes = shapes_of(entries, messages)
    drain, *ladder = LADDERS[key]
    assert shapes.chain_layout(interpret) == drain
    assert shapes.bisection_layouts(interpret) == ladder


def _books() -> dict:
    m = telemetry.get_metrics()
    hist = m.get_histogram("bls_bisect_seconds")
    return {"pass": m.get("bls_bisect_checks_total", result="pass"),
            "fail": m.get("bls_bisect_checks_total", result="fail"),
            "own": m.get("bls_chain_layouts_total", layout="own"),
            "warmed": m.get("bls_chain_layouts_total", layout="warmed"),
            "reused": m.get("bls_recheck_planes_total", planes="reused"),
            "spans": hist[3] if hist else 0}


def _gained(before: dict) -> dict:
    return {k: v - before[k] for k, v in _books().items()}


def shuffled_flush(entries: int, messages: int, seed: int) -> list[int]:
    """The message of each entry of a flush that carries ``entries //
    messages`` entries of every message, in a seeded arrival order."""
    order = [g for g in range(messages) for _ in range(entries // messages)]
    random.Random(seed).shuffle(order)
    return order


@pytest.fixture()
def judged(monkeypatch):
    """The chain replaced by a judge: the flush's first check
    (``chain_verify_cached_planes``) records the layout the call is
    dispatched at (``_chain_layout``, books included); every re-check
    after it runs ``chain_recheck`` itself with the tail's operands and
    dispatch replaced, recording the layout it chose.  The judge tells,
    per check, whether it holds no bad signature.  An entry's signature is
    its index in the flush; a re-check's entries are read at the lanes its
    offsets give."""
    calls, bad, interpret = [], set(), []

    def first(cache, checks, **_):
        checks = list(checks)
        calls.append(BB._chain_layout(checks, interpret[0]))
        flags = [not any(sig in bad for _, _, sig, _ in entries) for entries, _, _ in checks]
        return flags, BB.LadderedPlanes(None, None, calls[-1].b)

    def tail(checks, layout, offsets):
        calls.append(layout)
        return [not bad.intersection(range(at, at + len(entries)))
                for (entries, _, _), at in zip(checks, offsets)]

    monkeypatch.setattr(BB, "chain_verify_cached_planes", first)
    monkeypatch.setattr(BB, "_tail_operands", tail)
    monkeypatch.setattr(BB, "_dispatch_tail", lambda ops, jac1, jac2, flags: flags)
    monkeypatch.setattr(BB, "_WARMED_LAYOUTS", set())

    def run(key, order, bad_at, ladder=True):
        calls.clear()
        bad.clear()
        bad.update(bad_at)
        interpret[:] = [key[2]]
        BB._WARMED_LAYOUTS.clear()
        for layout in LADDERS[key][: None if ladder else 1]:
            BB.register_chain_layout(layout)
        messages = {(b"m%d" % g, DST_POP): object() for g in set(order)}
        flush = [(0, None, b"m%d" % g, i) for i, g in enumerate(order)]
        before = _books()
        cache = SimpleNamespace(_interpret=key[2], _ops=None)
        flags = batch_verify_each_cached(cache, flush, message_points=messages)
        return flags, list(calls), _gained(before)

    return run


POSITIONS = {"first": lambda n, rng: 0, "last": lambda n, rng: n - 1,
             "middle": lambda n, rng: n // 2,
             # the rng is seeded by the case's name: three positions of their own
             **{f"seeded{s}": lambda n, rng: rng.randrange(n) for s in range(3)}}


@pytest.mark.parametrize("where", list(POSITIONS))
@pytest.mark.parametrize("key", [(1024, 64, False), (16, 4, True), (24, 4, True)],
                         ids=["1024x64-chip", "16x4-cpu", "24x4-cpu"])
def test_every_level_lands_on_a_rung(judged, key, where):
    """One bad entry in a shuffled flush: the first call at the drain's
    layout, level k at rung k, no layout of its own; the blame books one
    ``pass`` and one ``fail`` a level and one span."""
    entries, messages, _ = key
    rng = random.Random(f"{key}:{where}")
    order = shuffled_flush(entries, messages, rng.randrange(1 << 30))
    at = POSITIONS[where](entries, rng)
    flags, layouts, gained = judged(key, order, {at})
    assert flags == [i != at for i in range(entries)]
    drain, *ladder = LADDERS[key]
    levels = len(layouts) - 1
    assert layouts[0] == drain and levels in (len(ladder), len(ladder) - 1)
    assert layouts[1:] == ladder[:levels]
    if entries & (entries - 1) == 0:
        assert levels == entries.bit_length() - 1  # k levels for 2^k entries
    assert gained == {"pass": levels, "fail": levels, "own": 0,
                      "warmed": levels + 1, "reused": levels, "spans": 1}


def test_an_all_valid_flush_books_no_blame(judged):
    key = (1024, 64, False)
    flags, layouts, gained = judged(key, shuffled_flush(1024, 64, 7), set())
    assert flags == [True] * 1024 and layouts == LADDERS[key][:1]
    assert gained == {"pass": 0, "fail": 0, "own": 0, "warmed": 1, "reused": 0,
                      "spans": 0}


def test_without_the_ladder_every_level_is_a_layout_of_its_own(judged):
    """What a node without the ladder does (the drain's layout alone
    warmed): every level after the first check is a program set of its
    own, compiled or loaded inside the drain."""
    key = (1024, 64, False)
    flags, layouts, gained = judged(key, shuffled_flush(1024, 64, 9), {700}, ladder=False)
    assert flags == [i != 700 for i in range(1024)]
    assert gained == {"pass": 10, "fail": 10, "own": 10, "warmed": 1, "reused": 10,
                      "spans": 1}
    assert all(layout.checks == 2 for layout in layouts[1:])


@pytest.mark.parametrize("bad_at,levels,books", [
    ({3, 12}, 4, {"pass": 6, "fail": 8}),  # one bad in each half of a flush of 16
    (set(range(16)), 4, {"pass": 0, "fail": 30}),  # every range fails down to one entry
])
def test_more_than_one_bad_entry_is_blamed_alone(judged, bad_at, levels, books):
    """Bad entries in different halves re-check four ranges a level: those
    levels are not a rung's (two checks) and run at their own layout."""
    key = (16, 4, True)
    flags, layouts, gained = judged(key, shuffled_flush(16, 4, 3), bad_at)
    assert flags == [i not in bad_at for i in range(16)]
    assert {k: gained[k] for k in books} == books and gained["spans"] == 1
    assert len(layouts) == 1 + levels
    assert gained["own"] == sum(layout.checks > 2 for layout in layouts) > 0


# ------------------- the verdicts through the real chain, interpret mode

K = 16  # committee size: two committees of a 32-key registry
COMMITTEES = np.arange(2 * K, dtype=np.int32).reshape(2, K)
MSGS = [b"ladder-root-%d" % g for g in range(2)]


@pytest.fixture(scope="module")
def keys():
    rng = random.Random(38)
    sks = [rng.randrange(1, 1 << 96) for _ in range(2 * K)]
    reg = [C.g1.multiply_raw(C.G1_GENERATOR, sk) for sk in sks]
    cache = BB.DeviceCommitteeCache(BB._g1_planes(reg), COMMITTEES, interpret=True, chunk=2)
    return sks, reg, cache


def real_flush(keys, n: int, bad_at: set, seed: int, single: bool = False):
    """``n`` aggregates over two messages, half each, shuffled; the ones at
    ``bad_at`` signed with a wrong secret: the entries as the drain builds
    them and as the host oracle takes them.  ``single``: ``n`` votes of one
    attester each, the subnet drain's shape (``members`` None)."""
    sks, reg, _ = keys
    rng = random.Random(seed)
    cached, host = [], []
    for i, g in enumerate(shuffled_flush(n, 2, seed)):
        cid = i % 2
        mask = np.zeros(K, bool)
        if single:
            mask[rng.randrange(K)] = True
        else:
            mask[:] = True
            mask[rng.sample(range(K), rng.randrange(K // 2))] = False
        attesting, missing = COMMITTEES[cid][mask], COMMITTEES[cid][~mask]
        sk = sum(sks[m] for m in attesting.tolist()) + (i in bad_at)
        sig = C.g2.multiply_raw(hash_to_g2(MSGS[g], DST_POP), sk)
        key = None
        for m in attesting.tolist():
            key = reg[m] if key is None else C.g1.affine_add(key, reg[m])
        if single:
            cached.append((int(attesting[0]), None, MSGS[g], sig))
        else:
            cached.append((cid, BB.smaller_side(attesting, missing), MSGS[g], sig))
        host.append((key, MSGS[g], sig))
    return cached, host


# name -> (entries, bad positions, single signers)
FLUSHES = {
    "one-bad": (4, {2}, False),
    "all-valid": (4, set(), False),
    "two-bad-halves": (4, {0, 3}, False),
    "all-bad": (2, {0, 1}, False),
    # 6 entries: the first call's b is 8, two padding lanes
    "padded": (6, {4}, False),
    "single-signer": (4, {1}, True),
}


@pytest.fixture(scope="module")
def through_the_chain(keys):
    """Each flush of ``FLUSHES`` once through ``batch_verify_each_cached``
    on the real chain (interpret mode, 16-bit coefficients, the drain's
    layout and its ladder warmed), with spies on the cache's aggregation
    and gather and on both ladders: its flags, the host oracle's, the books
    gained and the calls each spy saw."""
    cache = keys[2]
    done, seen = {}, {}

    def spy(name, fn):
        def counted(*args, **kwargs):
            seen[name] += 1
            return fn(*args, **kwargs)
        return counted

    def run(name):
        if name in done:
            return done[name]
        n, bad_at, single = FLUSHES[name]
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(batch_mod, "_COEFF_BITS", 16)
            mp.setattr(BB, "_WARMED_LAYOUTS", set())
            shapes = DrainShapes(n_validators=2 * K, n_committees=2, committee=K,
                                 entries=n, groups=2)
            for layout in (shapes.chain_layout(True), *shapes.bisection_layouts(True)):
                BB.register_chain_layout(layout)
            cached, host = real_flush(keys, n, bad_at, seed=n + len(bad_at), single=single)
            seen.clear()
            seen.update(aggregate=0, gather_single=0, ladder_g1=0, ladder_g2=0)
            for attr in ("aggregate", "gather_single"):
                mp.setattr(cache, attr, spy(attr, getattr(cache, attr)))
            for op in ("ladder_g1", "ladder_g2"):
                mp.setitem(cache._ops, op, spy(op, cache._ops[op]))
            before = _books()
            flags = batch_verify_each_cached(cache, cached)
            gained = _gained(before)
            calls = dict(seen)
        done[name] = {"flags": flags, "host": batch_verify_each_points(host),
                      "truth": [i not in bad_at for i in range(n)],
                      "gained": gained, "calls": calls}
        return done[name]

    return run


@pytest.mark.parametrize("name,books", [
    ("one-bad", {"pass": 2, "fail": 2, "own": 0, "spans": 1}),
    ("all-valid", {"pass": 0, "fail": 0, "own": 0, "spans": 0}),
    ("two-bad-halves", {"pass": 2, "fail": 4, "spans": 1}),  # one bad in each half
    ("all-bad", {"pass": 0, "fail": 2, "spans": 1}),
    ("padded", {"pass": 3, "fail": 3, "own": 0, "spans": 1}),  # 6 -> 3 + 3 -> 1 + 2 -> 1 + 1
    ("single-signer", {"pass": 2, "fail": 2, "own": 0, "spans": 1}),
])
def test_the_verdicts_equal_the_host_oracle(through_the_chain, name, books):
    ran = through_the_chain(name)
    assert ran["flags"] == ran["host"] == ran["truth"]
    assert {k: ran["gained"][k] for k in books} == books


@pytest.mark.parametrize("name,levels", [
    ("one-bad", 2), ("two-bad-halves", 2), ("padded", 3), ("single-signer", 2)])
def test_a_failed_flush_ladders_its_entries_once(through_the_chain, name, levels):
    """The first check aggregates (or, for single signers, gathers) the
    flush's pubkeys and runs both ladders; every bisection level after it
    re-checks on those planes: the tail alone, booked ``reused``."""
    ran = through_the_chain(name)
    pubkeys = "gather_single" if FLUSHES[name][2] else "aggregate"
    assert ran["calls"] == {"aggregate": 0, "gather_single": 0, "ladder_g1": 1,
                            "ladder_g2": 1, pubkeys: 1}
    assert ran["gained"]["reused"] == levels
