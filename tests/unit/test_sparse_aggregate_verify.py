"""The smaller-side aggregation of the cached device chain (PR 34).

An aggregate's public key is summed on the device at ANY participation:
``full_sum[committee] - sum(missing)`` while the missing members are at
most half the committee, ``sum(attesting)`` from the identity beyond that
(``ops/bls_batch`` ``smaller_side`` / ``agg_corrected``), at a gather width
chosen from the call's longest list (``DeviceCommitteeCache.widths``).
Held here (CPU, interpret mode) to the host oracle's ``g1.affine_add`` walk
over the same seeded keys: the aggregation program alone over every miss
count that changes side or width, a flush that mixes single, dense and
sparse entries with one wrong-secret signature, and a block of sparse
aggregates through ``process_attestations`` against the non-device path.
"""

import functools
import inspect
import random

import numpy as np
import pytest

from lambda_ethereum_consensus_tpu import telemetry
from lambda_ethereum_consensus_tpu.config import constants, minimal_spec, use_chain_spec
from lambda_ethereum_consensus_tpu.crypto import bls
from lambda_ethereum_consensus_tpu.crypto.bls import batch as batch_mod
from lambda_ethereum_consensus_tpu.crypto.bls import curve as C
from lambda_ethereum_consensus_tpu.crypto.bls.batch import (
    batch_verify_each_cached,
    batch_verify_each_points,
)
from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import DST_POP, hash_to_g2
from lambda_ethereum_consensus_tpu.fork_choice import handlers
from lambda_ethereum_consensus_tpu.ops import bls_batch as BB
from lambda_ethereum_consensus_tpu.ops.bls_g1 import _ints_batch
from lambda_ethereum_consensus_tpu.state_transition import accessors, misc, operations
from lambda_ethereum_consensus_tpu.state_transition.core import state_transition
from lambda_ethereum_consensus_tpu.state_transition.genesis import build_genesis_state
from lambda_ethereum_consensus_tpu.types.beacon import Attestation, AttestationData, Checkpoint
from lambda_ethereum_consensus_tpu.validator import build_signed_block

K = 16  # committee size: mmax 2, widths (2, 4, 8), k/2 = 8
N_REG = 2 * K
COMMITTEES = np.arange(N_REG, dtype=np.int32).reshape(2, K)
MSGS = [b"sparse-root-%d" % i for i in range(2)]
# every miss count at which the side or the width changes
MISSES = {"0": 0, "1": 1, "mmax": 2, "mmax+1": 3, "k/2": 8, "k/2+1": 9,
          "k-2": 14, "k-1": 15, "k": 16}


def _labelled(family: str, **labels) -> float:
    total = 0.0
    for line in telemetry.get_metrics().render_prometheus(self_scrape=False).splitlines():
        if line.startswith(family + "{") and all(
                f'{k}="{v}"' in line for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _agg_counter() -> dict:
    return {(w, side): _labelled("bls_agg_entries_total", width=w, side=side)
            for w in ("2", "4", "8") for side in ("missing", "attesting")}


def _chain_counter() -> dict:
    return {s: _labelled("bls_chain_entries_total", shape=s)
            for s in ("single", "committee", "points")}


def _gained(before: dict, after: dict) -> dict:
    return {k: after[k] - v for k, v in before.items() if after[k] != v}


@pytest.fixture(scope="module")
def keys():
    rng = random.Random(34)
    sks = [rng.randrange(1, 1 << 96) for _ in range(N_REG)]
    reg = [C.g1.multiply_raw(C.G1_GENERATOR, sk) for sk in sks]
    rx, ry = BB._g1_planes(reg)
    cache = BB.DeviceCommitteeCache((rx, ry), COMMITTEES, interpret=True, chunk=2)
    return sks, reg, cache


def host_sum(reg, members):
    acc = None
    for m in members:
        acc = reg[m] if acc is None else C.g1.affine_add(acc, reg[m])
    return acc


def split(rng, cid: int, misses: int):
    """``(attesting, missing)`` of committee ``cid`` with ``misses`` seeded
    absentees, both in committee order as ``ctx.participation`` gives them."""
    mask = np.ones(K, bool)
    mask[rng.sample(range(K), misses)] = False
    return COMMITTEES[cid][mask], COMMITTEES[cid][~mask]


# ------------------------------------------- the aggregation program alone


def test_widths_follow_the_committee(keys):
    _, _, cache = keys
    assert (cache.mmax, cache.wmax, cache.widths) == (2, 8, (2, 4, 8))
    # mainnet's committee of 512: the three buckets the issue names
    assert BB._pow2(512 // 8) == 64 and BB._pow2(512 // 2) == 256


@pytest.fixture(scope="module")
def sums(keys):
    """One call of the aggregation program per miss count (an entry of
    each committee), planes built as ``chain_verify_cached`` builds them."""
    _, reg, cache = keys
    rng = random.Random(7)
    out = {}
    for name, misses in MISSES.items():
        entries, expect = [], []
        for cid in (0, 1):
            attesting, missing = split(rng, cid, misses)
            side = BB.smaller_side(attesting, missing)
            entries.append((cid, side, None, None))
            expect.append(host_sum(reg, attesting.tolist()))
        before = _agg_counter()
        cid, is_single, idx, idx_inf, att = BB._pack_members(cache, entries, 4)
        gained = _gained(before, _agg_counter())
        ax, ay, inf = cache.aggregate(cid, idx, idx_inf, att)
        xs = _ints_batch(np.asarray(ax).T.astype(np.int32))
        ys = _ints_batch(np.asarray(ay).T.astype(np.int32))
        out[name] = {"got": list(zip(xs, ys))[:2], "inf": np.asarray(inf)[:2].tolist(),
                     "expect": expect, "width": idx.shape[1], "side": entries[0][1][1],
                     "listed": len(entries[0][1][0]), "gained": gained}
    return out


@pytest.mark.parametrize("name", list(MISSES))
def test_aggregation_program_matches_the_host_sum(sums, name):
    """The device's sum over the smaller side equals the host oracle's
    walk over the participants, at every miss count where the side or the
    width changes; with nobody attesting the entry comes back dead."""
    misses, got = MISSES[name], sums[name]
    # the side: the missing while they are at most k/2 (ties subtract)
    assert got["side"] is (misses > K // 2)
    assert got["listed"] == min(misses, K - misses)
    assert got["width"] == {0: 2, 1: 2, 2: 2, 3: 4, 8: 8, 9: 8, 14: 2, 15: 2, 16: 2}[misses]
    side = "attesting" if misses > K // 2 else "missing"
    assert got["gained"] == {(str(got["width"]), side): 2.0}
    if misses == K:
        assert got["inf"] == [True, True]
    else:
        assert got["inf"] == [False, False]
        assert got["got"] == got["expect"]


def test_a_list_longer_than_half_the_committee_is_refused(keys):
    """A caller that breaks the smaller-side contract (the longer list
    handed in) is refused loudly, never truncated."""
    _, _, cache = keys
    attesting, missing = split(random.Random(1), 0, K // 2 + 1)
    with pytest.raises(ValueError, match="missing members exceeds cache capacity 8"):
        BB._pack_members(cache, [(0, missing.tolist(), None, None)], 4)
    with pytest.raises(ValueError, match="exceeds cache capacity"):
        BB.chain_verify_cached(
            cache, [([(0, BB.CommitteeSide(missing, False), None, 3)], [None], [0])])


# -------------------- a flush that mixes single, dense and sparse entries


@functools.lru_cache(maxsize=None)
def message_point(g: int):
    return hash_to_g2(MSGS[g], DST_POP)


def flush_entry(keys, rng, cid, misses, g, corrupt=False):
    """One entry of a flush — committee ``cid`` with ``misses`` seeded
    absentees signing message ``g`` (``corrupt``: with a wrong secret) —
    as ``batch_verify_each_cached`` takes it (a single signer where one
    member is left, else the smaller side) and as the host oracle does."""
    sks, reg, _ = keys
    attesting, missing = split(rng, cid, misses)
    members = attesting.tolist()
    sk = sum(sks[m] for m in members) + (1 if corrupt else 0)
    sig = C.g2.multiply_raw(message_point(g), sk)
    if len(members) == 1:
        cached = (members[0], None, MSGS[g], sig)
    else:
        cached = (cid, BB.smaller_side(attesting, missing), MSGS[g], sig)
    return cached, (host_sum(reg, members), MSGS[g], sig)


@pytest.fixture(scope="module")
def mixed_flush(keys):
    """Six entries of one flush — a single signer, two dense aggregates
    (within ``mmax``), three sparse ones (both sides, the widest bucket),
    one of them signed with a wrong secret — through
    ``batch_verify_each_cached`` (4 chained calls: the flush, then three
    levels of bisection), against ``batch_verify_each_points``."""
    _, _, cache = keys
    entry = functools.partial(flush_entry, keys, random.Random(11))
    pairs = [entry(0, K - 1, 0), entry(0, 1, 0), entry(1, 0, 1),
             entry(1, 6, 1), entry(0, 7, 0, corrupt=True), entry(1, 11, 1)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batch_mod, "_COEFF_BITS", 16)
        agg0, chain0 = _agg_counter(), _chain_counter()
        cached = batch_verify_each_cached(cache, [c for c, _ in pairs])
        gained = _gained(agg0, _agg_counter()), _gained(chain0, _chain_counter())
        host = batch_verify_each_points([p for _, p in pairs])
    return {"cached": cached, "host": host, "agg": gained[0], "chain": gained[1]}


def test_mixed_flush_blames_the_wrong_secret_alone(mixed_flush):
    assert mixed_flush["cached"] == mixed_flush["host"] == [
        True, True, True, True, False, True]


def test_mixed_flush_counts_entries_by_width_and_side(mixed_flush):
    """A call of the chain is padded to its widest entry: the flush's first
    check (6 entries) holds sparse ones, so it runs at width 8.  The three
    bisection levels re-check on that check's laddered planes
    (``chain_recheck``) and book no entry.  No entry takes the uncached
    chain."""
    agg, chain = mixed_flush["agg"], mixed_flush["chain"]
    assert chain.get("points", 0) == 0
    assert chain["single"] == 1
    # missing side: dense 1, dense 0, sparse 6, bad 7; attesting side: sparse 11
    assert agg == {("8", "missing"): 4, ("8", "attesting"): 1}
    assert sum(agg.values()) == chain["committee"]


# ------------------ a flush that fills its entry budget (n == b, PR 35)


def _lanes() -> dict:
    return {use: _labelled("bls_chain_lanes_total", use=use) for use in ("live", "pad")}


@pytest.fixture(scope="module")
def full_flushes(keys):
    """Cached calls with no padding lane (interpret mode: quantum 8), their
    (group, slot) rectangles padded all the same: a flush of 8 that mixes
    single signers and both committee sides with a wrong secret in the LAST
    lane (4 chained calls: 8, then 4 + 4, 2 + 2, 1 + 1 entries re-checked on
    the first call's laddered planes), 8 single
    signers (1 call), 16 aggregates of both sides (1 call) — against the
    host oracle over the same points."""
    _, _, cache = keys
    entry = functools.partial(flush_entry, keys, random.Random(35))
    flushes = {
        # groups of 5 and 3: s = 8, so 3 + 5 padded slots and an empty group
        "blame8": [entry(0, K - 1, 0), entry(0, 1, 0), entry(1, 0, 0), entry(1, 6, 0),
                   entry(0, 11, 0), entry(1, 14, 1), entry(1, K - 1, 1),
                   entry(0, 7, 1, corrupt=True)],
        "single8": [entry(i % 2, K - 1, g) for i, g in enumerate((0, 0, 0, 0, 0, 0, 1, 1))],
        # groups of 11 and 5: s = 16
        "valid16": [entry(i % 2, misses, int(i >= 11)) for i, misses in enumerate(
            (0, 1, 2, 3, 6, 8, 9, 11, 13, 14, 5, 7, 10, 12, 4, 14))],
    }
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batch_mod, "_COEFF_BITS", 16)
        for name, pairs in flushes.items():
            lanes0, chain0 = _lanes(), _chain_counter()
            cached = batch_verify_each_cached(cache, [c for c, _ in pairs])
            out[name] = {"cached": cached,
                         "lanes": _gained(lanes0, _lanes()),
                         "chain": _gained(chain0, _chain_counter()),
                         "host": batch_verify_each_points([p for _, p in pairs])}
    return out


@pytest.mark.parametrize("name,want", [
    ("blame8", [True] * 7 + [False]), ("single8", [True] * 8), ("valid16", [True] * 16)])
def test_a_full_flush_reads_the_host_oracles_verdicts(full_flushes, name, want):
    assert full_flushes[name]["cached"] == full_flushes[name]["host"] == want


@pytest.mark.parametrize("name,lanes,chain", [
    # the first call, full; its levels re-check on its planes and book no lane
    ("blame8", {"live": 8.0}, {"single": 2, "committee": 6}),
    ("single8", {"live": 8.0}, {"single": 8.0}),  # pad gains nothing
    ("valid16", {"live": 16.0}, {"committee": 16.0}),
])
def test_a_full_flush_books_no_padding_lane(full_flushes, name, lanes, chain):
    assert full_flushes[name]["lanes"] == lanes
    assert full_flushes[name]["chain"] == chain


# ------------- a flush below the warmed drain is padded up to its layout

WARMED = BB.ChainLayout(b=16, checks=1, m1=7, s=4, e=16)


@pytest.fixture()
def warmed_layout(monkeypatch):
    """One warmed layout registered for the test alone."""
    monkeypatch.setattr(BB, "_WARMED_LAYOUTS", set())
    BB.register_chain_layout(WARMED)
    return WARMED


def _checks(*sizes_by_group):
    """Layout-only checks: one per argument, a group of each given size."""
    out = []
    for sizes in sizes_by_group:
        gids = [g for g, n in enumerate(sizes) for _ in range(n)]
        out.append(([None] * len(gids), [None] * len(sizes), gids))
    return out


@pytest.mark.parametrize("checks,own,padded", [
    # (checks, the call's own layout, whether the warmed one takes it)
    (_checks([1]), (8, 1, 1, 1, 1), True),  # a deadline flush of one
    (_checks([3, 2]), (8, 1, 3, 4, 8), True),
    (_checks([4] * 4), (16, 1, 7, 4, 16), True),  # the warmed drain itself: full
    (_checks([1] * 7), (8, 1, 7, 1, 8), True),  # as many groups as it holds
    (_checks([1] * 8), (8, 1, 15, 1, 8), False),  # one group too many; a full call
    (_checks([5, 1]), (8, 1, 3, 8, 8), False),  # a group too large
    (_checks([4] * 4 + [1]), (24, 1, 7, 4, 32), False),  # too many entries
    (_checks([2], [1]), (8, 2, 1, 2, 2), False),  # a bisection level: two checks
])
def test_a_call_inside_a_warmed_layout_is_padded_up_to_it(warmed_layout, checks, own, padded):
    """Every distinct layout is a set of six programs: a call that fits
    inside the warmed one on every axis runs at it, any other at its own
    pow2-padded layout, as before.  The dead slot is no lane of either
    (the index b, which ``prep`` reads as the identity:
    ``test_bls_chain.py``), so a call may fill its layout (b == n)."""
    n = sum(len(c[0]) for c in checks)
    layout = BB._chain_layout(checks, interpret=True)
    assert layout == (warmed_layout if padded else BB.ChainLayout(*own))
    BB._WARMED_LAYOUTS.clear()  # nothing warmed: every call at its own
    assert BB._chain_layout(checks, interpret=True) == BB.ChainLayout(*own)
    assert layout.b >= own[0] >= n > own[0] - 8  # never a whole quantum of padding lanes


def test_a_padded_flush_reads_the_same_verdicts(keys, warmed_layout, monkeypatch):
    """Three aggregates, one signed with a wrong secret, through the chain
    with a larger layout warmed: the flush is dispatched at the warmed
    shapes (its bisection levels, two checks each, at their own on the
    first call's planes and so at its b), and the blame falls where it
    falls unpadded."""
    sks, reg, cache = keys
    rng = random.Random(5)
    hs = [hash_to_g2(m, DST_POP) for m in MSGS]
    entries = []
    for cid, misses, bad in ((0, 1, 0), (1, 6, 1), (0, 11, 0)):
        attesting, missing = split(rng, cid, misses)
        sig = C.g2.multiply_raw(hs[cid], sum(sks[m] for m in attesting.tolist()) + bad)
        entries.append((cid, BB.smaller_side(attesting, missing), MSGS[cid], sig))
    shapes = []
    prep = cache._ops["prep"]

    def recording(jac1, jac2, idx_g1, idx_sig, *rest):
        shapes.append((jac1[0].shape[-1], *idx_g1.shape, idx_sig.shape[-1]))
        return prep(jac1, jac2, idx_g1, idx_sig, *rest)

    monkeypatch.setitem(cache._ops, "prep", recording)
    monkeypatch.setattr(batch_mod, "_COEFF_BITS", 16)
    assert batch_verify_each_cached(cache, entries) == [True, False, True]
    assert shapes[0] == tuple(warmed_layout)  # the flush: 3 entries at (16, 1, 7, 4, 16)
    assert [sh[1] for sh in shapes[1:]] == [2, 2]  # [a] with [bad, c]; [bad] with [c]
    assert [sh[0] for sh in shapes[1:]] == [16, 16]  # the flush's planes
    padded = list(shapes)
    BB._WARMED_LAYOUTS.clear()
    shapes.clear()
    assert batch_verify_each_cached(cache, entries) == [True, False, True]
    assert shapes[0] == (8, 1, 3, 2, 4) and [sh[0] for sh in shapes[1:]] == [8, 8]
    assert [sh[1:] for sh in shapes[1:]] == [sh[1:] for sh in padded[1:]]


@pytest.mark.parametrize("entries,groups,want", [
    # a full flush: no second tile of lanes for the dead slot
    (1024, 64, (1024, 1, 127, 16, 1024)),  # a slot's aggregate channel (head cells)
    (4096, 64, (4096, 1, 127, 64, 4096)),  # a flush of the all-subnets drain
])
def test_the_warmer_registers_the_layout_it_dispatches(monkeypatch, entries, groups, want):
    """``start_warmer`` advertises the drain's layout, beside its shape
    bucket, before the background dispatch — the one layout a smaller
    flush is padded up to — and the rungs of its bisection ladder (two
    checks each: ``test_bisection_ladder.py``)."""
    from lambda_ethereum_consensus_tpu.node import warmup
    from lambda_ethereum_consensus_tpu.ops import aot

    shapes = warmup.DrainShapes(n_validators=1 << 20, n_committees=2048, committee=512,
                                entries=entries, groups=groups)
    assert shapes.chain_layout(interpret=False) == BB.ChainLayout(*want)
    monkeypatch.setattr(BB, "_WARMED_LAYOUTS", set())
    monkeypatch.setattr(aot, "_SHAPE_BUCKETS", {})
    monkeypatch.setattr(BB, "_use_planes", lambda: True)
    for name in ("warm_drain_programs", "warm_transition", "warm_witness", "warm_duties",
                 "warm_kzg"):
        monkeypatch.setattr(warmup, name, lambda *a, **k: 0.0)
    warmup.start_warmer(shapes, {}).join()
    ladder = shapes.bisection_layouts(interpret=False)
    assert [w.checks for w in ladder] == [2] * (entries.bit_length() - 1)
    registered = sorted({BB.ChainLayout(*want), *ladder})
    assert BB.warmed_chain_layouts() == tuple(registered)
    assert [w for w in BB.warmed_chain_layouts() if w.checks == 1] == [BB.ChainLayout(*want)]
    assert aot.shape_buckets("attestation_entries") == (entries,)
    assert "aggregate_entries" not in aot.all_shape_buckets()
    # ... and /debug/compile shows them beside the buckets
    import json

    from lambda_ethereum_consensus_tpu.api.beacon_api import BeaconApiServer

    data = json.loads(BeaconApiServer(store=None, spec=None)._debug_compile()[2])["data"]
    assert data["warmed_chain_layouts"] == [w._asdict() for w in registered]


# ------------------------------------------------ both call sites' source


def test_call_sites_sum_no_committee_key_on_the_host():
    """Neither the gossip drain nor a block's deferred batch walks a
    committee's members in Python any more: no ``affine_add`` and no
    ``_pubkey_point`` where the entries for the cached chain are built."""
    for fn in (handlers._attestation_batch_cached, operations._verify_deferred_cached):
        source = inspect.getsource(fn)
        assert "affine_add" not in source and "_pubkey_point" not in source, fn.__name__
        assert "smaller_side(attesting, missing)" in source
        assert "host_entries" not in source and "mmax" not in source


@pytest.mark.parametrize("chain_env,n,want", [
    ("1", 1, "cached"), ("1", 127, "cached"), ("1", 128, "cached"),
    (None, 1, "host"), (None, 4096, "host"),
])
def test_a_flush_below_the_device_threshold_still_takes_the_cached_drain(
        monkeypatch, chain_env, n, want):
    """Where the device chain is on, a deadline flush smaller than
    ``BLS_DEVICE_CHAIN_MIN`` (128) goes through the cached drain like any
    other: the host body costs ~0.5 s an attestation at mainnet size.  A
    host without the chain keeps the host body at any size."""
    taken = []
    monkeypatch.setattr(handlers, "_attestation_batch_cached",
                        lambda *a: taken.append("cached"))
    monkeypatch.setattr(handlers, "_attestation_batch_host",
                        lambda *a: taken.append("host"))
    monkeypatch.delenv("BLS_DEVICE_CHAIN_MIN", raising=False)
    if chain_env is None:
        monkeypatch.delenv("BLS_DEVICE_CHAIN", raising=False)
    else:
        monkeypatch.setenv("BLS_DEVICE_CHAIN", chain_env)

    class Store:
        forensics = None

    assert handlers.on_attestation_batch(Store(), [object()] * n, spec=object()) == [None] * n
    assert taken == [want]


# ------------------------------- a block of sparse aggregates, both paths

N_VALIDATORS = 256  # minimal preset: 4 committees of 8 a slot, widths (2, 4)
SKS = [(i + 1).to_bytes(32, "big") for i in range(N_VALIDATORS)]


@pytest.fixture(scope="module")
def sparse_block():
    """A block at slot 2 carrying slot 1's four committees at 5, 3, 8 and 1
    of 8 members attesting, imported by ``state_transition`` once on the
    host path and once through the cached device chain."""
    with use_chain_spec(minimal_spec()) as spec:
        genesis = build_genesis_state([bls.sk_to_pk(sk) for sk in SKS], spec=spec)
        domain = accessors.get_domain(genesis, constants.DOMAIN_BEACON_ATTESTER, 0, spec)
        header = genesis.latest_block_header.copy(state_root=genesis.hash_tree_root(spec))
        anchor_root = header.hash_tree_root(spec)
        atts = []
        for index, attending in enumerate((5, 3, 8, 1)):
            committee = accessors.get_beacon_committee(genesis, 1, index, spec)
            assert len(committee) == 8
            data = AttestationData(
                slot=1, index=index, beacon_block_root=anchor_root,
                source=Checkpoint(epoch=0, root=b"\x00" * 32),
                target=Checkpoint(epoch=0, root=anchor_root))
            root = misc.compute_signing_root(data, domain)
            bits = [p < attending for p in range(8)]
            atts.append(Attestation(
                aggregation_bits=bits, data=data,
                signature=bls.aggregate(
                    [bls.sign(SKS[v], root) for p, v in enumerate(committee) if bits[p]])))
        signed, post = build_signed_block(genesis, 2, SKS, attestations=atts, spec=spec)
        out = {"built": post.hash_tree_root(spec)}
        out["host"] = state_transition(genesis, signed, spec=spec).hash_tree_root(spec)
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("BLS_DEVICE_CHAIN", "1")
            mp.setenv("BLS_BLOCK_BATCH_MIN_MEMBERS", "1")
            mp.setattr(batch_mod, "_COEFF_BITS", 16)
            agg0, chain0 = _agg_counter(), _chain_counter()
            out["device"] = state_transition(genesis, signed, spec=spec).hash_tree_root(spec)
            out["agg"] = _gained(agg0, _agg_counter())
            out["chain"] = _gained(chain0, _chain_counter())
    return out


def test_block_of_sparse_aggregates_same_state_root_on_both_paths(sparse_block):
    assert sparse_block["device"] == sparse_block["host"] == sparse_block["built"]


def test_block_of_sparse_aggregates_stays_on_the_device(sparse_block):
    """3 missing of 8 and 3 attesting of 8 select the width-4 program, the
    full committee rides along padded, the one-bit vote is a single
    signer; nothing reaches the uncached chain."""
    assert sparse_block["chain"] == {"single": 1.0, "committee": 3.0}
    assert sparse_block["agg"] == {("4", "missing"): 2.0, ("4", "attesting"): 1.0}
