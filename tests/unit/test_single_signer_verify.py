"""The single-signer verify shape of the cached device chain.

An entry with exactly one attester goes to ``batch_verify_each_cached``
as ``(validator_index, None, signing_root, sig_point)``: its pubkey is
gathered from the registry planes on the device by index
(``ops/bls_batch`` ``single_gather``) — no committee sum, no correction
table, no host point.  Held here (CPU, interpret mode) to
``batch_verify_each_points`` on the host path over the same seeded keys
and messages.
"""

import random

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
from lambda_ethereum_consensus_tpu.ops import bls_batch as BB

N_REG = 16
MSGS = [b"subnet-root-%d" % i for i in range(3)]
COMMITTEES = np.array([[0, 1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15]], np.int32)




def _counter(shape: str) -> float:
    total = 0.0
    for line in telemetry.get_metrics().render_prometheus(self_scrape=False).splitlines():
        if line.startswith("bls_chain_entries_total{") and f'shape="{shape}"' in line:
            total += float(line.rsplit(" ", 1)[1])
    return total


def _counters() -> dict:
    return {s: _counter(s) for s in ("single", "committee", "points")}


def _gained(before: dict) -> dict:
    return {s: _counter(s) - v for s, v in before.items()}


@pytest.fixture(scope="module")
def runs():
    """Every chained verify of this file, made once (interpret mode costs
    ~15 s a call whatever the batch): four calls in all, of three shapes (the ``points``
    shape of the uncached chain is counted in ``test_bls_chain.py``).  16-bit RLC
    coefficients and a 16-step ladder, as the benchmark's rehearsal runs."""
    rng = random.Random(28)
    sks = [rng.randrange(1, 1 << 96) for _ in range(N_REG)]
    reg = [C.g1.multiply_raw(C.G1_GENERATOR, sk) for sk in sks]
    hs = [hash_to_g2(m, DST_POP) for m in MSGS]
    rx, ry = BB._g1_planes(reg)
    cache = BB.DeviceCommitteeCache((rx, ry), COMMITTEES, interpret=True, chunk=2, mmax=2)

    def single(v, g, sk=None):
        """(cached entry, point entry) of validator ``v`` over message
        ``g``; ``sk`` overrides the signing secret."""
        sig = C.g2.multiply_raw(hs[g], sks[v] if sk is None else sk)
        return (v, None, MSGS[g], sig), (reg[v], MSGS[g], sig)

    def aggregate(cid, missing, g, corrupt=False):
        members = [int(m) for m in COMMITTEES[cid] if int(m) not in missing]
        sig = C.g2.multiply_raw(hs[g], sum(sks[m] for m in members) + (1 if corrupt else 0))
        pk = None
        for m in members:
            pk = reg[m] if pk is None else C.g1.affine_add(pk, reg[m])
        return (cid, list(missing), MSGS[g], sig), (pk, MSGS[g], sig)

    def both(pairs):
        before = _counters()
        cached = batch_verify_each_cached(cache, [c for c, _ in pairs])
        gained = _gained(before)
        return {"cached": cached, "host": batch_verify_each_points([p for _, p in pairs]),
                "gained": gained}

    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batch_mod, "_COEFF_BITS", 16)
        # (1 call) a subnet drain: single signers only, all valid
        out["valid"] = both([single(v, v % 3) for v in (0, 3, 5, 9, 12, 15)])
        # (2 calls of one shape) blame by bisection: a valid vote, validator
        # 3's signature sent under index 4 (a wrong secret for that key), an
        # undecodable signature (None: invalid without the device)
        undecodable = ((6, None, MSGS[0], None), (reg[6], MSGS[0], None))
        out["blame"] = both([single(1, 0), single(4, 1, sk=sks[3]), undecodable])
        # (1 call, three checks) what the aggregate lane can mix: single signers
        # and few-missing aggregates in one call of the chain, each verified
        # against its own pubkey — both programs run, then the select
        checks = [
            [single(1, 0), aggregate(0, {2}, 0)],
            [single(4, 1, sk=sks[3]), aggregate(1, set(), 1)],
            [single(13, 2), aggregate(1, {8, 15}, 2, corrupt=True)],
        ]
        before = _counters()
        out["mixed"] = BB.chain_verify_cached(cache, [
            ([(c[0], c[1], c[3], 3 + 2 * i) for i, (c, _) in enumerate(pairs)],
             hs, [MSGS.index(c[2]) for c, _ in pairs]) for pairs in checks],
            coeff_bits=16)
        out["mixed_gained"] = _gained(before)
        out["mixed_host"] = [all(batch_verify_each_points([p for _, p in pairs]))
                             for pairs in checks]
        # (no call of the chain) a registry key replaced after update(): the
        # store bumps its version, a cache built before keeps its snapshot
        store = BB.RegistryPlaneStore(interpret=True, min_capacity=16)
        store.update(rx, ry)
        pair = np.array([[5, 6]], np.int32)  # the committee sums are not used here
        old_cache = BB.DeviceCommitteeCache(store, pair, chunk=1, mmax=2)
        reg2 = list(reg)
        reg2[5] = C.g1.multiply_raw(C.G1_GENERATOR, 0xBEEF)
        rx2, ry2 = BB._g1_planes(reg2)
        version = store.version
        store.update(rx2, ry2)
        out["version_bumped"] = store.version == version + 1
        new_cache = BB.DeviceCommitteeCache(store, pair, chunk=1, mmax=2)
        gathered = lambda cache: [np.asarray(p)[:, 0] for p in cache.gather_single([5])]  # noqa: E731
        out["old_cache_reads_old_key"] = all(
            np.array_equal(g, p[:, 5]) for g, p in zip(gathered(old_cache), (rx, ry)))
        out["new_cache_reads_new_key"] = all(
            np.array_equal(g, p[:, 5]) for g, p in zip(gathered(new_cache), (rx2, ry2)))
    return out


@pytest.mark.device
def test_single_signers_all_valid(runs):
    assert runs["valid"]["cached"] == runs["valid"]["host"] == [True] * 6


@pytest.mark.device
def test_single_signers_are_counted_once_per_call_in_their_shape(runs):
    # one call of the cached chain: six entries, all in the single shape
    assert runs["valid"]["gained"] == {"single": 6, "committee": 0, "points": 0}


@pytest.mark.device
def test_bisection_equals_the_host_path(runs):
    assert runs["blame"]["cached"] == runs["blame"]["host"]


@pytest.mark.device
@pytest.mark.parametrize("at,want,what", [
    (0, True, "valid single signer"),
    (1, False, "another validator's signature under this index"),
    (2, False, "undecodable signature"),
])
def test_bisection_blames_each_entry_alone(runs, at, want, what):
    assert runs["blame"]["cached"][at] is want, what


@pytest.mark.device
def test_bisection_counts_what_reached_the_device(runs):
    # the undecodable one never does; the two others are checked one by one
    assert runs["blame"]["gained"] == {"single": 2, "committee": 0, "points": 0}


@pytest.mark.device
@pytest.mark.parametrize("at,want,what", [
    (0, True, "a valid vote beside a valid aggregate with one missing member"),
    (1, False, "a forged vote beside a valid full aggregate"),
    (2, False, "a valid vote beside an aggregate with a corrupted signature"),
])
def test_mixed_call_gives_each_shape_its_verdict(runs, at, want, what):
    assert runs["mixed"][at] is want, what
    assert runs["mixed_host"][at] is want, what


@pytest.mark.device
def test_mixed_call_counts_both_shapes(runs):
    assert runs["mixed_gained"] == {"single": 3, "committee": 3, "points": 0}


@pytest.mark.device
def test_replaced_registry_key_is_not_read_through_an_old_cache(runs):
    """``gather_single`` under index 5, through a cache built before the
    key was replaced and through one built after."""
    assert runs["version_bumped"]
    assert runs["old_cache_reads_old_key"]
    assert runs["new_cache_reads_new_key"]
