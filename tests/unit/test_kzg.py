"""The KZG plane (da/kzg.py) vs the pure-host Jacobian oracle.

Every claim is cross-checked against independent host math: commitments
re-derived per-term with ``g1._multiply_py`` + ``affine_add``, the
pairing identity evaluated directly, and tampered inputs rejected
identically on the device plane and the host path.  Reduced-width
scalars keep the eager CPU plane ladder test-sized for the shape
sweeps; one full-width fold pins the real verify path.
"""

import random

import pytest

from lambda_ethereum_consensus_tpu.crypto.bls import curve as C
from lambda_ethereum_consensus_tpu.crypto.bls.fields import R
from lambda_ethereum_consensus_tpu.da import kzg as K

RNG = random.Random(41)

WIDTH = 4  # the minimal-preset blob width


def _tiny_kzg_buckets(monkeypatch):
    """Pin the kzg_msm bucket registry to tiny test buckets so the eager
    interpret ladder exercises the identical snap/pad/chunk logic
    without 256-lane padded batches (the duty-sign test discipline)."""
    from lambda_ethereum_consensus_tpu.ops import aot

    monkeypatch.setitem(aot._SHAPE_BUCKETS, "kzg_msm", {4, 8})


def _blob(vals):
    return b"".join(int(v).to_bytes(32, "big") for v in vals)


@pytest.fixture(scope="module")
def setup():
    return K.dev_setup(WIDTH)


@pytest.fixture(scope="module")
def sample(setup):
    blobs = [
        _blob([RNG.randrange(R) for _ in range(WIDTH)]) for _ in range(3)
    ]
    commitments = [
        K.blob_to_commitment(b, setup, device=False) for b in blobs
    ]
    proofs = [
        K.compute_blob_proof(b, c, setup, device=False)
        for b, c in zip(blobs, commitments)
    ]
    return blobs, commitments, proofs


def test_known_answer_vectors(setup):
    """Width-4 dev-setup KATs: any change to the domain order, tau
    derivation or MSM semantics moves these bytes."""
    blob = _blob([1, 2, 3, 4])
    cb = K.blob_to_commitment(blob, setup, device=False)
    assert cb.hex() == (
        "8b99dbd4ceaf9cec8b60b7b7eb5ce3f31172fdd52965dab02a765a8ce96d0cbe"
        "9caebbae290b76d1aa428e46419a0461"
    )
    assert K.versioned_hash(cb).hex() == (
        "014cc44883d862b09092eadc5d6f7cca8d3f6e9be120ee842e2539eaff00aebb"
    )
    proof, y = K.compute_proof(blob, 5, setup, device=False)
    assert proof.hex() == (
        "84b90ba58530208f9f20588bdcae04bd0e4326002a9d7eefc83b85ce10f9bfd8"
        "30ae7bff111452b9d39a17c8412ebeab"
    )
    assert K.verify_proof(cb, 5, y, proof, setup, device=False)


def test_commitment_matches_per_term_host_oracle(setup):
    """C == sum_i blob_i * [L_i(tau)]G1 re-derived with the pure-host
    Jacobian ladder, term by term."""
    vals = [RNG.randrange(R) for _ in range(WIDTH)]
    acc = None
    for v, pt in zip(vals, setup.g1_lagrange):
        acc = C.g1.affine_add(acc, C.g1._multiply_py(pt, v))
    assert K.blob_to_commitment(_blob(vals), setup, device=False) == (
        C.g1_to_bytes(acc)
    )


def test_eval_via_lagrange_barycentric_agree(setup):
    """Barycentric out-of-domain evaluation == the direct Lagrange sum,
    and in-domain points return the stored evaluation."""
    evals = [RNG.randrange(R) for _ in range(WIDTH)]
    z = RNG.randrange(R)
    # direct Lagrange: sum_i e_i * prod_{j!=i} (z-d_j)/(d_i-d_j)
    want = 0
    d = setup.domain
    for i in range(WIDTH):
        term = evals[i]
        for j in range(WIDTH):
            if j != i:
                term = (
                    term
                    * ((z - d[j]) % R)
                    % R
                    * pow((d[i] - d[j]) % R, R - 2, R)
                    % R
                )
        want = (want + term) % R
    assert K._eval_at(evals, z, d) == want
    for i in range(WIDTH):
        assert K._eval_at(evals, d[i], d) == evals[i]


def test_proof_pairing_identity_host(setup):
    """verify_proof's verdict == the pairing identity computed directly
    with the host Miller loop: e(C - yG1, G2) == e(Q, (tau - z)G2)."""
    from lambda_ethereum_consensus_tpu.crypto.bls import pairing as PP
    from lambda_ethereum_consensus_tpu.crypto.bls import fields as F

    blob = _blob([RNG.randrange(R) for _ in range(WIDTH)])
    cb = K.blob_to_commitment(blob, setup, device=False)
    z = RNG.randrange(R)
    proof, y = K.compute_proof(blob, z, setup, device=False)
    lhs = PP.pairing(
        C.g1.affine_add(
            C.g1_from_bytes(cb),
            C.g1.affine_neg(C.g1.multiply(C.G1_GENERATOR, y)),
        ),
        C.G2_GENERATOR,
    )
    rhs = PP.pairing(
        C.g1_from_bytes(proof),
        C.g2.affine_add(
            setup.g2_tau,
            C.g2.affine_neg(C.g2.multiply(C.G2_GENERATOR, z)),
        ),
    )
    assert lhs == rhs
    assert K.verify_proof(cb, z, y, proof, setup, device=False)
    assert not K.verify_proof(cb, z, (y + 1) % R, proof, setup, device=False)


def test_rlc_fold_equals_per_proof_verification(setup, sample):
    """The ONE-pairing RLC fold agrees with per-proof verification —
    on the all-valid batch and with each single item tampered."""
    blobs, commitments, proofs = sample
    per_proof = all(
        K.verify_blob_proof(b, c, p, setup, device=False)
        for b, c, p in zip(blobs, commitments, proofs)
    )
    assert per_proof
    assert K.verify_blob_batch(
        blobs, commitments, proofs, setup, device=False
    ) == per_proof

    for slot in ("blob", "commitment", "proof"):
        bl, cm, pr = list(blobs), list(commitments), list(proofs)
        if slot == "blob":
            bad = bytearray(bl[1])
            bad[-1] ^= 1
            bl[1] = bytes(bad)
        elif slot == "commitment":
            cm[1] = cm[0]
        else:
            pr[1] = pr[2]
        assert not all(
            K.verify_blob_proof(b, c, p, setup, device=False)
            for b, c, p in zip(bl, cm, pr)
        )
        assert not K.verify_blob_batch(bl, cm, pr, setup, device=False), slot


def test_zero_blob_and_malformed_inputs(setup):
    """The all-zero blob commits to infinity and still verifies; the
    non-canonical field element and garbage encodings reject."""
    zb = _blob([0] * WIDTH)
    cb = K.blob_to_commitment(zb, setup, device=False)
    assert C.g1_from_bytes(cb) is None
    bp = K.compute_blob_proof(zb, cb, setup, device=False)
    assert K.verify_blob_proof(zb, cb, bp, setup, device=False)

    with pytest.raises(K.KzgError):
        K.blob_to_field_elements(_blob([R] + [0] * (WIDTH - 1)), WIDTH)
    with pytest.raises(K.KzgError):
        K.blob_to_field_elements(b"\x00" * 31, WIDTH)
    # malformed 48-byte encodings reject like tampered ones, not raise
    garbage = b"\xff" * 48
    assert not K.verify_blob_proof(zb, garbage, bp, setup, device=False)
    assert not K.verify_blob_batch([zb], [cb], [garbage], setup, device=False)


def test_load_trusted_setup_roundtrip(setup):
    """Serialized dev-setup points load back into an equivalent setup;
    truncated / non-pow2 / infinity setups reject."""
    loaded = K.load_trusted_setup(
        [C.g1_to_bytes(pt) for pt in setup.g1_lagrange],
        C.g2_to_bytes(setup.g2_tau),
    )
    assert loaded.domain == setup.domain
    blob = _blob([7, 11, 13, 17])
    assert K.blob_to_commitment(blob, loaded, device=False) == (
        K.blob_to_commitment(blob, setup, device=False)
    )
    with pytest.raises(K.KzgError):
        K.load_trusted_setup(
            [C.g1_to_bytes(setup.g1_lagrange[0])] * 3,
            C.g2_to_bytes(setup.g2_tau),
        )
    with pytest.raises(K.KzgError):
        K.load_trusted_setup(
            [C.g1_to_bytes(None)] * 4, C.g2_to_bytes(setup.g2_tau)
        )


def test_device_msm_bitexact_across_shapes(monkeypatch):
    """The device MSM plane vs the host oracle across sub-bucket
    (3 -> pad to 4), exact-bucket (8) and chunked ragged (11 = 8 + 4)
    shapes, zero scalars and infinity lanes included — and the device
    path must have ACTUALLY run (a raising dispatch falls back to host
    silently, which would compare the oracle against itself)."""
    _tiny_kzg_buckets(monkeypatch)
    from lambda_ethereum_consensus_tpu.telemetry import get_metrics

    device0 = get_metrics().get("kzg_msm_total", path="device")
    pts = [
        C.g1.multiply(C.G1_GENERATOR, RNG.randrange(1, R)) for _ in range(11)
    ]
    ks = [RNG.getrandbits(16) for _ in range(11)]
    ks[2] = 0  # infinity lane threads through pad-and-drop
    for shape in (3, 8, 11):
        got = K._mul_batch(
            list(zip(pts[:shape], ks[:shape])), device=True, nbits=16
        )
        want = [
            C.g1._multiply_py(pt, k) if k else None
            for pt, k in zip(pts[:shape], ks[:shape])
        ]
        assert got == want, f"device plane diverged at batch {shape}"
    assert (
        get_metrics().get("kzg_msm_total", path="device") - device0
        == 3 + 8 + 11
    ), "device path did not execute; test would be vacuous"


def test_device_dispatch_snaps_to_registered_buckets(monkeypatch):
    """Every ladder dispatch is a registered bucket shape — ragged and
    empty batches included (the retrace-hazard discipline)."""
    _tiny_kzg_buckets(monkeypatch)
    seen = []
    real = K._get_msm_kernel

    def spying(nbits, interpret):
        kernel = real(nbits, interpret)

        def wrapped(bx, by, kbits):
            seen.append(int(bx.shape[-1]))
            return kernel(bx, by, kbits)

        return wrapped

    monkeypatch.setattr(K, "_get_msm_kernel", spying)
    pts = [C.g1.multiply(C.G1_GENERATOR, i + 2) for i in range(11)]
    K._mul_batch([(pt, 3) for pt in pts[:3]], device=True, nbits=16)
    K._mul_batch([(pt, 3) for pt in pts], device=True, nbits=16)
    assert seen == [4, 8, 4]  # 3 -> 4; 11 -> 8 + (3 -> 4)
    assert all(b in {4, 8} for b in seen)
    # empty batch: no dispatch at all
    seen.clear()
    assert K._mul_batch([], device=True) == []
    assert seen == []
    assert K.verify_blob_batch([], [], []) is True


def test_shard_split_matches_unsharded(monkeypatch):
    """GRAFT_KZG_SHARD round-robin partials recombine to the same
    products as the single-shard dispatch."""
    _tiny_kzg_buckets(monkeypatch)
    pts = [C.g1.multiply(C.G1_GENERATOR, i + 5) for i in range(7)]
    ks = [RNG.getrandbits(16) | 1 for _ in range(7)]
    base = K._mul_batch(list(zip(pts, ks)), device=True, nbits=16)
    monkeypatch.setenv("GRAFT_KZG_SHARD", "3")
    assert K._mul_batch(list(zip(pts, ks)), device=True, nbits=16) == base
    assert base == [C.g1._multiply_py(pt, k) for pt, k in zip(pts, ks)]


def test_device_and_host_verdicts_identical_full_width(
    monkeypatch, setup, sample
):
    """One full-width RLC fold through the device plane: same verdict as
    the host path for the valid batch and a tampered proof (the eager
    256-step walk is seconds-scale here, so exactly one pair)."""
    _tiny_kzg_buckets(monkeypatch)
    blobs, commitments, proofs = sample
    assert K.verify_blob_batch(
        blobs[:2], commitments[:2], proofs[:2], setup, device=True
    )
    assert not K.verify_blob_batch(
        blobs[:2], commitments[:2], [proofs[1], proofs[0]], setup, device=True
    )


def test_device_fault_falls_back_to_host(monkeypatch, setup, sample):
    """A raising device dispatch degrades to the host oracle LOUDLY
    (device_fault latch + host_fallback counter), never a wrong verdict."""
    from lambda_ethereum_consensus_tpu.telemetry import get_metrics

    def boom(nbits, interpret):
        raise RuntimeError("dead device")

    monkeypatch.setattr(K, "_get_msm_kernel", boom)
    blobs, commitments, proofs = sample
    fb0 = get_metrics().get("kzg_msm_total", path="host_fallback")
    assert K.verify_blob_batch(
        blobs, commitments, proofs, setup, device=True
    )
    assert get_metrics().get("kzg_msm_total", path="host_fallback") > fb0


def test_guard_rejects_bad_ladder_widths():
    """Caller errors raise loudly instead of reading as device faults."""
    pt = C.G1_GENERATOR
    with pytest.raises(K.KzgError):
        K._mul_batch([(pt, 1)], device=True, nbits=12)
    with pytest.raises(K.KzgError):
        K._mul_batch([(pt, 1 << 20)], device=True, nbits=16)
    with pytest.raises(K.KzgError):
        K.verify_blob_batch([b"\x00" * 128], [], [])
