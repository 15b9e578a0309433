"""Differential tests: C++ BLS backend vs the pure-Python oracle.

The native library silently takes over ``multiply_raw``/``pairing_check``
when built, so without these tests the Python oracle would lose coverage and
divergence would go unnoticed.  Every test here runs both paths on the same
inputs and requires identical results.
"""

import functools
import random

import pytest

from lambda_ethereum_consensus_tpu.crypto import bls
from lambda_ethereum_consensus_tpu.crypto.bls import curve as C
from lambda_ethereum_consensus_tpu.crypto.bls import fields as F
from lambda_ethereum_consensus_tpu.crypto.bls import native
from lambda_ethereum_consensus_tpu.crypto.bls import pairing as PR
from lambda_ethereum_consensus_tpu.crypto.bls.fields import R

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native BLS library not built"
)

RNG = random.Random(1234)


@pytest.fixture
def pure_python(monkeypatch):
    """Takes the native library out from under fields.py and curve.py: the
    Python oracle then shares no code with what it is compared with."""
    monkeypatch.setattr(F, "_fq_powmod", lambda base, exp: pow(base, exp, F.P))
    object.__setattr__(C.g1, "native_mul", None)
    object.__setattr__(C.g2, "native_mul", None)
    yield
    object.__setattr__(C.g1, "native_mul", native.g1_mul)
    object.__setattr__(C.g2, "native_mul", native.g2_mul)


def python_pairing_check(pairs) -> bool:
    f = F.FQ12_ONE
    for p, q in pairs:
        f = F.fq12_mul(f, PR.miller_loop(p, q))
    return F.fq12_is_one(PR.final_exponentiation(f))


@pytest.mark.parametrize("trial", range(8))
def test_fp_powmod_matches_builtin(trial):
    base = RNG.getrandbits(380)
    exp = RNG.getrandbits(trial * 48 + 1)
    assert native.fp_powmod(base, exp) == pow(base, exp, F.P)


@pytest.mark.parametrize("trial", range(5))
def test_g1_mul_matches_python(trial):
    k = RNG.getrandbits(256) + 1
    base = C.g1._multiply_py(C.G1_GENERATOR, RNG.getrandbits(64) + 1)
    assert native.g1_mul(base, k) == C.g1._multiply_py(base, k)


@pytest.mark.parametrize("trial", range(5))
def test_g2_mul_matches_python(trial):
    k = RNG.getrandbits(256) + 1
    base = C.g2._multiply_py(C.G2_GENERATOR, RNG.getrandbits(64) + 1)
    assert native.g2_mul(base, k) == C.g2._multiply_py(base, k)


def test_mul_edge_cases():
    assert native.g1_mul(C.G1_GENERATOR, R) is None  # order annihilates
    assert native.g2_mul(C.G2_GENERATOR, R) is None
    assert native.g1_mul(C.G1_GENERATOR, 1) == C.G1_GENERATOR
    assert native.g1_mul(None, 5) is None
    assert native.g1_mul(C.G1_GENERATOR, 0) is None
    # scalars larger than R (cofactor clearing uses unreduced scalars)
    big = R * 3 + 12345
    assert native.g1_mul(C.G1_GENERATOR, big) == C.g1._multiply_py(C.G1_GENERATOR, big)


@pytest.mark.parametrize("seed", range(3))
def test_pairing_check_matches_python(seed):
    rng = random.Random(seed)
    a = rng.getrandbits(128) + 2
    b = rng.getrandbits(128) + 2
    p_a = C.g1._multiply_py(C.G1_GENERATOR, a)
    q_b = C.g2._multiply_py(C.G2_GENERATOR, b)
    # e(aG1, bG2) * e(-abG1, G2) == 1
    p_neg = C.g1.affine_neg(C.g1._multiply_py(C.G1_GENERATOR, a * b % R))
    good = [(p_a, q_b), (p_neg, C.G2_GENERATOR)]
    bad = [(p_a, q_b), (C.g1.affine_neg(C.G1_GENERATOR), C.G2_GENERATOR)]
    assert native.pairing_check(good) is True
    assert python_pairing_check(good) is True
    assert native.pairing_check(bad) is False
    assert python_pairing_check(bad) is False


def test_verify_same_through_both_paths(request, monkeypatch):
    sk = b"\x2a" * 32
    pk = bls.sk_to_pk(sk)
    sig = bls.sign(sk, b"both paths")
    assert bls.verify(pk, b"both paths", sig)
    assert not bls.verify(pk, b"other", sig)
    # force the pure-Python path everywhere and require identical verdicts
    request.getfixturevalue("pure_python")
    monkeypatch.setattr(native, "_LIB", None)
    assert not native.available()
    assert bls.verify(pk, b"both paths", sig)
    assert not bls.verify(pk, b"other", sig)


# ---------------------------------------------------------- hash_to_g2


@pytest.mark.skipif(
    not native.hash_available(), reason="native hash_to_g2 not built"
)
class TestNativeHashToG2:
    """The C++ RFC 9380 pipeline must be byte-identical to the Python
    oracle — including the ψ-endomorphism cofactor clearing, which RFC
    9380 §8.8.2 defines to equal multiplication by h_eff exactly."""

    def test_matches_python_oracle(self):
        from lambda_ethereum_consensus_tpu.crypto.bls import hash_to_curve as H

        for i, msg in enumerate(
            [b"", b"abc", b"a" * 200, bytes(range(64)), b"\x00" * 33]
        ):
            u0, u1 = H.hash_to_field_fq2(msg, 2, H.DST_POP)
            py = H.clear_cofactor(
                H.g2.affine_add(H.iso_map(H._sswu(u0)), H.iso_map(H._sswu(u1)))
            )
            nat = native.hash_to_g2_batch([msg], H.DST_POP)[0]
            assert nat == py, f"case {i} diverged"

    def test_batch_order_and_custom_dst(self):
        from lambda_ethereum_consensus_tpu.crypto.bls import hash_to_curve as H

        msgs = [b"m%d" % i for i in range(7)]
        dst = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
        out = native.hash_to_g2_batch(msgs, dst)
        for m, pt in zip(msgs, out):
            u0, u1 = H.hash_to_field_fq2(m, 2, dst)
            py = H.clear_cofactor(
                H.g2.affine_add(H.iso_map(H._sswu(u0)), H.iso_map(H._sswu(u1)))
            )
            assert pt == py
        # outputs are valid subgroup points
        for pt in out:
            assert C.g2.in_subgroup(pt)

    def test_hash_to_g2_many_routes_native(self):
        from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (
            hash_to_g2,
            hash_to_g2_many,
        )

        msgs = [b"route%d" % i for i in range(3)]
        assert hash_to_g2_many(msgs) == [hash_to_g2(m) for m in msgs]
        assert hash_to_g2_many([]) == []


# ---------------------------------------------------------- RLC verify


@pytest.mark.skipif(
    not native.rlc_available(), reason="native RLC verify not built"
)
class TestNativeRlcVerify:
    """The all-native RLC product check (scalar muls + group sums +
    lockstep Miller + shared final exp) vs verify_points' Python path."""

    def _entries(self, n, n_msgs=3):
        from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import (
            DST_POP,
            hash_to_g2,
        )

        entries = []
        for i in range(n):
            sk = 5 + i
            m = b"rlc-%d" % (i % n_msgs)
            pk = C.g1.multiply_raw(C.G1_GENERATOR, sk)
            sig = C.g2.multiply_raw(hash_to_g2(m, DST_POP), sk)
            entries.append((pk, m, sig))
        return entries

    def test_valid_and_corrupted(self, monkeypatch):
        from lambda_ethereum_consensus_tpu.crypto.bls.batch import verify_points

        entries = self._entries(12)
        monkeypatch.setenv("BLS_NO_NATIVE_RLC", "1")
        assert verify_points(entries)
        monkeypatch.delenv("BLS_NO_NATIVE_RLC")
        assert verify_points(entries)

        pk, m, sig = entries[7]
        entries[7] = (pk, m, C.g2.multiply_raw(sig, 2))
        assert not verify_points(entries)
        monkeypatch.setenv("BLS_NO_NATIVE_RLC", "1")
        assert not verify_points(entries)

    def test_direct_api_group_edge_cases(self):
        from lambda_ethereum_consensus_tpu.crypto.bls.batch import _pack_check
        from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import DST_POP

        entries = self._entries(5, n_msgs=5)  # every entry its own group
        packed, h_points, gids = _pack_check(
            [(pk, m, sig) for pk, m, sig in entries], DST_POP, {}
        )
        assert native.rlc_verify(packed, h_points, gids) is True
        assert native.rlc_verify([], [], []) is True

    def test_wrong_message_grouping_fails(self):
        from lambda_ethereum_consensus_tpu.crypto.bls.batch import _pack_check
        from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import DST_POP

        entries = self._entries(6)
        # swap one entry's message after signing: grouping mismatch
        pk, _, sig = entries[2]
        entries[2] = (pk, b"rlc-other", sig)
        packed, h_points, gids = _pack_check(entries, DST_POP, {})
        assert native.rlc_verify(packed, h_points, gids) is False


@pytest.mark.skipif(
    not native.decompress_available(), reason="decompress entry points absent"
)
class TestDecompressBatch:
    """Native point decompression vs the Python decoders — including the
    endomorphism subgroup checks, which init() self-validates against the
    multiply-by-r oracle (a wrong eigenvalue constant falls back to
    mul-by-r rather than admitting non-members)."""

    def test_fast_paths_validated(self):
        # 2 = G2 psi-check live, 1 = G1 phi-check live
        assert native._LIB.bls381_decompress_fast_paths() == 3

    def test_g2_roundtrip_and_negatives(self):
        pts = [C.g2.multiply_raw(C.G2_GENERATOR, 5 + 7 * i) for i in range(8)]
        blobs = [C.g2_to_bytes(p) for p in pts]
        corrupt = bytearray(blobs[0])
        corrupt[7] ^= 0xFF
        infinity = bytes([0xC0]) + b"\x00" * 95
        inf_with_sign = bytes([0xE0]) + b"\x00" * 95
        cases = blobs + [bytes(corrupt), infinity, inf_with_sign]
        out = native.g2_decompress_batch(cases)
        for got, want in zip(out[:8], pts):
            assert got == want
        for blob, got in zip(cases, out):
            try:
                want = C.g2_from_bytes(blob)
            except C.DeserializationError:
                want = False
            assert got == want  # exact decoder parity, incl. the negatives

    def test_g2_non_subgroup_rejected(self):
        # a curve point OFF the subgroup: x from a fixed non-member search
        # (mirrors the decoder's own subgroup rejection)
        rng = random.Random(99)
        for _ in range(50):
            x = (rng.randrange(C.P), rng.randrange(C.P))
            y2 = F.fq2_add(F.fq2_mul(F.fq2_sq(x), x), (4, 4))
            y = F.fq2_sqrt(y2)
            if y is None:
                continue
            from lambda_ethereum_consensus_tpu.crypto.bls.curve import (
                _fq2_is_larger,
            )

            raw = bytearray(x[1].to_bytes(48, "big") + x[0].to_bytes(48, "big"))
            raw[0] |= 0x80 | (0x20 if _fq2_is_larger(y) else 0)
            (got,) = native.g2_decompress_batch([bytes(raw)])
            try:
                C.g2_from_bytes(bytes(raw))
                want = True
            except C.DeserializationError:
                want = False
            assert (got is not False) == want
            if not want:
                return  # found and agreed on a non-member
        pytest.skip("no twist point found in 50 draws (improbable)")

    def test_g1_roundtrip_and_subgroup(self):
        pts = [C.g1.multiply_raw(C.G1_GENERATOR, 11 + i) for i in range(8)]
        blobs = [C.g1_to_bytes(p) for p in pts]
        out = native.g1_decompress_batch(blobs + [bytes([0xC0]) + b"\x00" * 47])
        assert out[:8] == pts and out[8] is None
        # batch API parity through the curve-level wrapper
        from lambda_ethereum_consensus_tpu.crypto.bls.curve import (
            g1_from_bytes_batch,
            g2_from_bytes_batch,
        )

        assert g1_from_bytes_batch(blobs) == pts
        assert g2_from_bytes_batch([C.g2_to_bytes(C.G2_GENERATOR)]) == [
            C.G2_GENERATOR
        ]


# ------------------------------------- the field kernel under decompression
#
# fq2_sqrt (two exponentiations, shared with SSWU), the windowed fp_pow
# (fp_inv and fp_powmod ride it) and the Montgomery multiply are held to the
# PURE-Python oracle (``pure_python``), so a fault in the library cannot
# agree with itself.


def _oracle_g2(blob: bytes, subgroup_check: bool = True):
    try:
        return C.g2_from_bytes(blob, subgroup_check)
    except C.DeserializationError:
        return False


def _g2_blob(x: F.Fq2, sign: bool) -> bytes:
    raw = bytearray(x[1].to_bytes(48, "big") + x[0].to_bytes(48, "big"))
    raw[0] |= 0x80 | (0x20 if sign else 0)
    return bytes(raw)


def _g2_y2(x: F.Fq2) -> F.Fq2:
    return F.fq2_add(F.fq2_mul(F.fq2_sq(x), x), (4, 4))


def _root_branch(blob: bytes) -> str:
    """The branch of the native fq2_sqrt this encoding's y^2 takes: ``real``
    (c1 == 0), else by the Legendre symbol of delta = (a0 + sqrt(norm))/2."""
    x1 = int.from_bytes(blob[:48], "big") & ((1 << 381) - 1)
    a0, a1 = _g2_y2((int.from_bytes(blob[48:], "big"), x1))
    if a1 == 0:
        return "real"
    s = pow((a0 * a0 + a1 * a1) % F.P, (F.P + 1) // 4, F.P)
    delta = (a0 + s) * ((F.P + 1) // 2) % F.P
    return "residue" if pow(delta, (F.P - 1) // 2, F.P) == 1 else "non_residue"


@functools.lru_cache(maxsize=None)
def _signatures(n: int, tag: bytes) -> tuple[bytes, ...]:
    """n seeded signatures sk_i * H(m_i), compressed by the Python encoder."""
    from lambda_ethereum_consensus_tpu.crypto.bls.hash_to_curve import DST_POP

    rng = random.Random(tag)
    hs = native.hash_to_g2_batch([tag + b"-%d" % i for i in range(n)], DST_POP)
    return tuple(C.g2_to_bytes(native.g2_mul(h, rng.randrange(1, R))) for h in hs)


def _first_x(rng, want_root: bool) -> F.Fq2:
    """First seeded x whose y^2 has (or has not) a root in Fq2."""
    while True:
        x = (rng.randrange(F.P), rng.randrange(F.P))
        if (F.fq2_sqrt(_g2_y2(x)) is not None) == want_root:
            return x


def _x_real() -> F.Fq2:
    """Smallest x = (x0, 0) on the twist."""
    return next(
        (x0, 0) for x0 in range(1, 64) if F.fq2_sqrt(_g2_y2((x0, 0))) is not None
    )


def _x_with_real_y2() -> F.Fq2:
    """x = (a, b) with Im(x^3) = 3a^2 b - b^3 = -4, so y^2 is real and the
    root takes fq2_sqrt's c1 == 0 branch (a real always has a root in Fq2)."""
    for b in range(1, 64):
        a2 = (b**3 - 4) * pow(3 * b, F.P - 2, F.P) % F.P
        a = F.fq_sqrt(a2)
        if a is not None:
            assert _g2_y2((a, b))[1] == 0
            return (a, b)
    raise AssertionError("no x with a real y^2 among b < 64")


_INFINITY = bytes([0xC0]) + b"\x00" * 95

# name -> (blobs, what the oracle must say with the subgroup check on)
_G2_EDGE_CASES = {
    "y2_non_square": (
        lambda: [_g2_blob(_first_x(random.Random(5), False), s) for s in (0, 1)],
        "rejected",
    ),
    "on_curve_off_subgroup": (
        lambda: [_g2_blob(_first_x(random.Random(6), True), s) for s in (0, 1)],
        "rejected",
    ),
    "x_c1_zero": (lambda: [_g2_blob(_x_real(), s) for s in (0, 1)], "rejected"),
    "y2_c1_zero": (
        lambda: [_g2_blob(_x_with_real_y2(), s) for s in (0, 1)],
        "rejected",
    ),
    "infinity": (lambda: [_INFINITY], "infinity"),
    "infinity_sign_bit": (lambda: [bytes([0xE0]) + b"\x00" * 95], "rejected"),
    "infinity_low_bits": (lambda: [bytes([0xC1]) + b"\x00" * 95], "rejected"),
    "infinity_trailing_byte": (
        lambda: [_INFINITY[:95] + b"\x01", _INFINITY[:48] + b"\x80" + b"\x00" * 47],
        "rejected",
    ),
    "x_c1_not_below_p": (
        lambda: [_g2_blob((7, F.P), 0), _g2_blob((7, (1 << 381) - 1), 1)],
        "rejected",
    ),
    "x_c0_not_below_p": (
        lambda: [_g2_blob((F.P, 7), 0), _g2_blob(((1 << 384) - 1, 7), 1)],
        "rejected",
    ),
    "no_compression_bit": (
        lambda: [
            bytes([b[0] & 0x7F]) + b[1:] for b in _signatures(2, b"nobit")
        ],
        "rejected",
    ),
}


@pytest.mark.skipif(
    not native.decompress_available(), reason="decompress entry points absent"
)
class TestDecompressKernel:
    CHUNKS, PER_CHUNK = 8, 32  # 256 seeded signatures

    @pytest.mark.parametrize("chunk", range(CHUNKS))
    def test_valid_signatures_match_python(self, chunk, pure_python):
        sigs = _signatures(self.CHUNKS * self.PER_CHUNK, b"kernel")
        part = sigs[chunk * self.PER_CHUNK : (chunk + 1) * self.PER_CHUNK]
        got = native.g2_decompress_batch(part)
        want = [C.g2_from_bytes(b) for b in part]  # raises on a rejection
        assert got == want

    def test_valid_corpus_covers_both_roots_and_signs(self):
        sigs = _signatures(self.CHUNKS * self.PER_CHUNK, b"kernel")
        seen = {(_root_branch(b), bool(b[0] & 0x20)) for b in sigs}
        assert seen >= {
            (branch, sign)
            for branch in ("residue", "non_residue")
            for sign in (False, True)
        }

    @pytest.mark.parametrize("subgroup_check", [True, False])
    @pytest.mark.parametrize("name", sorted(_G2_EDGE_CASES))
    def test_edge_encodings_match_python(self, name, subgroup_check, pure_python):
        build, expect = _G2_EDGE_CASES[name]
        blobs = build()
        want = [_oracle_g2(b, subgroup_check) for b in blobs]
        if subgroup_check:
            assert want == [None if expect == "infinity" else False] * len(blobs)
        if name in ("on_curve_off_subgroup", "x_c1_zero", "y2_c1_zero"):
            # on the twist: only the subgroup check turns them away
            assert subgroup_check or all(isinstance(w, tuple) for w in want)
        if name == "y2_c1_zero":
            assert {_root_branch(b) for b in blobs} == {"real"}
        assert native.g2_decompress_batch(blobs, subgroup_check) == want

    def test_wrong_length_items_fail_alone(self):
        sigs = _signatures(6, b"lengths")
        pts = native.g2_decompress_batch(sigs)
        assert all(isinstance(p, tuple) for p in pts)
        batch = [
            sigs[0], sigs[1][:95], sigs[2], sigs[3] + b"\x00", b"",
            bytearray(sigs[4]), _INFINITY, sigs[5],
        ]
        assert native.g2_decompress_batch(batch) == [
            pts[0], False, pts[2], False, False, pts[4], None, pts[5]
        ]
        assert native.g2_decompress_batch([b"", sigs[0][:10]]) == [False, False]

    def test_batch_of_4096_equals_its_quarters(self):
        n, q = 4096, 1024
        blobs = [
            C.g2_to_bytes(native.g2_mul(C.G2_GENERATOR, 3 + k)) for k in range(n)
        ]
        # every kind of slot next to each quarter's edge
        for edge in (0, q, 2 * q, 3 * q):
            blobs[edge + 1] = _INFINITY
            blobs[edge + q - 2] = blobs[edge + q - 2][:-1]
            blobs[edge + q - 1] = bytes([0xE0]) + b"\x00" * 95
        whole = native.g2_decompress_batch(blobs)
        apart = []
        for edge in (0, q, 2 * q, 3 * q):
            apart += native.g2_decompress_batch(blobs[edge : edge + q])
        assert whole == apart
        assert len(set(p for p in whole if isinstance(p, tuple))) == n - 12
        for k in (0, 2, q - 3, q, n - 3):
            assert whole[k] == C.g2_from_bytes(blobs[k])


def _sswu_first_branch(u: F.Fq2) -> bool:
    """Whether g(x1) is a square for this u (else SSWU takes x2 = Z u^2 x1)."""
    from lambda_ethereum_consensus_tpu.crypto.bls import hash_to_curve as H

    zu2 = F.fq2_mul(H._Z, F.fq2_sq(u))
    x1 = F.fq2_mul(
        F.fq2_mul(F.fq2_neg(H._B), F.fq2_inv(H._A)),
        F.fq2_add(F.FQ2_ONE, F.fq2_inv(F.fq2_add(F.fq2_sq(zu2), zu2))),
    )
    gx1 = F.fq2_add(F.fq2_add(F.fq2_mul(F.fq2_sq(x1), x1), F.fq2_mul(H._A, x1)), H._B)
    return F.fq2_sqrt(gx1) is not None


@pytest.mark.skipif(
    not native.hash_available(), reason="native hash_to_g2 not built"
)
class TestHashToG2Kernel:
    CHUNKS, PER_CHUNK = 4, 8  # 32 seeded messages

    @staticmethod
    def _messages(chunk: int) -> list[bytes]:
        rng = random.Random(7000 + chunk)
        return [rng.randbytes(rng.randrange(0, 96)) for _ in range(8)]

    @pytest.mark.parametrize("chunk", range(CHUNKS))
    def test_batch_matches_python_hash_to_g2(self, chunk, pure_python, monkeypatch):
        from lambda_ethereum_consensus_tpu.crypto.bls import hash_to_curve as H

        msgs = self._messages(chunk)
        got = native.hash_to_g2_batch(msgs, H.DST_POP)
        monkeypatch.setattr(native, "hash_available", lambda: False)
        assert got == [H.hash_to_g2(m) for m in msgs]

    def test_messages_take_both_sswu_branches(self):
        from lambda_ethereum_consensus_tpu.crypto.bls import hash_to_curve as H

        branches = {
            _sswu_first_branch(u)
            for chunk in range(self.CHUNKS)
            for m in self._messages(chunk)
            for u in H.hash_to_field_fq2(m, 2, H.DST_POP)
        }
        assert branches == {True, False}


_POW_EXPONENTS = {
    "zero": 0,
    "one": 1,
    "p_minus_2": F.P - 2,
    "sqrt_exponent": (F.P + 1) // 4,
    "inverse_sqrt_exponent": (F.P - 3) // 4,
    "low_digits_zero": 0xABC << 372,
    "longer_than_the_field": (1 << 400) + 0x1234567,
}


@pytest.mark.parametrize("name", sorted(_POW_EXPONENTS) + ["random_381_bit"])
def test_fp_powmod_windowed_matches_pow(name):
    rng = random.Random(name)
    bases = [0, 1, 2, F.P - 1, F.P + 5, (1 << 384) - 1]  # the last two unreduced
    bases += [rng.randrange(F.P) for _ in range(10)]
    for base in bases:
        if name == "random_381_bit":
            exp = rng.getrandbits(381) | (1 << 380)
        else:
            exp = _POW_EXPONENTS[name]
        assert native.fp_powmod(base, exp) == pow(base, exp, F.P), (base, exp)
