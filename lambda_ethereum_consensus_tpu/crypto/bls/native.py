"""ctypes binding over ``native/libbls381.so``.

Accelerates the three hot operations — pairing product checks, G1/G2 scalar
multiplication (signing, subgroup checks, cofactor clearing) — while the
pure-Python implementation stays as the always-available oracle and fallback.
Boundary format: big-endian 48-byte field elements, affine ``x||y`` points.
"""

from __future__ import annotations

import ctypes
import os

_SO_PATH = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ),
    "native",
    "build",
    "libbls381.so",
)


def _load():
    if os.environ.get("BLS_DISABLE_NATIVE"):
        return None
    if not os.path.exists(_SO_PATH):
        return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError:
        return None
    lib.bls381_init.restype = None
    lib.bls381_pairing_check.restype = ctypes.c_int
    lib.bls381_pairing_check.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.bls381_g1_mul.restype = None
    lib.bls381_g1_mul.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_int),
    ]
    lib.bls381_g2_mul.restype = None
    lib.bls381_g2_mul.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_int),
    ]
    lib.bls381_fp_powmod.restype = None
    lib.bls381_fp_powmod.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
    ]
    # newer entry points — probe so an older .so still loads
    try:
        lib.bls381_hash_to_g2_batch.restype = None
        lib.bls381_hash_to_g2_batch.argtypes = [
            ctypes.c_char_p,                      # msgs, concatenated
            ctypes.POINTER(ctypes.c_size_t),      # per-message lengths
            ctypes.c_size_t,                      # n
            ctypes.c_char_p, ctypes.c_size_t,     # dst
            ctypes.c_char_p,                      # out: n * 192 bytes
            ctypes.c_int,                         # nthreads (0 = auto)
        ]
        lib.bls381_rlc_verify.restype = ctypes.c_int
        lib.bls381_rlc_verify.argtypes = [
            ctypes.c_char_p,                      # pks: n * 96
            ctypes.c_char_p,                      # sigs: n * 192
            ctypes.c_char_p,                      # coeffs: n * coeff_len
            ctypes.c_size_t,                      # coeff_len
            ctypes.POINTER(ctypes.c_int32),       # group id per entry
            ctypes.c_size_t,                      # n entries
            ctypes.c_char_p,                      # h_points: n_groups * 192
            ctypes.c_size_t,                      # n_groups
            ctypes.c_int,                         # nthreads (0 = auto)
        ]
        lib.bls381_final_exp_is_one.restype = ctypes.c_int
        lib.bls381_final_exp_is_one.argtypes = [
            ctypes.c_char_p,                      # fq12s: n * 576 BE bytes
            ctypes.c_size_t,                      # n
            ctypes.c_char_p,                      # out: n bools
        ]
        for name, insz, outsz in (
            ("bls381_g1_decompress_batch", 48, 96),
            ("bls381_g2_decompress_batch", 96, 192),
        ):
            fn = getattr(lib, name)
            fn.restype = None
            fn.argtypes = [
                ctypes.c_char_p,                  # in: n * insz compressed
                ctypes.c_size_t,                  # n
                ctypes.c_char_p,                  # out: n * outsz affine
                ctypes.c_char_p,                  # ok flags (1/0/2)
                ctypes.c_int,                     # subgroup_check
                ctypes.c_int,                     # nthreads (0 = auto)
            ]
        lib.bls381_decompress_fast_paths.restype = ctypes.c_int
        lib.bls381_decompress_fast_paths.argtypes = []
    except AttributeError:
        pass
    lib.bls381_init()
    return lib


_LIB = _load()


def available() -> bool:
    return _LIB is not None


# ------------------------------------------------------------- converters

def _g1_bytes(pt) -> bytes:
    x, y = pt
    return x.to_bytes(48, "big") + y.to_bytes(48, "big")


def _g2_bytes(pt) -> bytes:
    (x0, x1), (y0, y1) = pt
    return (
        x0.to_bytes(48, "big") + x1.to_bytes(48, "big")
        + y0.to_bytes(48, "big") + y1.to_bytes(48, "big")
    )


def _g1_from(buf: bytes):
    return (int.from_bytes(buf[:48], "big"), int.from_bytes(buf[48:], "big"))


def _g2_from(buf: bytes):
    return (
        (int.from_bytes(buf[:48], "big"), int.from_bytes(buf[48:96], "big")),
        (int.from_bytes(buf[96:144], "big"), int.from_bytes(buf[144:], "big")),
    )


# ------------------------------------------------------------- operations

def pairing_check(pairs) -> bool:
    """prod e(P_i, Q_i) == 1 over affine (g1, g2) point pairs (no Nones)."""
    g1buf = b"".join(_g1_bytes(p) for p, _ in pairs)
    g2buf = b"".join(_g2_bytes(q) for _, q in pairs)
    return bool(_LIB.bls381_pairing_check(g1buf, g2buf, len(pairs)))


def g1_mul(pt, scalar: int):
    if pt is None or scalar == 0:
        return None
    nbytes = max(1, (scalar.bit_length() + 7) // 8)
    out = ctypes.create_string_buffer(96)
    is_inf = ctypes.c_int()
    _LIB.bls381_g1_mul(
        out, _g1_bytes(pt), scalar.to_bytes(nbytes, "big"), nbytes, ctypes.byref(is_inf)
    )
    return None if is_inf.value else _g1_from(out.raw)


def fp_powmod(base: int, exp: int) -> int:
    """base^exp mod p via the Montgomery backend (exp >= 0)."""
    nbytes = max(1, (exp.bit_length() + 7) // 8)
    out = ctypes.create_string_buffer(48)
    _LIB.bls381_fp_powmod(
        out, base.to_bytes(48, "big"), exp.to_bytes(nbytes, "big"), nbytes
    )
    return int.from_bytes(out.raw, "big")


def g2_mul(pt, scalar: int):
    if pt is None or scalar == 0:
        return None
    nbytes = max(1, (scalar.bit_length() + 7) // 8)
    out = ctypes.create_string_buffer(192)
    is_inf = ctypes.c_int()
    _LIB.bls381_g2_mul(
        out, _g2_bytes(pt), scalar.to_bytes(nbytes, "big"), nbytes, ctypes.byref(is_inf)
    )
    return None if is_inf.value else _g2_from(out.raw)


def hash_available() -> bool:
    return _LIB is not None and hasattr(_LIB, "bls381_hash_to_g2_batch")


def hash_to_g2_batch(msgs: list[bytes], dst: bytes):
    """Batch hash_to_g2 across a C++ thread pool; None when unavailable."""
    if not hash_available():
        return None
    n = len(msgs)
    lens = (ctypes.c_size_t * n)(*[len(m) for m in msgs])
    out = ctypes.create_string_buffer(192 * n)
    _LIB.bls381_hash_to_g2_batch(b"".join(msgs), lens, n, dst, len(dst), out, 0)
    points = out.raw  # .raw copies the whole buffer: once, not per point
    return [_g2_from(points[i * 192 : (i + 1) * 192]) for i in range(n)]


def rlc_available() -> bool:
    return _LIB is not None and hasattr(_LIB, "bls381_rlc_verify")


def final_exp_available() -> bool:
    return _LIB is not None and hasattr(_LIB, "bls381_final_exp_is_one")


def final_exp_is_one(fq12s) -> list[bool] | None:
    """Batch final exponentiation + identity check over host fq12 tuples
    ``((c0..), (c1..))`` — the host tail for the device chained verify
    (everything up to the masked Miller product stays on-chip; this
    finishes the O(checks) remainder in C++ instead of ~29 more device
    dispatches)."""
    if not final_exp_available():
        return None
    n = len(fq12s)
    if n == 0:
        return []
    buf = bytearray()
    for f in fq12s:
        for c6 in f:
            for c2 in c6:
                for c in c2:
                    buf += int(c).to_bytes(48, "big")
    out = ctypes.create_string_buffer(n)
    _LIB.bls381_final_exp_is_one(bytes(buf), n, out)
    return [b == 1 for b in out.raw]


def decompress_available() -> bool:
    return _LIB is not None and hasattr(_LIB, "bls381_g2_decompress_batch")


def _decompress_batch(fn, insz: int, outsz: int, blobs, subgroup_check, from_buf):
    n = len(blobs)
    if n == 0:
        return []
    # per-item contract everywhere: a wrong-length blob is that ITEM's
    # invalidity (False), matching the Python fallback — one bad item
    # must not throw away the whole batch
    raw = [bytes(b) for b in blobs]
    keep = [i for i, b in enumerate(raw) if len(b) == insz]
    res: list = [False] * n
    if not keep:
        return res
    buf = b"".join(raw[i] for i in keep)
    m = len(keep)
    out = ctypes.create_string_buffer(outsz * m)
    ok = ctypes.create_string_buffer(m)
    fn(buf, m, out, ok, 1 if subgroup_check else 0, 0)
    # a ctypes buffer's .raw copies the WHOLE buffer per access: once each
    points, flags = out.raw, ok.raw
    for j, i in enumerate(keep):
        flag = flags[j]
        if flag == 1:
            res[i] = from_buf(points[j * outsz : (j + 1) * outsz])
        elif flag == 2:
            res[i] = None  # canonical infinity (g*_from_bytes semantics)
    return res


def g2_decompress_batch(blobs, subgroup_check: bool = True):
    """Batch G2 decompression with the endomorphism subgroup check
    (validated against mul-by-r at init).  Per item: affine ``((x0,x1),
    (y0,y1))`` | ``None`` (infinity encoding) | ``False`` (invalid).
    Returns None when the native library lacks the entry point."""
    if not decompress_available():
        return None
    return _decompress_batch(
        _LIB.bls381_g2_decompress_batch, 96, 192, blobs, subgroup_check, _g2_from
    )


def g1_decompress_batch(blobs, subgroup_check: bool = True):
    """Batch G1 decompression (pubkeys); same conventions as G2."""
    if not decompress_available():
        return None
    return _decompress_batch(
        _LIB.bls381_g1_decompress_batch, 48, 96, blobs, subgroup_check, _g1_from
    )


def rlc_verify(entries, h_points, group_ids, coeff_bits: int = 128) -> bool:
    """One RLC pairing-product check fully in C++ (the reference's blst
    batch role, ref native/bls_nif/src/lib.rs:14-158):

        prod_g e(sum_{i in g} r_i pk_i, H_g) * e(-g1, sum_i r_i sig_i) == 1

    entries: [(pk_xy, sig_xy, coeff)]; h_points: one G2 point per group;
    group_ids: per-entry group index.  Points must be on-curve and
    subgroup-checked by the caller (same contract as chain_verify).
    """
    if not rlc_available():
        return None
    n = len(entries)
    if n == 0:
        return True
    coeff_len = (coeff_bits + 7) // 8
    pks = b"".join(_g1_bytes(pk) for pk, _, _ in entries)
    sigs = b"".join(_g2_bytes(sig) for _, sig, _ in entries)
    coeffs = b"".join(c.to_bytes(coeff_len, "big") for _, _, c in entries)
    gids = (ctypes.c_int32 * n)(*group_ids)
    hbuf = b"".join(_g2_bytes(h) for h in h_points)
    return bool(
        _LIB.bls381_rlc_verify(
            pks, sigs, coeffs, coeff_len, gids, n, hbuf, len(h_points), 0
        )
    )
