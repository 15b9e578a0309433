"""SSZ codec + Merkleization tests (model: test/unit/ssz_test.exs and the
ssz_static spec-test format — decode/encode/hash_tree_root round-trips plus
independently-computed known answers)."""

import hashlib

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need the optional 'hypothesis' module",
)

from hypothesis import given, settings
from hypothesis import strategies as st

from lambda_ethereum_consensus_tpu import ssz
from lambda_ethereum_consensus_tpu import types as T
from lambda_ethereum_consensus_tpu.config import minimal_spec, use_chain_spec
from lambda_ethereum_consensus_tpu.ssz import (
    Bitlist,
    Bitvector,
    ByteList,
    ByteVector,
    List,
    SSZError,
    Vector,
    boolean,
    merkleize_chunks,
    uint8,
    uint16,
    uint64,
    uint256,
)


def h(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


# --- basic types ---------------------------------------------------------------


def test_uint_roundtrip():
    assert uint64.serialize(0x0102030405060708) == bytes.fromhex("0807060504030201")
    assert uint64.deserialize(bytes.fromhex("0807060504030201")) == 0x0102030405060708
    assert uint16.serialize(0xABCD) == bytes.fromhex("cdab")
    assert uint256.deserialize(uint256.serialize(2**255 + 17)) == 2**255 + 17


def test_uint_bounds():
    with pytest.raises(SSZError):
        uint8.serialize(256)
    with pytest.raises(SSZError):
        uint64.serialize(-1)
    with pytest.raises(SSZError):
        uint64.deserialize(b"\x00" * 7)


def test_boolean():
    assert boolean.serialize(True) == b"\x01"
    assert boolean.deserialize(b"\x00") is False
    with pytest.raises(SSZError):
        boolean.deserialize(b"\x02")


def test_uint_htr_padding():
    assert uint64.hash_tree_root(5) == (5).to_bytes(8, "little") + b"\x00" * 24


# --- merkleization vs an independent mini-oracle -------------------------------


def naive_merkle(chunks: list[bytes], limit: int) -> bytes:
    """Straightforward recursive Merkle root, independent of the engine."""
    padded = 1 if limit == 0 else 1 << (limit - 1).bit_length()
    nodes = list(chunks) + [b"\x00" * 32] * (padded - len(chunks))

    def root(lo, hi):
        if hi - lo == 1:
            return nodes[lo]
        mid = (lo + hi) // 2
        return h(root(lo, mid) + root(mid, hi))

    return root(0, len(nodes))


@given(st.integers(0, 20), st.integers(0, 40))
@settings(max_examples=30, deadline=None)
def test_merkleize_matches_naive(count, extra_limit):
    limit = count + extra_limit
    rng = np.random.default_rng(count * 100 + extra_limit)
    chunks = rng.integers(0, 256, (count, 32), dtype=np.uint8)
    got = merkleize_chunks(chunks, limit or None)
    want = naive_merkle([chunks[i].tobytes() for i in range(count)], limit or count)
    assert got == want


def test_merkleize_huge_limit_is_lazy():
    # 2**40-chunk limit must not allocate the virtual tree
    chunks = np.ones((3, 32), np.uint8)
    out = merkleize_chunks(chunks, 2**40)
    assert len(out) == 32


# --- containers: known answers computable by hand ------------------------------


def test_checkpoint_known_root():
    cp = T.Checkpoint(epoch=5, root=b"\x11" * 32)
    expect = h((5).to_bytes(32, "little") + b"\x11" * 32)
    assert cp.hash_tree_root() == expect


def test_fork_known_root():
    f = T.Fork(previous_version=b"\x01\x00\x00\x00", current_version=b"\x02\x00\x00\x00", epoch=9)
    leaves = [
        b"\x01\x00\x00\x00".ljust(32, b"\x00"),
        b"\x02\x00\x00\x00".ljust(32, b"\x00"),
        (9).to_bytes(32, "little"),
    ]
    expect = h(h(leaves[0] + leaves[1]) + h(leaves[2] + b"\x00" * 32))
    assert f.hash_tree_root() == expect


def test_list_uint64_known_root():
    # List[uint64, 4] of [1,2] -> one chunk (1,2 packed) merkleized at limit 1, mixed with len
    typ = List(uint64, 4)
    chunk = (1).to_bytes(8, "little") + (2).to_bytes(8, "little") + b"\x00" * 16
    expect = h(chunk + (2).to_bytes(32, "little"))
    assert typ.hash_tree_root([1, 2]) == expect


def test_bitlist_known_root():
    # Bitlist[8] of [1,0,1] -> byte 0b101 in one chunk, mix_in_length 3
    typ = Bitlist(8)
    bits = ssz.BitlistValue.from_bools([1, 0, 1])
    expect = h(bytes([0b101]).ljust(32, b"\x00") + (3).to_bytes(32, "little"))
    assert typ.hash_tree_root(bits) == expect
    assert typ.serialize(bits) == bytes([0b1101])  # sentinel at bit 3


def test_bitvector_roundtrip_and_root():
    typ = Bitvector(10)
    v = ssz.BitvectorValue.from_bools([1, 1, 0, 0, 1, 0, 0, 0, 1, 1])
    enc = typ.serialize(v)
    assert len(enc) == 2
    assert typ.deserialize(enc) == v
    # fits in one chunk: root is just the padded chunk (no length mixin)
    assert typ.hash_tree_root(v) == enc.ljust(32, b"\x00")


def test_bitlist_sentinel_validation():
    typ = Bitlist(16)
    with pytest.raises(SSZError):
        typ.deserialize(b"")
    with pytest.raises(SSZError):
        typ.deserialize(b"\x00")  # missing sentinel
    with pytest.raises(SSZError):
        typ.deserialize(b"\x05\x00")  # trailing zero byte


# --- container codec round-trips ----------------------------------------------


def random_validator(rng):
    return T.Validator(
        pubkey=bytes(rng.integers(0, 256, 48, dtype=np.uint8)),
        withdrawal_credentials=bytes(rng.integers(0, 256, 32, dtype=np.uint8)),
        effective_balance=int(rng.integers(0, 2**40)),
        slashed=bool(rng.integers(0, 2)),
        activation_eligibility_epoch=int(rng.integers(0, 2**20)),
        activation_epoch=int(rng.integers(0, 2**20)),
        exit_epoch=2**64 - 1,
        withdrawable_epoch=2**64 - 1,
    )


def test_validator_fixed_size(mainnet):
    assert T.Validator.is_fixed_size(mainnet)
    assert T.Validator.fixed_length(mainnet) == 121


def test_attestation_roundtrip():
    cp = T.Checkpoint(epoch=1, root=b"\x07" * 32)
    att = T.Attestation(
        aggregation_bits=ssz.BitlistValue.from_bools([1, 0, 1, 1, 0]),
        data=T.AttestationData(slot=3, index=1, beacon_block_root=b"\x22" * 32, source=cp, target=cp),
        signature=b"\x99" * 96,
    )
    assert T.Attestation.decode(att.encode()) == att


def test_indexed_attestation_roundtrip():
    cp = T.Checkpoint()
    ia = T.IndexedAttestation(
        attesting_indices=[1, 5, 9],
        data=T.AttestationData(slot=1, index=0, beacon_block_root=b"\x00" * 32, source=cp, target=cp),
        signature=b"\x11" * 96,
    )
    assert T.IndexedAttestation.decode(ia.encode()) == ia


def test_beacon_state_roundtrip_minimal(minimal):
    rng = np.random.default_rng(42)
    state = T.BeaconState(
        slot=17,
        validators=[random_validator(rng) for _ in range(8)],
        balances=[32 * 10**9] * 8,
        previous_epoch_participation=[0] * 8,
        current_epoch_participation=[7] * 8,
        inactivity_scores=[0] * 8,
    )
    enc = state.encode()
    state2 = T.BeaconState.decode(enc)
    assert state2 == state
    assert state2.hash_tree_root() == state.hash_tree_root()


def test_beacon_block_roundtrip(minimal):
    body = T.BeaconBlockBody(
        execution_payload=T.ExecutionPayload(
            transactions=[b"\x01\x02", b""],
            withdrawals=[T.Withdrawal(index=1, validator_index=2, address=b"\x03" * 20, amount=4)],
        ),
    )
    blk = T.SignedBeaconBlock(
        message=T.BeaconBlock(slot=7, proposer_index=1, parent_root=b"\x01" * 32,
                              state_root=b"\x02" * 32, body=body),
        signature=b"\x55" * 96,
    )
    assert T.SignedBeaconBlock.decode(blk.encode()) == blk


def test_deserialize_rejects_bad_offsets(minimal):
    enc = bytearray(T.IndexedAttestation(
        attesting_indices=[1], data=T.AttestationData(), signature=b"\x00" * 96).encode())
    enc[0] = 0xFF  # corrupt first offset
    with pytest.raises(SSZError):
        T.IndexedAttestation.decode(bytes(enc))


def test_config_dependent_sizes():
    with use_chain_spec(minimal_spec()):
        assert len(T.BeaconState().block_roots) == 64
        sc = T.SyncCommittee()
        assert len(sc.pubkeys) == 32
    assert len(T.BeaconState().block_roots) == 8192


def test_immutability_and_copy():
    cp = T.Checkpoint(epoch=1, root=b"\x00" * 32)
    with pytest.raises(AttributeError):
        cp.epoch = 2
    cp2 = cp.copy(epoch=2)
    assert cp2.epoch == 2 and cp.epoch == 1


# --- p2p / validator containers -----------------------------------------------


def test_status_message_roundtrip():
    sm = T.StatusMessage(fork_digest=b"\xba\xa4\xda\x96", finalized_root=b"\x01" * 32,
                         finalized_epoch=3, head_root=b"\x02" * 32, head_slot=99)
    assert T.StatusMessage.decode(sm.encode()) == sm
    assert T.StatusMessage.is_fixed_size()


def test_metadata_roundtrip():
    md = T.Metadata(seq_number=7, attnets=ssz.BitvectorValue.from_bools([0] * 63 + [1]),
                    syncnets=ssz.BitvectorValue.from_bools([1, 0, 0, 0]))
    assert T.Metadata.decode(md.encode()) == md


def test_aggregate_and_proof_roundtrip():
    ap = T.SignedAggregateAndProof(
        message=T.AggregateAndProof(
            aggregator_index=11,
            aggregate=T.Attestation(aggregation_bits=ssz.BitlistValue.from_bools([1])),
            selection_proof=b"\x01" * 96,
        ),
        signature=b"\x02" * 96,
    )
    assert T.SignedAggregateAndProof.decode(ap.encode()) == ap


# --- property-based round-trips ------------------------------------------------


@given(st.lists(st.integers(0, 2**64 - 1), max_size=50))
@settings(max_examples=50, deadline=None)
def test_uint64_list_roundtrip(xs):
    typ = List(uint64, 128)
    assert typ.deserialize(typ.serialize(xs)) == xs


@given(st.lists(st.booleans(), min_size=0, max_size=70))
@settings(max_examples=50, deadline=None)
def test_bitlist_roundtrip(bools):
    typ = Bitlist(128)
    v = ssz.BitlistValue.from_bools(bools)
    assert typ.deserialize(typ.serialize(v)) == v


@given(st.binary(max_size=64))
@settings(max_examples=50, deadline=None)
def test_bytelist_roundtrip(b):
    typ = ByteList(64)
    assert typ.deserialize(typ.serialize(b)) == b


# --- regressions from review --------------------------------------------------


def test_variable_list_rejects_zero_first_offset():
    typ = List(ByteList(100), 100)
    with pytest.raises(SSZError):
        typ.deserialize(b"\x00\x00\x00\x00GARBAGE")


def test_uint_list_htr_raises_sszerror_not_overflow():
    typ = List(uint64, 10)
    with pytest.raises(SSZError):
        typ.hash_tree_root([2**64])
    with pytest.raises(SSZError):
        typ.hash_tree_root([-1])


def test_bitvector_deserialize_bad_padding_is_sszerror():
    with pytest.raises(SSZError):
        Bitvector(4).deserialize(b"\xff")


def test_bits_set_bounds_checked():
    v = ssz.BitvectorValue(4)
    with pytest.raises(IndexError):
        v.set(6)
    assert v.set(3)[3] is True


def test_load_config_file_hex_fields(tmp_path):
    from lambda_ethereum_consensus_tpu.config import load_config_file

    p = tmp_path / "conf.yaml"
    p.write_text(
        "PRESET_BASE: 'mainnet'\n"
        "CONFIG_NAME: 'testnet'\n"
        "GENESIS_FORK_VERSION: 0x00000001  # unquoted hex\n"
        "DEPOSIT_CONTRACT_ADDRESS: 0x1234567890123456789012345678901234567890\n"
        "SECONDS_PER_SLOT: 3\n"
    )
    spec = load_config_file(str(p))
    assert spec.GENESIS_FORK_VERSION == bytes.fromhex("00000001")
    assert spec.DEPOSIT_CONTRACT_ADDRESS == bytes.fromhex("1234567890123456789012345678901234567890")
    assert spec.SECONDS_PER_SLOT == 3
    assert spec.SLOTS_PER_EPOCH == 32  # inherited from mainnet preset


def test_batched_element_roots_match_loop(mainnet):
    """The vectorized registry-root path (ssz/core._element_roots_batched)
    must agree byte-for-byte with the per-element loop (the oracle) —
    covering Uint, Boolean and both ByteVector chunk shapes."""
    import numpy as np

    from lambda_ethereum_consensus_tpu.ssz import core
    from lambda_ethereum_consensus_tpu.ssz.hash import get_hash_backend
    from lambda_ethereum_consensus_tpu.types.beacon import Validator

    spec = mainnet
    vals = [
        Validator(
            pubkey=bytes([i % 251] * 48),
            withdrawal_credentials=bytes([i % 7] * 32),
            effective_balance=32_000_000_000 + i,
            slashed=(i % 3 == 0),
            activation_eligibility_epoch=i,
            activation_epoch=i + 1,
            exit_epoch=2**64 - 1,
            withdrawable_epoch=2**64 - 1,
        )
        for i in range(130)  # > the 64-element fast-path threshold
    ]
    be = get_hash_backend()
    fast = core._element_roots_batched(Validator, vals, spec, be)
    assert fast is not None
    slow = np.stack(
        [np.frombuffer(Validator.hash_tree_root(v, spec, be), np.uint8) for v in vals]
    )
    assert (fast == slow).all()


# --- the decode plan against the per-call schema walk it replaced (ISSUE 31) ----
#
# ``oracle_decode`` is the decoder ``Container.deserialize`` and
# ``_deserialize_elements`` were before the plan: it derives sizes, slices
# and offsets from the schema on every call, at every nesting level, and
# builds through ``cls(**kwargs)``.  It lives here as the reference only.

import importlib
import pkgutil
import random

from lambda_ethereum_consensus_tpu.config import mainnet_spec
from lambda_ethereum_consensus_tpu.ssz import Bits, BitlistValue, BitvectorValue, Container
from lambda_ethereum_consensus_tpu.ssz import core as ssz_core
from lambda_ethereum_consensus_tpu.telemetry import get_metrics

OFFSET = ssz_core.OFFSET_SIZE


def _container_of(t):
    """The Container class behind a schema entry, or None."""
    t = getattr(t, "cls", t)
    return t if isinstance(t, type) and issubclass(t, Container) else None


def oracle_elements(elem, data, spec):
    if len(data) == 0:
        return []
    if elem.is_fixed_size(spec):
        size = elem.fixed_length(spec)
        if size == 0 or len(data) % size:
            raise SSZError("sequence length not a multiple of element size")
        return [oracle_decode(elem, data[i : i + size], spec) for i in range(0, len(data), size)]
    first = int.from_bytes(data[:OFFSET], "little")
    if first == 0 or first % OFFSET or first > len(data):
        raise SSZError("bad first offset")
    count = first // OFFSET
    offsets = [
        int.from_bytes(data[i * OFFSET : (i + 1) * OFFSET], "little") for i in range(count)
    ] + [len(data)]
    values = []
    for i in range(count):
        a, b = offsets[i], offsets[i + 1]
        if a > b or b > len(data):
            raise SSZError("offsets not monotonic or out of bounds")
        values.append(oracle_decode(elem, data[a:b], spec))
    return values


def oracle_decode(t, data, spec):
    cls = _container_of(t)
    if cls is None:
        if isinstance(t, Vector):
            values = oracle_elements(t.elem, data, spec)
            t._check_len(values, spec)
            return values
        if isinstance(t, List):
            values = oracle_elements(t.elem, data, spec)
            t._check_limit(values, spec)
            return values
        return t.deserialize(data, spec)  # a leaf: its own type's decoder
    data = bytes(data)
    fixed_sizes = []
    for ftype in cls.__ssz_schema__.values():
        ft = ssz_core._typ(ftype)
        fixed_sizes.append(ft.fixed_length(spec) if ft.is_fixed_size(spec) else None)
    fixed_len = sum(OFFSET if s is None else s for s in fixed_sizes)
    if len(data) < fixed_len:
        raise SSZError(f"{cls.__name__}: truncated ({len(data)} < {fixed_len})")
    pos = 0
    slices = []
    offsets = []
    for fname, size in zip(cls.__ssz_schema__, fixed_sizes):
        if size is None:
            offsets.append(int.from_bytes(data[pos : pos + OFFSET], "little"))
            slices.append((fname, None))
            pos += OFFSET
        else:
            slices.append((fname, data[pos : pos + size]))
            pos += size
    if offsets:
        if offsets[0] != fixed_len:
            raise SSZError(f"{cls.__name__}: first offset {offsets[0]} != fixed size {fixed_len}")
        bounds = offsets + [len(data)]
        for a, b in zip(bounds, bounds[1:]):
            if a > b or b > len(data):
                raise SSZError(f"{cls.__name__}: invalid offsets")
    elif len(data) != fixed_len:
        raise SSZError(f"{cls.__name__}: {len(data) - fixed_len} trailing bytes")
    kwargs = {}
    oi = 0
    for ftype, (fname, chunk) in zip(cls.__ssz_schema__.values(), slices):
        if chunk is None:
            a = offsets[oi]
            b = offsets[oi + 1] if oi + 1 < len(offsets) else len(data)
            kwargs[fname] = oracle_decode(ftype, data[a:b], spec)
            oi += 1
        else:
            kwargs[fname] = oracle_decode(ftype, chunk, spec)
    return cls(**kwargs)


def _types_containers():
    """Every Container subclass defined under ``types/``, in a fixed order."""
    found = {}
    for info in pkgutil.iter_modules(T.__path__):
        module = importlib.import_module(f"{T.__name__}.{info.name}")
        for name, obj in vars(module).items():
            if _container_of(obj) is not None and obj.__module__ == module.__name__:
                found[f"{info.name}.{name}"] = obj
    return [found[k] for k in sorted(found)]


TYPES_CONTAINERS = _types_containers()
SPECS = {"minimal": minimal_spec(), "mainnet": mainnet_spec()}


def random_value(t, spec, rng):
    cls = _container_of(t)
    if cls is not None:
        return cls(**{f: random_value(ft, spec, rng) for f, ft in cls.__ssz_schema__.items()})
    resolve = lambda n: ssz_core._resolve(n, spec)  # noqa: E731
    if isinstance(t, ssz.Uint):
        return rng.choice([0, (1 << t.bits) - 1, rng.randrange(1 << t.bits)])
    if isinstance(t, ssz.Boolean):
        return rng.random() < 0.5
    if isinstance(t, ByteVector):
        return rng.randbytes(resolve(t.length))
    if isinstance(t, ByteList):
        return rng.randbytes(rng.randint(0, min(resolve(t.limit), 40)))
    if isinstance(t, Vector):
        return [random_value(t.elem, spec, rng) for _ in range(resolve(t.length))]
    if isinstance(t, List):
        return [random_value(t.elem, spec, rng) for _ in range(rng.randint(0, min(resolve(t.limit), 3)))]
    if isinstance(t, Bitvector):
        return BitvectorValue.from_bools(rng.random() < 0.5 for _ in range(resolve(t.length)))
    if isinstance(t, Bitlist):
        n = rng.randint(0, min(resolve(t.limit), 70))
        return BitlistValue.from_bools(rng.random() < 0.5 for _ in range(n))
    raise AssertionError(f"no generator for {t!r}")


def same_leaf_types(a, b):
    if type(a) is not type(b):
        return False
    if isinstance(a, Container):
        return all(same_leaf_types(getattr(a, f), getattr(b, f)) for f in type(a).__ssz_schema__)
    if isinstance(a, list):
        return len(a) == len(b) and all(map(same_leaf_types, a, b))
    return True


def sites(t, data, a, b, spec):
    """Where a corruption can bite inside the encoding ``data[a:b]`` of a
    valid ``t`` value: ``("offset", pos, base)`` for every 4-byte offset (it
    points at ``base + value``), ``("boolean", pos)``, ``("bitlist", a, b)``
    and ``("bitvector", a, b, bits)``, nested levels included."""
    cls = _container_of(t)
    if cls is not None:
        pos = a
        var = []
        for ftype in cls.__ssz_schema__.values():
            ft = ssz_core._typ(ftype)
            if ft.is_fixed_size(spec):
                size = ft.fixed_length(spec)
                yield from sites(ftype, data, pos, pos + size, spec)
                pos += size
            else:
                yield ("offset", pos, a)
                var.append((ftype, a + int.from_bytes(data[pos : pos + OFFSET], "little")))
                pos += OFFSET
        for (ftype, start), end in zip(var, [s for _, s in var[1:]] + [b]):
            yield from sites(ftype, data, start, end, spec)
    elif isinstance(t, (Vector, List)):
        if _container_of(t.elem) is None and not isinstance(t.elem, (ssz.Boolean, Bitlist, ByteList)):
            return  # nothing to corrupt in a run of uints or byte vectors
        if a == b:
            return
        if t.elem.is_fixed_size(spec):
            size = t.elem.fixed_length(spec)
            for at in range(a, b, size):
                yield from sites(t.elem, data, at, at + size, spec)
        else:
            count = int.from_bytes(data[a : a + OFFSET], "little") // OFFSET
            starts = [a + int.from_bytes(data[a + OFFSET * i : a + OFFSET * (i + 1)], "little")
                      for i in range(count)]
            for i in range(count):
                yield ("offset", a + OFFSET * i, a)
            for start, end in zip(starts, starts[1:] + [b]):
                yield from sites(t.elem, data, start, end, spec)
    elif isinstance(t, ssz.Boolean):
        yield ("boolean", a)
    elif isinstance(t, Bitlist):
        yield ("bitlist", a, b)
    elif isinstance(t, Bitvector):
        yield ("bitvector", a, b, ssz_core._resolve(t.length, spec))


def corruptions(cls, enc, spec, rng):
    """Seeded corruptions of a valid encoding, as ``(label, bytes)``."""
    n = len(enc)

    def patched(at, new):
        return enc[:at] + new + enc[at + len(new):]

    cuts = {n - 1, cls._decode_plan(spec).fixed_len - 1, cls._decode_plan(spec).fixed_len + 1}
    yield "append-00", enc + b"\x00"
    yield "append-01", enc + b"\x01"
    by_kind = {}
    for site in sites(cls, enc, 0, n, spec):
        by_kind.setdefault(site[0], []).append(site)
    # a big value: every kind still, a seeded sample of each
    found = [s for group in by_kind.values() for s in rng.sample(group, min(len(group), 16))]
    for site in found:
        if site[0] == "offset":
            _, pos, base = site
            value = int.from_bytes(enc[pos : pos + OFFSET], "little")
            cuts.update((base + value - 1, base + value, base + value + 1))
            for new in {value + 1, value - 1, value ^ 4, 0, n - base + 1, 0xFFFFFFFF} - {value, -1}:
                yield f"offset@{pos}={new}", patched(pos, new.to_bytes(OFFSET, "little"))
        elif site[0] == "boolean":
            yield f"boolean@{site[1]}=2", patched(site[1], b"\x02")
            yield f"boolean@{site[1]}=ff", patched(site[1], b"\xff")
        elif site[0] == "bitlist":
            _, a, b = site
            top = enc[b - 1]
            # the sentinel cleared: a shorter list, trailing zero bytes or none at all
            yield f"bitlist@{a}-sentinel", patched(b - 1, bytes([top ^ (1 << (top.bit_length() - 1))]))
            yield f"bitlist@{a}-zeroed", patched(a, bytes(b - a))
            if b == n:  # the encoding's last span: more bytes lengthen it past its limit
                limit = max(
                    ssz_core._resolve(size, spec) for size in cls.__ssz_late_sizes__
                ) if cls.__ssz_late_sizes__ else 4096
                yield f"bitlist@{a}-over-limit", enc[: b - 1] + b"\xff" * (limit // 8 + 2)
        elif site[0] == "bitvector":
            _, a, b, bits = site
            if bits % 8:
                yield f"bitvector@{a}-padding", patched(b - 1, bytes([enc[b - 1] | 0x80]))
    for cut in sorted(c for c in cuts if 0 <= c < n):
        yield f"truncate@{cut}", enc[:cut]


def outcome(decode, data):
    try:
        return decode(data)
    except SSZError:
        return SSZError


CASES = [
    pytest.param(cls, spec, id=f"{cls.__name__}-{name}")
    for cls in TYPES_CONTAINERS
    for name, spec in SPECS.items()
]


@pytest.mark.parametrize("cls, spec", CASES)
def test_plan_decodes_what_the_schema_walk_decoded(cls, spec):
    rng = random.Random(f"{cls.__name__}/{spec.name}")
    for _ in range(3):
        value = random_value(cls, spec, rng)
        enc = value.encode(spec)
        got = cls.decode(enc, spec)
        want = oracle_decode(cls, enc, spec)
        assert got == want == value
        assert same_leaf_types(got, want)
        assert list(got.__dict__) == list(cls.__ssz_schema__)  # filled in schema order
        assert got.encode(spec) == enc


@pytest.mark.parametrize("cls, spec", CASES)
def test_plan_rejects_what_the_schema_walk_rejected(cls, spec):
    rng = random.Random(f"{cls.__name__}/{spec.name}/corrupt")
    enc = random_value(cls, spec, rng).encode(spec)
    rejected = 0
    for label, bad in corruptions(cls, enc, spec, rng):
        got = outcome(lambda d: cls.decode(d, spec), bad)
        want = outcome(lambda d: oracle_decode(cls, d, spec), bad)
        assert got == want, label  # both accept, equal, or both raise SSZError
        rejected += want is SSZError
    # appended bytes never pass a fixed-size container; a variable one takes
    # them into its last field or refuses them there, as the walk does
    assert rejected >= (2 if cls.is_fixed_size(spec) else 1), rejected


# --- the plan cache ------------------------------------------------------------


def _plans_built(cls, kind):
    return get_metrics().get("ssz_decode_plans_total", type=cls.__name__, kind=kind)


@pytest.fixture
def counting():
    m = get_metrics()
    was = m.enabled
    m.set_enabled(True)
    yield
    m.set_enabled(was)


def test_a_plan_is_built_once_per_type_and_spec(counting):
    class Vote(ssz.Container):  # a class of this test: no other has decoded it
        bits: Bitlist("MAX_VALIDATORS_PER_COMMITTEE")
        root: ByteVector(32)
        roots: Vector(ByteVector(32), "SLOTS_PER_HISTORICAL_ROOT")

    encodings = {}
    for name, spec in SPECS.items():
        value = Vote(
            bits=[True] * 5, root=b"\x07" * 32,
            roots=[bytes([i % 256]) * 32 for i in range(spec.SLOTS_PER_HISTORICAL_ROOT)],
        )
        encodings[name] = value.encode(spec)
        before = _plans_built(Vote, "mixed")
        for _ in range(50):
            assert Vote.decode(encodings[name], spec) == value
        assert _plans_built(Vote, "mixed") == before + 1
    # the vector's length differs: neither spec decodes by the other's plan
    assert len(encodings["minimal"]) != len(encodings["mainnet"])
    minimal, mainnet = SPECS["minimal"], SPECS["mainnet"]
    assert Vote._decode_plan(minimal) is not Vote._decode_plan(mainnet)
    assert Vote._decode_plan(minimal).fixed_len == 4 + 32 + 32 * 64
    with pytest.raises(SSZError):
        Vote.decode(encodings["mainnet"], minimal)
    with pytest.raises(SSZError):
        Vote.decode(encodings["minimal"], mainnet)
    # a spec of the same NAME with another size is another plan too
    wider = minimal.replace(SLOTS_PER_HISTORICAL_ROOT=128)
    assert wider.name == minimal.name
    assert Vote._decode_plan(wider).fixed_len == 4 + 32 + 32 * 128
    assert Vote._decode_plan(minimal.replace(SECONDS_PER_SLOT=3)) is Vote._decode_plan(minimal)
    # and the bitlist's limit is the decoding spec's own
    tight = minimal.replace(MAX_VALIDATORS_PER_COMMITTEE=4)
    with pytest.raises(SSZError, match="over limit"):
        Vote.decode(encodings["minimal"], tight)


class _Empty(ssz.Container):
    pass


class _OnlyVariable(ssz.Container):
    xs: List(uint64, 8)
    blob: ByteList(16)


class _Wide(ssz.Container):
    a: uint8
    big: uint256
    flag: boolean
    cp: T.Checkpoint


class _FlatOuter(ssz.Container):
    cp: T.Checkpoint
    flag: boolean
    n: uint16


class _BigOnly(ssz.Container):
    big: uint256
    votes: Vector(uint64, 2)


@pytest.mark.parametrize(
    "cls, kind, value",
    [
        (_Empty, "fields", _Empty()),
        (_OnlyVariable, "fields", _OnlyVariable(xs=[1, 2**64 - 1], blob=b"\x00\x01")),
        (_OnlyVariable, "fields", _OnlyVariable()),
        (_BigOnly, "fields", _BigOnly(big=2**256 - 1, votes=[3, 4])),
        (_Wide, "mixed", _Wide(a=255, big=2**255 + 1, flag=True,
                               cp=T.Checkpoint(epoch=9, root=b"\x09" * 32))),
        (_FlatOuter, "flat", _FlatOuter(cp=T.Checkpoint(epoch=1, root=b"\x01" * 32),
                                        flag=False, n=513)),
        (T.Validator, "flat", T.Validator(pubkey=b"\x05" * 48, slashed=True, exit_epoch=2**64 - 1)),
        (T.Attestation, "mixed", T.Attestation(aggregation_bits=[False, True, False])),
        (T.SignedAggregateAndProof, "mixed", T.SignedAggregateAndProof(signature=b"\x06" * 96)),
    ],
    ids=lambda v: v if isinstance(v, str) else getattr(v, "__name__", type(v).__name__),
)
def test_plan_kinds_and_edge_shapes(cls, kind, value, minimal):
    assert cls.decode_plan_kind(minimal) == kind
    enc = value.encode(minimal)
    got = cls.decode(enc, minimal)
    assert got == value == oracle_decode(cls, enc, minimal)
    assert same_leaf_types(got, oracle_decode(cls, enc, minimal))
    for bad in (enc + b"\x00", enc[:-1] if enc else b"\x01"):
        assert outcome(lambda d: cls.decode(d, minimal), bad) == outcome(
            lambda d: oracle_decode(cls, d, minimal), bad
        )


def test_list_of_flat_containers_decodes_by_the_elements_plan(minimal):
    validators = [
        T.Validator(pubkey=bytes([i]) * 48, slashed=bool(i % 2), effective_balance=i)
        for i in range(70)
    ]
    registry = List(T.Validator, "VALIDATOR_REGISTRY_LIMIT")
    enc = registry.serialize(validators, minimal)
    assert registry.deserialize(enc, minimal) == validators == oracle_decode(registry, enc, minimal)
    size = T.Validator.fixed_length(minimal)
    bad = bytearray(enc)
    bad[3 * size + 48 + 32 + 8] = 2  # the fourth validator's ``slashed`` byte
    with pytest.raises(SSZError):
        registry.deserialize(bytes(bad), minimal)
    with pytest.raises(SSZError):
        registry.deserialize(enc[:-1], minimal)
