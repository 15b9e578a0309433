"""Persistence: native + Python KV engines, typed stores, crash resume.

Round 20 adds the crash-consistency edge cases: empty/zero-length logs,
partial records at the tail (both backends), CRC-caught bit flips,
duplicate-key last-wins, delete-then-compact, legacy-log migration, and
the native<->Python framed-file interchange round trip."""

import os
import struct

import pytest

from lambda_ethereum_consensus_tpu.config import minimal_spec, use_chain_spec
from lambda_ethereum_consensus_tpu.crypto import bls
from lambda_ethereum_consensus_tpu.state_transition.genesis import build_genesis_state
from lambda_ethereum_consensus_tpu.store import (
    BlockStore,
    KvStore,
    StateStore,
    get_finalized_anchor,
    set_finalized_anchor,
)
from lambda_ethereum_consensus_tpu.store.kv import _NATIVE, WAL_HEADER
from lambda_ethereum_consensus_tpu.types.beacon import (
    BeaconBlock,
    BeaconBlockBody,
    SignedBeaconBlock,
)

ENGINES = [False] + ([True] if _NATIVE is not None else [])


def _legacy_record(op: int, key: bytes, val: bytes) -> bytes:
    """A pre-round-20 unframed WAL record."""
    return bytes([op]) + struct.pack("<II", len(key), len(val)) + key + val


@pytest.fixture(params=ENGINES, ids=["python", "native"][: len(ENGINES)])
def kv(request, tmp_path):
    store = KvStore(str(tmp_path / "db.wal"), native=request.param)
    yield store
    store.close()


def test_put_get_delete(kv):
    kv.put(b"a", b"1")
    kv.put(b"b", b"2")
    assert kv.get(b"a") == b"1"
    kv.put(b"a", b"updated")
    assert kv.get(b"a") == b"updated"
    kv.delete(b"a")
    assert kv.get(b"a") is None
    assert kv.count() == 1


def test_iteration_ordered_and_prefix(kv):
    for i in [3, 1, 2]:
        kv.put(b"x|" + bytes([i]), bytes([i]))
    kv.put(b"y|\x01", b"other")
    asc = [k for k, _ in kv.iterate_prefix(b"x|")]
    assert asc == [b"x|\x01", b"x|\x02", b"x|\x03"]
    desc = [k for k, _ in kv.iterate_prefix(b"x|", descending=True)]
    assert desc == asc[::-1]
    assert kv.last_under_prefix(b"x|") == (b"x|\x03", b"\x03")


def test_persistence_across_reopen(tmp_path):
    for native in ENGINES:
        path = str(tmp_path / f"reopen-{native}.wal")
        s = KvStore(path, native=native)
        s.put(b"k1", b"v1")
        s.put(b"k2", b"v2")
        s.delete(b"k1")
        s.flush()
        s.close()
        s2 = KvStore(path, native=native)
        assert s2.get(b"k1") is None
        assert s2.get(b"k2") == b"v2"
        s2.close()


def test_torn_tail_recovers(tmp_path):
    path = str(tmp_path / "torn.wal")
    s = KvStore(path, native=False)
    s.put(b"good", b"value")
    s.flush()
    s.close()
    with open(path, "ab") as f:
        f.write(b"\x01\xff\xff")  # truncated record header
    s2 = KvStore(path, native=False)
    assert s2.get(b"good") == b"value"
    s2.close()


def test_compaction_shrinks_log(tmp_path):
    path = str(tmp_path / "compact.wal")
    s = KvStore(path, native=False)
    for i in range(50):
        s.put(b"churn", str(i).encode())
    s.flush()
    before = os.path.getsize(path)
    s.compact()
    after = os.path.getsize(path)
    assert after < before
    assert s.get(b"churn") == b"49"
    s.close()


def test_engines_share_wal_format(tmp_path):
    if _NATIVE is None:
        pytest.skip("native engine not built")
    path = str(tmp_path / "shared.wal")
    a = KvStore(path, native=True)
    a.put(b"from", b"native")
    a.flush()
    a.close()
    b = KvStore(path, native=False)
    assert b.get(b"from") == b"native"
    b.put(b"and", b"python")
    b.flush()
    b.close()
    c = KvStore(path, native=True)
    assert c.get(b"and") == b"python"
    c.close()


# -------------------------------------------------- crash-consistency edges


def test_empty_and_zero_length_log(tmp_path):
    """A zero-length file (created then crashed before the header) and a
    missing file both open as an empty framed store."""
    for native in ENGINES:
        empty = str(tmp_path / f"zero-{native}.wal")
        open(empty, "wb").close()
        s = KvStore(empty, native=native)
        assert s.count() == 0
        assert s.recovery == {
            "records": 0, "dropped_bytes": 0,
            "truncated": False, "migrated": False,
        }
        s.put(b"k", b"v")
        s.close()
        s2 = KvStore(empty, native=native)
        assert s2.get(b"k") == b"v"
        s2.close()


@pytest.mark.parametrize("cut", [1, 5, 12, 14])
def test_partial_record_at_tail_both_backends(tmp_path, cut):
    """A record sheared mid-frame (header, CRC, or payload) is truncated
    at the last verified frame by BOTH backends, with the drop reported."""
    for native in ENGINES:
        path = str(tmp_path / f"partial-{native}-{cut}.wal")
        s = KvStore(path, native=native)
        s.put(b"keep", b"me")
        s.put(b"gone", b"x" * 64)
        s.sync()
        s.close()
        size = os.path.getsize(path)
        os.truncate(path, size - cut)
        s2 = KvStore(path, native=native)
        assert s2.get(b"keep") == b"me"
        assert s2.get(b"gone") is None
        assert s2.recovery["truncated"] is True
        assert s2.recovery["dropped_bytes"] > 0
        # the file was physically truncated back to the good prefix, so
        # a THIRD open is clean
        s2.close()
        s3 = KvStore(path, native=native)
        assert s3.recovery["truncated"] is False
        assert s3.get(b"keep") == b"me"
        s3.close()


def test_torn_header_recovers_both_backends(tmp_path):
    """A crash during file creation leaves a SHORT header (1-7 bytes of
    'KVWL...'): no record can exist yet, so both backends must recover
    to an empty framed store — never crash, never misalign appends."""
    for native in ENGINES:
        for cut in (4, 5, 7):
            path = str(tmp_path / f"tornhead-{native}-{cut}.wal")
            with open(path, "wb") as f:
                f.write(WAL_HEADER[:cut])
            s = KvStore(path, native=native)
            assert s.count() == 0
            s.put(b"k", b"v")
            s.sync()
            s.close()
            # the repaired file is a clean framed log: records written
            # after recovery survive the next open intact
            s2 = KvStore(path, native=native)
            assert s2.get(b"k") == b"v"
            assert s2.recovery["truncated"] is False
            s2.close()


def test_crc_catches_bit_flip(tmp_path):
    """A flipped payload bit in the last record is caught by the CRC and
    the record is dropped — never silently served corrupt."""
    for native in ENGINES:
        path = str(tmp_path / f"flip-{native}.wal")
        s = KvStore(path, native=native)
        s.put(b"a", b"solid")
        s.put(b"b", b"flipped-payload")
        s.sync()
        s.close()
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.seek(size - 3)
            byte = f.read(1)[0]
            f.seek(size - 3)
            f.write(bytes([byte ^ 0x01]))
        s2 = KvStore(path, native=native)
        assert s2.get(b"a") == b"solid"
        assert s2.get(b"b") is None  # dropped, not corrupt
        assert s2.recovery["truncated"] is True
        s2.close()


def test_duplicate_key_last_wins_across_reopen(kv):
    for i in range(10):
        kv.put(b"dup", str(i).encode())
    assert kv.get(b"dup") == b"9"


def test_duplicate_key_last_wins_replay(tmp_path):
    for native in ENGINES:
        path = str(tmp_path / f"dup-{native}.wal")
        s = KvStore(path, native=native)
        for i in range(10):
            s.put(b"dup", str(i).encode())
        s.flush()
        s.close()
        s2 = KvStore(path, native=native)
        assert s2.get(b"dup") == b"9"
        assert s2.count() == 1
        s2.close()


def test_delete_then_compact(tmp_path):
    for native in ENGINES:
        path = str(tmp_path / f"delcomp-{native}.wal")
        s = KvStore(path, native=native)
        for i in range(20):
            s.put(f"k{i}".encode(), b"v" * 32)
        for i in range(15):
            s.delete(f"k{i}".encode())
        s.flush()
        before = os.path.getsize(path)
        s.compact()
        after = os.path.getsize(path)
        assert after < before
        assert s.count() == 5
        assert s.get(b"k0") is None
        assert s.get(b"k19") == b"v" * 32
        s.close()
        # the compacted file replays identically
        s2 = KvStore(path, native=native)
        assert s2.count() == 5
        assert s2.get(b"k17") == b"v" * 32
        assert s2.get(b"k3") is None
        s2.close()


def test_legacy_log_migrates_on_open(tmp_path):
    """A pre-round-20 unframed log is detected, replayed under the old
    torn-tail rule, and rewritten as a framed file in place."""
    for native in ENGINES:
        path = str(tmp_path / f"legacy-{native}.wal")
        with open(path, "wb") as f:
            f.write(_legacy_record(1, b"old", b"data"))
            f.write(_legacy_record(1, b"gone", b"soon"))
            f.write(_legacy_record(2, b"gone", b""))
            f.write(b"\x01\x03\x00")  # legacy torn tail
        s = KvStore(path, native=native)
        assert s.recovery["migrated"] is True
        assert s.recovery["truncated"] is True  # the torn legacy tail
        assert s.get(b"old") == b"data"
        assert s.get(b"gone") is None
        s.close()
        # the migrated file is framed: reopen reports a clean v2 log
        with open(path, "rb") as f:
            assert f.read(len(WAL_HEADER)) == WAL_HEADER
        s2 = KvStore(path, native=native)
        assert s2.recovery["migrated"] is False
        assert s2.get(b"old") == b"data"
        s2.close()


def test_framed_interchange_round_trip(tmp_path):
    """Files written by either backend — including one MIGRATED from the
    legacy format — open in the other (the acceptance round trip).  The
    native lane skips when libkvstore.so is unbuilt."""
    if _NATIVE is None:
        pytest.skip("native engine not built")
    # start from a legacy file so the migration product itself is the
    # thing being interchanged
    path = str(tmp_path / "interchange.wal")
    with open(path, "wb") as f:
        f.write(_legacy_record(1, b"seed", b"legacy"))
    a = KvStore(path, native=False)
    assert a.recovery["migrated"] is True
    a.put(b"from", b"python")
    a.sync()
    a.close()
    b = KvStore(path, native=True)
    assert b.get(b"seed") == b"legacy"
    assert b.get(b"from") == b"python"
    b.put(b"and", b"native")
    b.compact()  # native durable-rename compaction output...
    b.close()
    c = KvStore(path, native=False)  # ...read back by Python
    assert c.get(b"seed") == b"legacy"
    assert c.get(b"and") == b"native"
    assert c.recovery["truncated"] is False
    c.close()


def test_finalized_anchor_helpers(tmp_path):
    kv = KvStore(str(tmp_path / "anchor.wal"), native=False)
    assert get_finalized_anchor(kv) is None
    set_finalized_anchor(kv, b"\xaa" * 32)
    assert get_finalized_anchor(kv) == b"\xaa" * 32
    kv.put(b"finalized|anchor", b"short")  # junk-length pointer ignored
    assert get_finalized_anchor(kv) is None
    kv.close()


def test_durability_knob_validation(tmp_path):
    with pytest.raises(Exception):
        KvStore(str(tmp_path / "knob.wal"), native=False, durability="sometimes")
    s = KvStore(str(tmp_path / "knob2.wal"), native=False, durability="always")
    s.put(b"k", b"v")  # synced per put
    s.barrier()
    s.close()


def test_verified_resume_rejects_corrupt_state(tmp_path):
    """A state record whose bytes no longer Merkle-root to the stored
    block's state_root is REJECTED as a resume candidate (the node then
    falls back instead of booting on it)."""
    with use_chain_spec(minimal_spec()) as spec:
        sks = [(i + 1).to_bytes(32, "big") for i in range(16)]
        state = build_genesis_state([bls.sk_to_pk(sk) for sk in sks], spec=spec)
        kv = KvStore(str(tmp_path / "verify.wal"), native=False)
        blocks = BlockStore(kv)
        states = StateStore(kv)
        signed = SignedBeaconBlock(
            message=BeaconBlock(
                slot=1, state_root=state.hash_tree_root(spec),
                body=BeaconBlockBody(),
            )
        )
        root = blocks.store_block(signed, spec)
        states.store_state(root, state, spec)
        assert states.verified_state(root, blocks, spec) is not None
        assert states.get_latest_verified_state(blocks, spec) is not None
        # corrupt the stored state in place (valid KV record, wrong data:
        # the WAL CRC cannot catch this — only root verification can)
        raw = bytearray(kv.get(b"beacon_state|" + root))
        raw[50] ^= 0xFF
        kv.put(b"beacon_state|" + root, bytes(raw))
        assert states.verified_state(root, blocks, spec) is None
        assert states.get_latest_verified_state(blocks, spec) is None
        kv.close()


# ------------------------------------------------------------ typed stores

def test_block_and_state_store_roundtrip(tmp_path):
    with use_chain_spec(minimal_spec()) as spec:
        sks = [(i + 1).to_bytes(32, "big") for i in range(16)]
        state = build_genesis_state([bls.sk_to_pk(sk) for sk in sks], spec=spec)
        kv = KvStore(str(tmp_path / "chain.wal"))
        blocks = BlockStore(kv)
        states = StateStore(kv)

        signed = SignedBeaconBlock(
            message=BeaconBlock(
                slot=5, state_root=state.hash_tree_root(spec), body=BeaconBlockBody()
            )
        )
        root = blocks.store_block(signed, spec)
        states.store_state(root, state, spec)
        kv.flush()

        assert blocks.has_block(root)
        got = blocks.get_block(root, spec)
        assert got.message.hash_tree_root(spec) == root
        assert blocks.get_block_by_slot(5, spec) is not None
        assert blocks.highest_slot() == 5
        assert blocks.missing_slots(3, 8) == [3, 4, 6, 7]

        latest = states.get_latest_state(spec)
        assert latest is not None
        latest_root, latest_state = latest
        assert latest_root == root
        assert latest_state.hash_tree_root(spec) == state.hash_tree_root(spec)
        kv.close()


def test_store_state_encodes_each_post_state_from_the_delta(kv):
    """Every applied block leaves ONE complete record, byte-identical to
    the element-loop serializer's, while the encoded image riding the
    lineage walks the registry once: ``validators`` is rebuilt for the
    first state stored and reused for every post-state after it."""
    from lambda_ethereum_consensus_tpu.state_transition import state_transition
    from lambda_ethereum_consensus_tpu.store.state_store import _STATE
    from lambda_ethereum_consensus_tpu.telemetry import get_metrics
    from lambda_ethereum_consensus_tpu.types.beacon import BeaconState
    from lambda_ethereum_consensus_tpu.validator import build_signed_block

    from .test_state_encode import loop_oracle

    m = get_metrics()

    def fields(path):
        return m.get("state_encode_fields_total", field="validators", path=path)

    def samples(family):
        hist = m.get_histogram(family)
        return 0 if hist is None else hist[3]

    with use_chain_spec(minimal_spec()) as spec:
        sks = [(i + 1).to_bytes(32, "big") for i in range(64)]
        state = build_genesis_state([bls.sk_to_pk(sk) for sk in sks], spec=spec)
        blocks, states = BlockStore(kv), StateStore(kv)
        before = {p: fields(p) for p in ("rebuilt", "patched", "reused")}
        spans = {f: samples(f) for f in ("state_encode_seconds", "state_kv_put_seconds")}
        stored = []
        for slot in range(1, 5):
            signed, _post = build_signed_block(state, slot, sks, spec=spec)
            state = state_transition(state, signed, validate_result=True, spec=spec)
            root = blocks.store_block(signed, spec)
            states.store_state(root, state, spec)
            stored.append((root, state))
            assert fields("rebuilt") - before["rebuilt"] == 1
            assert fields("reused") - before["reused"] == slot - 1
            for family, had in spans.items():
                assert samples(family) - had == slot
        assert fields("patched") == before["patched"]
        for root, post in stored:  # none skipped, deferred or stored as a delta
            raw = kv.get(_STATE + root)
            assert raw == loop_oracle(post, spec)
            assert BeaconState.decode(raw, spec).hash_tree_root(spec) == bytes(
                blocks.get_block(root, spec).message.state_root
            )
        resumed_root, resumed = states.get_latest_verified_state(blocks, spec)
        assert resumed_root == stored[-1][0]
        assert resumed.encode(spec) == kv.get(_STATE + resumed_root)
