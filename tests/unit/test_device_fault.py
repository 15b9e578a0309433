"""Device runtime-fault containment (round 20 satellite): a device dying
mid-dispatch must cost latency, never correctness or the batch —
the verify/sign hot paths fall back to the bit-exact host math, count
``device_fault_total{plane}``, and latch the ``/debug/slo`` health flag."""

import pytest

from lambda_ethereum_consensus_tpu.crypto import bls
from lambda_ethereum_consensus_tpu.crypto.bls import batch as bls_batch
from lambda_ethereum_consensus_tpu.crypto.bls import curve as C
from lambda_ethereum_consensus_tpu.crypto.bls.api import _pubkey_point
from lambda_ethereum_consensus_tpu.telemetry import (
    device_fault,
    device_fault_state,
    get_metrics,
)


class _DeadDevice(RuntimeError):
    """Stands in for XlaRuntimeError without importing jax."""


def _entries(n=3, bad=()):
    """(pk point, message, sig point) triples; indices in ``bad`` get a
    tampered message so their signature is invalid."""
    out = []
    for i in range(n):
        sk = (i + 1).to_bytes(32, "big")
        msg = b"message-%d" % i
        sig = bls.sign(sk, msg)
        check_msg = b"tampered" if i in bad else msg
        out.append((
            _pubkey_point(bls.sk_to_pk(sk)), check_msg, C.g2_from_bytes(sig)
        ))
    return out


@pytest.fixture
def dead_device(monkeypatch):
    """Force the device chain route on, then make every dispatch die."""
    monkeypatch.setattr(bls_batch, "_chain_enabled", lambda n: True)
    monkeypatch.setattr(bls_batch, "shard_active", lambda: False)

    def boom(checks):
        raise _DeadDevice("PJRT client lost mid-dispatch")

    monkeypatch.setattr(bls_batch, "_device_chain_verify", boom)


def test_verify_points_survives_device_fault(dead_device):
    before = get_metrics().get("device_fault_total", plane="bls_verify")
    assert bls_batch.verify_points(_entries(3)) is True
    assert bls_batch.verify_points(_entries(3, bad=(1,))) is False
    after = get_metrics().get("device_fault_total", plane="bls_verify")
    assert after >= before + 2
    state = device_fault_state()
    assert state["faulted"] is True
    assert state["planes"].get("bls_verify", 0) >= 2


def test_bisection_survives_device_fault_with_exact_blame(dead_device):
    """The bisection path's containment must keep per-item attribution:
    the bad item is flagged, its neighbors are not, whole batch intact."""
    flags = bls_batch.batch_verify_each_points(_entries(4, bad=(2,)))
    assert flags == [True, True, False, True]


def test_containment_does_not_mask_host_results(dead_device):
    """All-bad and empty batches behave identically to the host path."""
    assert bls_batch.batch_verify_each_points([]) == []
    flags = bls_batch.batch_verify_each_points(_entries(2, bad=(0, 1)))
    assert flags == [False, False]


def test_device_fault_latch_accumulates():
    before = device_fault_state()["planes"].get("test_plane", 0)
    device_fault("test_plane")
    device_fault("test_plane")
    state = device_fault_state()
    assert state["planes"]["test_plane"] == before + 2
    assert state["faulted"] is True
    assert get_metrics().get("device_fault_latched", plane="test_plane") == 1.0


def test_sign_batch_fault_latches_duty_plane(monkeypatch):
    """A raising device signing plane falls back to the host comb,
    bit-exact against the oracle, and latches the duty_sign plane."""
    from lambda_ethereum_consensus_tpu.ops import bls_sign

    def boom(points, scalars, nbits=255):
        raise _DeadDevice("device signing plane died")

    monkeypatch.setattr(bls_sign, "_sign_points_device", boom)
    sks = [(i + 1).to_bytes(32, "big") for i in range(4)]
    msgs = [b"duty-%d" % (i % 2) for i in range(4)]
    before = get_metrics().get("device_fault_total", plane="duty_sign")
    got = bls_sign.sign_batch(sks, msgs, device=True)
    assert got == [bls.sign(sk, msg) for sk, msg in zip(sks, msgs)]
    assert get_metrics().get("device_fault_total", plane="duty_sign") == before + 1
    assert device_fault_state()["planes"].get("duty_sign", 0) >= 1
