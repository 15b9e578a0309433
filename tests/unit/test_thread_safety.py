"""Regression tests for the round-25 thread-shared-state /
lifecycle-teardown sweep (graftlint v2's first interprocedural catch).

Four process-wide memos (``utils/env._TPU_BACKEND``,
``ops/bigint._OPS``, ``ops/bls_fq12._FQ12_OPS``,
``ops/mesh._DEFAULT_MESH``) were rebuilt with no lock while being
reachable from three thread classes at once — the asyncio event loop,
executor duty/API threads, and the drain-warmer thread — so two racing
first-callers could each pay the build (and, for the jax-probing ones,
race backend init).  Each test hammers the memo from a thread barrier
and asserts the build ran exactly once / every caller saw one object.

Plus the two teardown leaks: ``prefetched()`` dropped its
replay-prefetch thread handle on generator close, and
``BeaconNode.stop()`` never joined the drain-warmer.
"""

import asyncio
import os
import threading

from lambda_ethereum_consensus_tpu.node.replay import prefetched
from lambda_ethereum_consensus_tpu.utils import env as env_mod


def _hammer(fn, n=16):
    """Call ``fn`` from n threads released together; return results."""
    barrier = threading.Barrier(n)
    results = [None] * n
    errors = []

    def run(i):
        try:
            barrier.wait(timeout=10)
            results[i] = fn()
        except Exception as e:  # surfaced below, never swallowed
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors
    return results


def test_device_default_memo_single_probe(monkeypatch):
    """Concurrent first calls compute the platform probe once and agree."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("BLS_NO_DEVICE", raising=False)
    monkeypatch.setattr(env_mod, "_TPU_BACKEND", None)
    results = _hammer(env_mod.device_default)
    assert results == [False] * len(results)
    assert env_mod._TPU_BACKEND is False


def test_bigint_ops_memo_builds_once(monkeypatch):
    from lambda_ethereum_consensus_tpu.ops import bigint

    calls = []
    real = bigint.make_ops

    def counted():
        calls.append(1)
        return real()

    monkeypatch.setattr(bigint, "make_ops", counted)
    monkeypatch.setattr(bigint, "_OPS", None)
    results = _hammer(bigint.get_ops, n=8)
    assert len(calls) == 1
    assert all(r is results[0] for r in results)


def test_fq12_ops_memo_builds_once(monkeypatch):
    from lambda_ethereum_consensus_tpu.ops import bls_fq12

    calls = []
    real = bls_fq12.make_fq12_ops

    def counted():
        calls.append(1)
        return real()

    monkeypatch.setattr(bls_fq12, "make_fq12_ops", counted)
    monkeypatch.setattr(bls_fq12, "_FQ12_OPS", None)
    results = _hammer(bls_fq12.get_fq12_ops, n=8)
    assert len(calls) == 1
    assert all(r is results[0] for r in results)


def test_default_mesh_single_identity(monkeypatch):
    """Every concurrent first-caller gets the SAME Mesh object — distinct
    meshes would fork every id-keyed stage cache downstream."""
    from lambda_ethereum_consensus_tpu.ops import mesh as mesh_mod

    monkeypatch.setattr(mesh_mod, "_DEFAULT_MESH", None)
    results = _hammer(mesh_mod.default_mesh, n=8)
    assert all(r is results[0] for r in results)


def test_prefetched_close_joins_worker():
    """Abandoning the generator tears the replay-prefetch thread down
    (PR 8 leak class): after close(), no replay-prefetch thread lives."""
    started = threading.Event()

    def slow_prep(x):
        started.set()
        return x

    gen = prefetched(range(100), slow_prep, depth=2)
    assert next(gen) == 0
    assert started.wait(timeout=5)
    gen.close()
    leaked = [
        t for t in threading.enumerate() if t.name == "replay-prefetch" and t.is_alive()
    ]
    assert leaked == []


def test_node_stop_joins_warmer():
    """BeaconNode.stop() joins the drain-warmer thread instead of leaking
    it into the next test's process state."""
    from lambda_ethereum_consensus_tpu.node.node import BeaconNode, NodeConfig

    node = BeaconNode(NodeConfig(db_path=os.devnull))
    release = threading.Event()
    warmer = threading.Thread(
        target=release.wait, kwargs={"timeout": 5}, daemon=True, name="drain-warmer"
    )
    warmer.start()
    node._warmer = warmer
    release.set()
    asyncio.run(node.stop())
    assert node._warmer is None
    assert not warmer.is_alive()
