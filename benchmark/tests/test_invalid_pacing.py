"""``generators/invalid_bursts.py``'s settling of the harness before the
window, on real child processes at a tenth of the chip's time scale: 8
minters that take 0.154 s a burst, a guard burst and a consumer that take
0.09 s a burst, round robin, a 4.8 s window.  Before it opens the reference's
child has answered and every minter holds its share of the whole window; from
the opening every minter rests, the bank never runs dry, something is banked
at the close, and a minter that rested ends when told to.

    python3 -m pytest benchmark/tests/test_invalid_pacing.py -q
"""

from __future__ import annotations

import asyncio
import os
import queue
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

from generators import invalid_bursts  # noqa: E402
from test_sparse_pacing import Minter  # noqa: E402


class Reference:
    """The plain reference's answer queue, answering the anchor state late."""

    def __init__(self, after_s: float):
        self.answers: queue.Queue = queue.Queue()
        self.t_ready = time.perf_counter() + after_s

    def poll(self):
        if time.perf_counter() >= self.t_ready and self.answers.empty():
            self.answers.put({"kind": "state", "anchor_root": "00", "seconds": 1.0})

    def take(self, kind: str, timeout: float) -> dict:
        answer = self.answers.get(timeout=timeout)
        assert answer["kind"] == kind
        return answer


def test_the_bank_covers_the_window_before_it_opens():
    W, burst_s, seconds = 8, 0.09, 4.8
    minters = [Minter(0.154) for _ in range(W)]
    plain = Reference(after_s=0.5)
    try:
        ctx = types.SimpleNamespace(
            minters=minters, plain=plain, mix={"max_bursts": 600},
            args=types.SimpleNamespace(seconds=seconds),
            window=types.SimpleNamespace(t_open=None))
        feeder = invalid_bursts.Feeder.__new__(invalid_bursts.Feeder)
        feeder.ctx, feeder.burst_s, feeder.resting = ctx, float("inf"), {}
        feeder.rested_s = [0.0] * W
        feeder.guard_s, feeder.state_answer = burst_s, None

        async def settle():
            task = asyncio.ensure_future(feeder.settle())
            while not task.done():
                plain.poll()
                await asyncio.sleep(0.01)
            task.result()

        asyncio.run(settle())
        share = feeder.share(seconds, burst_s)
        assert feeder.state_answer["kind"] == "state"
        assert share == -(-(seconds / burst_s + 1) // W) + 1
        assert min(m.frames.qsize() for m in minters) >= share

        feeder.pace()  # the opening: the bank covers the window
        assert len(feeder.resting) == W
        ctx.window.t_open = time.perf_counter()
        bank = sum(m.frames.qsize() for m in minters)
        taken, lowest = 0, bank
        while time.perf_counter() - ctx.window.t_open < seconds:
            minters[taken % W].frames.get(timeout=2)  # dry for 2 s: the test fails
            taken += 1
            time.sleep(burst_s)
            feeder.burst_s = min(feeder.burst_s, burst_s)
            feeder.pace()
            lowest = min(lowest, sum(m.frames.qsize() for m in minters))
        banked = sum(m.frames.qsize() for m in minters)
        resting_at_close = len(feeder.resting)
        feeder.wake_all()
        assert taken >= 0.8 * seconds / burst_s / 1.3  # the consumer kept its pace
        assert lowest >= 1 and banked >= 1
        assert resting_at_close == W and not feeder.resting
        # no minter minted inside the window: each rested all of it
        assert banked == bank - taken
        assert all(r > 0.9 * seconds for r in feeder.rested_s), feeder.rested_s
    finally:
        for m in minters:
            m.proc.terminate()
        for m in minters:
            assert m.proc.wait(timeout=5) is not None
