"""``generators/sparse_bursts.py``'s pacing of the minters, on real child
processes at a tenth of the chip's time scale: 8 minters that take 0.154 s a
burst, a consumer that takes one burst every 0.03 s round robin, a bank of 96
at the opening of a 4.8 s window.  The bank never runs dry, every minter rests
for part of the window, something is banked at the close, and a minter that
rested ends when told to.

    python3 -m pytest benchmark/tests/test_sparse_pacing.py -q
"""

from __future__ import annotations

import os
import queue
import subprocess
import sys
import threading
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from generators import sparse_bursts  # noqa: E402

MINTER = """
import sys, time
while True:
    t = time.perf_counter()
    while time.perf_counter() - t < float(sys.argv[1]):
        pass
    print("burst", flush=True)
"""


class Minter:
    def __init__(self, mint_s: float):
        self.proc = subprocess.Popen([sys.executable, "-c", MINTER, str(mint_s)],
                                     stdout=subprocess.PIPE, text=True)
        self.frames: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self):
        for line in self.proc.stdout:
            self.frames.put(line)


def test_minters_rest_once_the_window_is_covered():
    W, burst_s, seconds, bank = 8, 0.03, 4.8, 96
    minters = [Minter(0.154) for _ in range(W)]
    try:
        while sum(m.frames.qsize() for m in minters) < bank:
            time.sleep(0.01)
        ctx = types.SimpleNamespace(
            minters=minters, args=types.SimpleNamespace(seconds=seconds),
            window=types.SimpleNamespace(t_open=time.perf_counter()))
        feeder = sparse_bursts.Feeder.__new__(sparse_bursts.Feeder)
        feeder.ctx, feeder.burst_s, feeder.resting = ctx, burst_s, {}
        feeder.rested_s = [0.0] * W
        taken, lowest = 0, bank
        while time.perf_counter() - ctx.window.t_open < seconds:
            minters[taken % W].frames.get(timeout=2)  # dry for 2 s: the test fails
            taken += 1
            time.sleep(burst_s)
            feeder.pace()
            lowest = min(lowest, sum(m.frames.qsize() for m in minters))
        banked = sum(m.frames.qsize() for m in minters)
        resting_at_close = len(feeder.resting)
        feeder.wake_all()
        assert taken >= 0.8 * seconds / burst_s / 1.3  # the consumer kept its pace
        assert lowest >= 1 and banked >= 1
        assert resting_at_close == W and not feeder.resting
        # each rested for most of what was left once the bank covered the window
        assert all(r > 0.3 * seconds for r in feeder.rested_s), feeder.rested_s
        assert banked < bank  # and did not mint the window through
    finally:
        for m in minters:
            m.proc.terminate()
        for m in minters:
            assert m.proc.wait(timeout=5) is not None
