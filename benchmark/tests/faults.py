#!/usr/bin/env python3
"""benchmark/tests/faults.py — one run of a cell with the timed path broken
underneath: ``correct`` has to come out false.

    python3 benchmark/tests/faults.py --fault <name> --workload <cell> --seed <n> [--rehearse]

Everything but the planted fault is ``run.py``'s own run (``run.main``): on
the chip at the cell's own size, with ``--rehearse`` on the CPU at the tiny
size (``test_faults.py`` runs that).  Exit code 0 where the run read
``correct: false`` (``true`` for ``none``), 1 where the fault went unseen.  The benchmark's own runs
never come here.

Faults of a block-import cell (``range_blocks``):

``host_rlc``         the control: the program's own lower path switched on —
                     ``BLS_BLOCK_BATCH_MIN_MEMBERS`` above any block's membership
                     sends the blocks' attestation batches down the host RLC
                     branch, which breaks the configuration's guarantee that the
                     device chain verifies them inside the window.
``state_unchanged``  a step that returns its state unchanged: ``on_block``'s
                     state transition hands back the pre-state for the window's
                     blocks.
``half_left_out``    half of the batch left out: every second block of the round
                     never reaches ``PendingBlocks``.
``answer_altered``   an answer altered where it is produced: the post-state of
                     the round's last block is stored with one field changed.
``persisted_altered`` an acknowledged write that reads back wrong: the last
                     block's post-state goes to the store with one byte changed.
``none``             nothing planted: the same run has to read ``correct: true``
                     (exit code 0 where it does).
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import run as bench_run  # noqa: E402  (benchmark/run.py; touches neither JAX nor the package)


def window_slots(workload: str, rehearse: bool) -> list[int]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    if rehearse:
        mix = {**mix, **mix.get("rehearse", {})}
    return [int(mix["first_slot"]) + i for i in range(int(mix["blocks"]))]


def plant_host_rlc(slots):
    steer = bench_run.steer_rehearsal

    def steer_then_host():
        steer()
        os.environ["BLS_BLOCK_BATCH_MIN_MEMBERS"] = str(10 ** 9)

    bench_run.steer_rehearsal = steer_then_host
    os.environ["BLS_BLOCK_BATCH_MIN_MEMBERS"] = str(10 ** 9)


def plant_state_unchanged(slots):
    from lambda_ethereum_consensus_tpu.fork_choice import handlers

    transition = handlers.state_transition

    def unchanged(pre_state, signed_block, **kw):
        if int(signed_block.message.slot) in slots:
            return pre_state
        return transition(pre_state, signed_block, **kw)

    handlers.state_transition = unchanged


def plant_half_left_out(slots):
    from lambda_ethereum_consensus_tpu.node.pending_blocks import PendingBlocks

    add_block = PendingBlocks.add_block
    dropped = set(slots[1::2])

    def add_half(self, signed_block):
        if int(signed_block.message.slot) not in dropped:
            add_block(self, signed_block)

    PendingBlocks.add_block = add_half


def plant_answer_altered(slots):
    from lambda_ethereum_consensus_tpu.fork_choice import handlers

    transition = handlers.state_transition

    def altered(pre_state, signed_block, **kw):
        out = transition(pre_state, signed_block, **kw)
        if int(signed_block.message.slot) == slots[-1]:
            kept = {k: getattr(out, k) for k in ("_root_engine", "_resident_plane")
                    if hasattr(out, k)}
            out = out.copy(eth1_deposit_index=int(out.eth1_deposit_index) + 1)
            for k, v in kept.items():  # the altered state rides the same lineage
                try:
                    object.__setattr__(out, k, v)
                except (AttributeError, TypeError):
                    pass
        return out

    handlers.state_transition = altered


def plant_persisted_altered(slots):
    from lambda_ethereum_consensus_tpu.store import state_store

    def store_altered(self, block_root, state, spec=None):
        raw = bytearray(state.encode(spec))
        if int(state.slot) == slots[-1]:
            raw[-1] ^= 1  # the tail of the last variable field that holds bytes
        self._kv.put(state_store._STATE + block_root, bytes(raw))
        self._kv.put(state_store._slot_key(state.slot), block_root)

    state_store.StateStore.store_state = store_altered


FAULTS = {
    "none": lambda slots: None,
    "persisted_altered": plant_persisted_altered,
    "host_rlc": plant_host_rlc,
    "state_unchanged": plant_state_unchanged,
    "half_left_out": plant_half_left_out,
    "answer_altered": plant_answer_altered,
}


class Tee(io.TextIOBase):
    """Standard output, passed on and kept: the result is its last line."""

    def __init__(self, out):
        self.out, self.lines, self._tail = out, [], ""

    def write(self, text):
        self.out.write(text)
        *whole, self._tail = (self._tail + text).split("\n")
        self.lines += [ln for ln in whole if ln.strip()]
        return len(text)

    def flush(self):
        self.out.flush()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--fault", choices=sorted(FAULTS), required=True)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    if args.rehearse:
        bench_run.steer_rehearsal()  # before the package is imported below
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    FAULTS[args.fault](window_slots(args.workload, args.rehearse))
    run_argv = ["--workload", args.workload, "--seed", str(args.seed), "--trace", "0"]
    if args.seconds is not None:
        run_argv += ["--seconds", str(args.seconds)]
    if args.rehearse:
        run_argv.append("--rehearse")
    tee = sys.stdout = Tee(sys.stdout)
    try:
        code = bench_run.main(run_argv)
    finally:
        sys.stdout = tee.out
    if code != 0 or not tee.lines:
        print(f"faults: the run ended with code {code} and no result", file=sys.stderr)
        return 2
    result = json.loads(tee.lines[-1])
    seen = result["correct"] is (args.fault == "none")  # sound run: true; a fault: false
    print(json.dumps({"fault": args.fault, "seen": seen, "correct": result["correct"],
                      "failed": result["failed"], "why": result.get("why"),
                      "compared": result.get("compared")}), flush=True)
    return 0 if seen else 1


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # as run.py: a failed run may leave the node's threads behind
