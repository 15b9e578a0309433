"""benchmark/tracered.py — from the profiler's trace to numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with nothing but JAX
(``jax.profiler.ProfileData``).  Per device plane: the union of the
intervals in which an operation ran (busy), each operation's self time
summed by name, and the idle gaps inside the traced window, each labelled
by the innermost program span open on the host at its midpoint (the
harness writes those spans into the same trace as ``TraceAnnotation``s
named ``bench:<span>``; ``bench:window`` brackets the traced window).

Checked on ``fixtures/tiny.xplane.pb`` by ``run.py --rehearse``.
"""

from __future__ import annotations

import glob
import os
from bisect import bisect_right

DEVICE_PREFIX = "/device:TPU:"  # beside it the trace has "/device:CUSTOM:..." and "/host:..." planes
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _self_times(events: list[tuple[int, int, str]]) -> dict[str, int]:
    """Per name, duration minus the part covered by nested events (a
    ``while`` holds its body's operations on the same line)."""
    out: dict[str, int] = {}
    stack: list[list] = []  # [end, name, self_ns]
    for start, end, name in sorted(events, key=lambda e: (e[0], -(e[1] - e[0]))):
        while stack and stack[-1][0] <= start:
            done = stack.pop()
            out[done[1]] = out.get(done[1], 0) + done[2]
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    for done in stack:
        out[done[1]] = out.get(done[1], 0) + done[2]
    return out


def _events(line) -> list[tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events if e.duration_ns > 0]


def reduce_trace(path: str) -> dict:
    """``{"devices", "busy_s", "ops", "modules", "gaps", "window_ns",
    "planes"}``: busy seconds averaged over the device planes, self time by
    operation and time by XLA module summed over them (seconds, longest
    first), idle time inside ``bench:window`` by host span label."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {p.name: [ln.name for ln in p.lines] for p in data.planes}
    spans: list[tuple[int, int, str]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            spans += [e for e in _events(line) if e[2].startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    window = (min(w[0] for w in windows), max(w[1] for w in windows)) if windows else None
    labels = sorted((s for s in spans if s[2] != WINDOW_SPAN), key=lambda s: s[0])
    starts = [s[0] for s in labels]

    def label_at(t: int) -> str:
        """The innermost span open at ``t``: the latest started that holds it
        (looking back over at most 512 starts keeps a long trace cheap)."""
        i = bisect_right(starts, t) - 1
        for j in range(i, max(i - 512, -1), -1):
            if labels[j][1] > t:
                return labels[j][2][len(SPAN_PREFIX):]
        return "none"

    busy_ns, ops, modules, gaps, n_dev = 0, {}, {}, {}, 0
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        op_events = (_events(lines[OPS_LINE]) if OPS_LINE in lines
                     else [e for ln in plane.lines for e in _events(ln)])
        if not op_events:
            continue
        n_dev += 1
        if window is not None:
            op_events = [(max(s, window[0]), min(e, window[1]), n)
                         for s, e, n in op_events if e > window[0] and s < window[1]]
        busy = _union([(s, e) for s, e, _ in op_events])
        busy_ns += sum(e - s for s, e in busy)
        for name, ns in _self_times(op_events).items():
            ops[name] = ops.get(name, 0) + ns
        if MODULES_LINE in lines:
            for s, e, name in _events(lines[MODULES_LINE]):
                modules[name] = modules.get(name, 0) + (e - s)
        lo, hi = window or (busy[0][0], busy[-1][1])
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            label = label_at((a + b) // 2)
            gaps[label] = gaps.get(label, 0) + (b - a)

    def ranked(d: dict[str, int]) -> list[list]:
        return [[k, v / 1e9 / max(n_dev, 1)]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {
        "devices": n_dev,
        "busy_s": busy_ns / 1e9 / max(n_dev, 1),
        "window_ns": None if window is None else window[1] - window[0],
        "ops": ranked(ops), "modules": ranked(modules), "gaps": ranked(gaps),
        "planes": planes,
    }
