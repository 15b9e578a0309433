"""CPU checks of the benchmark's data: every name in ``BENCHMARK.json``
resolves to the files the harness will look for, so a cell cannot die on a
missing file or a ``KeyError`` after minutes of set-up on the chip.

    python3 -m pytest benchmark/tests -q

Parametrised over the cells and metrics of ``BENCHMARK.json``, so a later PR's
entries are checked without an edit here.  Nothing imports JAX.
"""

from __future__ import annotations

import ast
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = BENCH["workloads"]
LAYERS = BENCH["per_layer"]
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def ids(entries):
    return [e["name"] for e in entries]


@pytest.mark.parametrize("cell", CELLS, ids=ids(CELLS))
def test_cell_files_resolve(cell):
    config = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert config["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == config["name"] and cfg["source"] == config["source"]
    assert cfg["reduced"] == config["reduced"]
    assert cfg["reference"]["compared"], "the configuration names no plain reference"
    mix = load("traffic", cell["traffic"] + ".json")
    generator = importlib.import_module("generators." + mix["generator"])
    assert callable(generator.run) and callable(generator.start_workers)


def returned_end_to_end(module_name: str) -> set[str]:
    """The keys of the ``"end_to_end": {...}`` literal a generator returns."""
    path = os.path.join(HERE, "generators", module_name + ".py")
    with open(path) as f:
        tree = ast.parse(f.read())
    keys: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Dict):
            for k, v in zip(node.keys, node.values):
                if (isinstance(k, ast.Constant) and k.value == "end_to_end"
                        and isinstance(v, ast.Dict)):
                    keys |= {x.value for x in v.keys if isinstance(x, ast.Constant)}
    return keys


@pytest.mark.parametrize("cell", CELLS, ids=ids(CELLS))
def test_generator_reports_only_entered_end_to_end(cell):
    """``session.run`` looks every key a generator returns up in
    ``end_to_end`` (``units[k]``): one that is not entered is a ``KeyError``
    after the window."""
    mix = load("traffic", cell["traffic"] + ".json")
    keys = returned_end_to_end(mix["generator"])
    assert keys, "the generator returns no end-to-end metric"
    for key in keys:
        assert key in END_TO_END, f"{key} is not an end_to_end entry"
    # every cell reports setup_s, one more end-to-end and one per-layer metric
    def here(metric):
        return "workloads" not in metric or cell["name"] in metric["workloads"]

    mine = [m for m in keys if here(END_TO_END[m])]
    assert mine and here(END_TO_END["setup_s"])
    assert any(here(m) for m in LAYERS)


@pytest.mark.parametrize("entry", LAYERS, ids=ids(LAYERS))
def test_layer_metric_resolves(entry):
    spec = load("layers", entry["name"] + ".json")
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert spec["cells"] == entry["workloads"]
    reader = importlib.import_module("readers." + spec["reader"])
    assert callable(reader.read)
    moved = END_TO_END[entry["moves"]]  # KeyError: `moves` names no end-to-end metric
    for cell in entry["workloads"]:
        assert cell in ids(CELLS)
        assert "workloads" not in moved or cell in moved["workloads"], (
            f"{cell} does not report {entry['moves']}")


def test_every_layer_file_is_entered_or_named_as_not_entered():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    section3 = perf.split("## 3.")[1].split("## 4.")[0]
    entered = set(ids(LAYERS))
    for name in sorted(os.listdir(os.path.join(HERE, "layers"))):
        metric = name[: -len(".json")]
        if metric in entered:
            continue
        line = next((ln for ln in section3.splitlines() if metric in ln), "")
        assert "not entered" in section3 and line, (
            f"layers/{name} is neither a per_layer entry nor named in PERF.md section 3")


class FakeWindow:
    """A window's books with nothing behind them."""

    def __init__(self, gained: dict):
        self.gained = gained

    def span_delta(self, family):
        return self.gained.get(family, (0.0, 0))


def test_span_readers_per_blocks():
    from readers import fact_minus_span, span_count, span_mean

    window = FakeWindow({"block_transition_seconds": (36.0, 4),
                         "bls_dispatch_seconds": (0.6, 8)})
    facts = {"blocks": 4, "ms_per_block": 10_000.0}
    assert span_mean.read(window, facts, family="block_transition_seconds",
                          scale=1000.0) == 9000.0
    # per "blocks": two chains a block are summed, not averaged
    assert span_mean.read(window, facts, family="bls_dispatch_seconds",
                          per="blocks", scale=1000.0) == pytest.approx(150.0)
    assert span_count.read(window, facts, family="bls_dispatch_seconds",
                           per="blocks") == 2.0
    assert fact_minus_span.read(window, facts, fact="ms_per_block",
                                family="block_transition_seconds",
                                scale=1000.0) == 1000.0
    # nothing to read: nothing returned, never 0
    assert span_mean.read(window, facts, family="absent_seconds", per="blocks") is None
    assert span_mean.read(window, {"blocks": 0}, family="bls_dispatch_seconds",
                          per="blocks") is None
    assert fact_minus_span.read(window, {"ms_per_block": None}, fact="ms_per_block",
                                family="block_transition_seconds") is None
    assert fact_minus_span.read(window, facts, fact="ms_per_block",
                                family="absent_seconds") is None


def test_trace_readers_per_traced_items():
    from readers import trace_device_time, trace_module_time

    class Traced:
        trace = {"ops": [["fusion.1", 0.2], ["copy.2", 0.1]],
                 "modules": [["jit_miller(1)", 0.05], ["jit_ladder_g1(2)", 0.03],
                             ["jit_transition_epoch(3)", 0.4]]}
        traced = {"items": 2}

    assert trace_device_time.read(Traced, {}, scale=1000.0) == pytest.approx(150.0)
    assert trace_module_time.read(
        Traced, {}, select="agg_corrected|ladder_g[12]|miller|masked_product",
        scale=1000.0) == pytest.approx(40.0)
    assert trace_module_time.read(Traced, {}, select="no_such_module") is None
    Traced.traced = {"items": None}  # the trace stopped at no item boundary
    assert trace_device_time.read(Traced, {}) is None


def test_contract_limits_of_the_entries():
    """The limits ``BENCHMARK.json`` is refused over before any run."""
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = ids(BENCH[group])
        assert len(names) == len(set(names)), f"a name twice in {group}"
        for entry in BENCH[group]:
            assert name.match(entry["name"]), entry["name"]
            for key in ("why", "source", "layer"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key], (
                        entry["name"], key, len(entry[key]))
    for metric in BENCH["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25, metric["name"]
    pairs = [(w["config"], w["traffic"]) for w in CELLS]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in CELLS) <= max(1, len(CELLS) // 2)
    used = {w["config"] for w in CELLS}
    assert used == set(ids(BENCH["configs"])), "a configuration no cell uses"
