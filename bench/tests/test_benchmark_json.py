"""BENCHMARK.json keeps to its contract: names, units, keys, and a file
for every configuration, traffic mix and metric it names."""
import os
import re

import pytest

from harness.spec import BENCH_DIR, ROOT, load_benchmark, load_cell, load_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
BENCH = load_benchmark()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(TEXT.match(w) for w in BENCH["command"])


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_and_text(entry):
    assert NAME.match(entry["name"])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert TEXT.match(entry[key]), key
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in entry.get("reduced", ()):
        assert NAME.match(key)


def test_unique_names():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", METRICS, ids=lambda e: e["name"])
def test_metric_has_reader_and_moves(metric):
    assert os.path.isfile(os.path.join(BENCH_DIR, "metrics",
                                       f"{metric['name']}.py"))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    if metric in BENCH["per_layer"]:
        moved = e2e[metric["moves"]]
        for cell in metric.get("workloads", cells):
            assert cell in moved.get("workloads", cells), cell
    else:
        assert 0 < metric["bound"] <= 0.25


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_loads(w):
    cell = load_cell(w["name"])
    assert cell.chips in (1, 4)
    assert os.path.isfile(os.path.join(ROOT, [
        c["file"] for c in BENCH["configs"] if c["name"] == w["config"]][0]))
    assert {m["name"] for m in cell.metrics(False)} >= {"setup_s"}
    assert cell.metrics(True)


def test_reduced_lists_only_depth():
    """``reduced`` names only the cuts the model-configs guide allows
    (depth, the chip's share of the routed experts, a slice of the
    vocabulary), each beside its published value, above the guide's
    floors, and never a width."""
    allowed = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for c in BENCH["configs"]:
        cfg = load_json(os.path.join(ROOT, c["file"]))
        assert cfg.get("reduced", []) == c["reduced"], c["name"]
        published = cfg.get("published", {})
        for key in c["reduced"]:
            assert key in allowed, (c["name"], key)
            assert not re.search(r"(_dim|_rank|hidden|heads)$", key)
            assert published[key] > cfg[key], (c["name"], key)
        if "num_hidden_layers" in c["reduced"]:
            lead = cfg.get("first_k_dense_replace", 0)
            assert cfg["num_hidden_layers"] - lead >= 4, c["name"]
        if "n_routed_experts" in c["reduced"]:
            assert cfg["n_routed_experts"] >= 8, c["name"]
        if "vocab_size" in c["reduced"]:
            assert 8 * cfg["vocab_size"] >= published["vocab_size"], c["name"]
