"""The benchmark's files: every cell, traffic mix, configuration and metric
is found by its name, and BENCHMARK.json keeps to its own rules."""
from __future__ import annotations

import json
import re

import numpy as np
import pytest

from _bench_tiny import ROOT  # noqa: F401  (puts bench/ on the path)
from harness import spec

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def _cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"][1] == "bench/run.py"
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    named = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    names = [e["name"] for e in named]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    c = spec.load_cell(cell)
    assert c.kind in ("train", "serve")
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    compared = {"train": {"loss_gap", "grad_gap", "grad_dir_gap", "grad_dir_median",
                          "delta_gap", "delta_median"},
                "serve": {"logit_gap"}}
    assert c.limits and set(c.limits) <= compared[c.kind]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_loads_and_moves_a_reported_metric(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    assert callable(spec.metric_reader(metric))
    moved = E2E[m["moves"]]
    for cell in _cells_of(m):
        assert cell in _cells_of(moved), f"{metric} moves {m['moves']}, not reported in {cell}"


def test_every_config_is_used_and_lies_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]


def test_each_pair_of_config_and_traffic_is_one_cell():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_file_states_what_its_cells_run(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    data = json.loads((ROOT / entry["file"]).read_text())
    assert data.get("reduced", []) == entry["reduced"]
    for w in BENCH["workloads"]:
        if w["config"] == config and "stated_numerics" in data:
            assert spec.load_cell(w["name"]).numerics == data["stated_numerics"], w["name"]


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        spec.load_cell("no-such-cell")


def test_serving_traffic_gives_every_seed_the_same_work_in_an_even_order():
    from harness import traffic

    tr = json.loads((ROOT / "bench/traffic/poisson-lognormal-32x2560.json").read_text())
    runs = [traffic.requests(tr, seed, 40, 1000) for seed in (7, 2**31 + 5)]
    sizes = [[(len(r.prompt), r.new_tokens) for r in rs] for rs in runs]
    assert sizes[0] != sizes[1]
    for k in (0, 1):
        assert sorted(s[k] for s in sizes[0]) == sorted(s[k] for s in sizes[1])
    gen = np.array([r.new_tokens for r in runs[1]])
    strata = np.array_split(np.sort(gen), traffic.BLOCK)
    for j in range(len(gen) // traffic.BLOCK):   # one size of each stratum a block
        block = np.sort(gen[j * traffic.BLOCK:(j + 1) * traffic.BLOCK])
        assert all(lo[0] <= v <= lo[-1] for v, lo in zip(block, strata))
