"""Find a cell's files by the names in ``BENCHMARK.json``.

Every configuration, traffic mix, cell and per-layer metric is a file of
its own under ``bench/``; adding one never edits another:

    bench/configs/<config>.json      sizes of the model, as run
    bench/traffic/<traffic>.json     parameters of the traffic generator
    bench/workloads/<workload>.json  numerics, peak and correctness limits
    bench/metrics/<metric>.py        reader of one per-layer metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """Everything one run of one workload needs to know."""

    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    workload: dict          # bench/workloads/<name>.json
    end_to_end: tuple       # BENCHMARK.json entries this cell reports
    per_layer: tuple

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def numerics(self) -> dict:
        return self.workload["numerics"]

    @property
    def limits(self) -> dict:
        return self.workload["limits"]


def _applies(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def load_cell(name: str) -> Cell:
    spec = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in spec["workloads"])
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: {known}")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name,
        chips=entry["chips"],
        config=load_json(ROOT / cfg_entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        workload=load_json(BENCH / "workloads" / f"{name}.json"),
        end_to_end=tuple(m for m in spec["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _applies(m, name)),
    )


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
