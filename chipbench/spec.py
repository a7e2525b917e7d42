"""Everything a cell needs, found by the names ``BENCHMARK.json`` gives.

A cell names a configuration and a traffic mix; each is a JSON file
``configs/<config>.json`` and ``traffic/<traffic>.json`` beside this
module. A cell's correctness limits are ``limits/<workload>.json``, one
per number compared (``check.readings`` names them), and a per-layer
metric is a reader ``metrics/<metric>.py`` with a function ``read(window)
-> float | None``. A new cell, mix or metric is a new file and a new
entry, never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    workload: dict            # the BENCHMARK.json entry
    config: dict              # configs/<config>.json
    traffic: dict             # traffic/<traffic>.json
    limits: dict              # limits/<workload>.json
    end_to_end: list          # metric entries this cell reports
    per_layer: list


def load_benchmark(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: Path, workload: str, bench_dir: Path = HERE) -> Cell:
    bench = load_benchmark(root)
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if not wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    wl = wl[0]
    return Cell(
        workload=wl,
        config=_load_json(bench_dir / "configs" / f"{wl['config']}.json"),
        traffic=_load_json(bench_dir / "traffic" / f"{wl['traffic']}.json"),
        limits=_load_json(bench_dir / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"]
                    if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)])


def metric_reader(name: str, bench_dir: Path = HERE):
    """The ``read`` function of ``metrics/<name>.py``, loaded by path so a
    metric's name may hold dots and dashes."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: "
                                f"{path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics:{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
