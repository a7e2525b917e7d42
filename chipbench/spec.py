"""Everything a cell needs, found by the names ``BENCHMARK.json`` gives.

A cell names a configuration and a traffic mix; each is a JSON file
``configs/<config>.json`` and ``traffic/<traffic>.json`` beside this
module. A cell's correctness limits are ``limits/<workload>.json``, one
per number compared (``check.readings`` names them), and a per-layer
metric is a reader ``metrics/<metric>.py`` with a function ``read(window)
-> float | None``. A new cell, mix or metric is a new file and a new
entry, never an edit.

A configuration's model family is ``families/<family>.py``, named by the
file's ``"family"`` key (``qwen`` without it). A new architecture is a
new family module and a configuration file that names it, never an edit.
A family module gives:

- ``shapes(conf)``: leaf shapes of the program's parameter tree;
- ``init_std(leaf, shape, conf)``: the standard deviation of a leaf's
  random weights, by the leaf's name;
- ``program_config(conf)``: the program's ``ModelConfig`` at the file's
  sizes, or ``None`` where the program has no model for the family;
- ``make_reference(conf, *, lo, n, fp8)``: the plain reference (with
  ``fp8`` its float8 control), which imports nothing of the program;
- ``token_forward_flops(conf, keys)``: one token's forward operations,
  its attention reading ``keys`` cached positions;
- ``token_params(conf)``: the parameters one token reads (of an expert
  layer, only the experts it is routed to);
- ``kv_plane_values(conf, chunk_tokens)``: the values in one streamed K
  or V plane.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
DEFAULT_FAMILY = "qwen"


class NoFamily(RuntimeError):
    """A configuration's model family has no module, or the program has
    no model for it."""


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


def family_name(conf: dict) -> str:
    """The model family a configuration names, ``qwen`` without one."""
    return conf.get("family", DEFAULT_FAMILY)


def family(conf: dict):
    """The module ``families/<family>.py`` of a configuration, loaded by
    path once per file."""
    path = HERE / "families" / f"{family_name(conf)}.py"
    if not path.is_file():
        raise NoFamily(f"configuration {conf.get('name')!r} names model "
                       f"family {family_name(conf)!r}, which has no module "
                       f"{path}")
    return _load_family(path)


@functools.cache
def _load_family(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench.families:{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
