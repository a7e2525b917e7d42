"""Discovery by name, and BENCHMARK.json against the rules it must keep:
a new configuration, traffic mix or per-layer metric is a new file and a
new entry, with no edit to the harness. A new architecture is a new
``families/<name>.py`` and a configuration file whose ``"family"`` names
it (``test_families.py``)."""
import json
import re
import shutil

import pytest

from chipbench import check, spec
from chipbench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_every_cell_finds_its_files(bench):
    for wl in bench["workloads"]:
        cell = spec.load_cell(ROOT, wl["name"])
        assert cell.config["name"] == wl["config"]
        assert cell.traffic["doc_tokens"] % \
            cell.config["server"]["chunk_tokens"] == 0
        assert cell.limits and set(cell.limits) <= set(check.NUMBERS)
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_names_units_and_files_keep_the_rules(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics] + \
        [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/")
        with open(ROOT / c["file"]) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert 1 <= bench["run_seconds"] <= 51


def test_a_new_cell_mix_and_metric_are_only_new_files(tmp_path):
    """A dummy configuration, mix, limit and metric added beside the
    real ones are found by their names, with no file of the harness
    edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p.relative_to(bench_dir): p.read_bytes()
              for p in bench_dir.rglob("*") if p.is_file()}

    conf = json.loads((bench_dir / "configs" / "qwen2.5-3b.json")
                      .read_text())
    conf.update(name="dummy-model", num_hidden_layers=2,
                reduced=["num_hidden_layers"])
    (bench_dir / "configs" / "dummy-model.json").write_text(
        json.dumps(conf))
    (bench_dir / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"policy": "cachegen", "doc_tokens": 1024, "question_tokens": [1, 2],
         "answer_tokens": [1, 2], "block": 2, "link_seed": 1}))
    (bench_dir / "limits" / "dummy-model.dummy-mix.json").write_text(
        json.dumps({"mean_logit_gap": 1.0}))
    (bench_dir / "metrics" / "dummy.metric-x.py").write_text(
        "def read(w):\n    return 42.0\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy-model", "source": "x",
                             "file": "chipbench/configs/dummy-model.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "dummy-model.dummy-mix",
                               "config": "dummy-model",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "dummy.metric-x", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels", "moves": "ttft_p50_s",
                               "workloads": ["dummy-model.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell(tmp_path, "dummy-model.dummy-mix", bench_dir)
    assert cell.config["name"] == "dummy-model"
    assert cell.traffic["policy"] == "cachegen"
    assert cell.limits == {"mean_logit_gap": 1.0}
    assert [m["name"] for m in cell.per_layer] == ["dummy.metric-x"]
    assert spec.metric_reader("dummy.metric-x", bench_dir)(None) == 42.0
    # the real cells see every metric but the dummy's
    real = spec.load_cell(tmp_path, "qwen3-4b.docqa-1k", bench_dir)
    assert "dummy.metric-x" not in [m["name"] for m in real.per_layer]
    for rel, data in before.items():
        assert (bench_dir / rel).read_bytes() == data, rel


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell(ROOT, "no-such.cell")
    with pytest.raises(FileNotFoundError, match="no reader"):
        spec.metric_reader("no_such_metric")
