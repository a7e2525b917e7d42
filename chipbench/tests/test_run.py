"""run.py's entry point refuses to run without a TPU, printing no
result, here and in a directory that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.tests.conftest import ROOT

ARGS = ["--workload", "qwen3-4b.docqa-1k", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_cpu_backend():
    p = _run(ROOT)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


@pytest.mark.parametrize("what", ["benchmark only"])
def test_refuses_without_the_program(tmp_path, what):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_result_line_is_strict_json():
    from chipbench import run

    line = json.dumps(run._json_safe(
        {"readings": {"max_logit_err": float("inf"), "rms_logit_err": 0.5},
         "checks": {"x": {"value": float("nan"), "limit": 0.1}}}),
        allow_nan=False)
    assert json.loads(line)["readings"] == {"max_logit_err": "inf",
                                            "rms_logit_err": 0.5}
