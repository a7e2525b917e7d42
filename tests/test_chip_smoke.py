"""chip_smoke.py on CPU: its phases at reduced width with the kernels in
interpret mode, and its entry point refusing a backend that is not a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def records(chip_smoke):
    out = []
    chip_smoke.run(reduced=True, interpret=True, seed=0, context_tokens=256,
                   mixed_tokens=128, max_new=4, log=out.append)
    return out


def _phase(records, name):
    return [r for r in records if r["phase"] == name]


def test_smoke_phases_reduced_width(records, chip_smoke):
    (cfg,) = _phase(records, "config")
    assert (cfg["arch"], cfg["layers"], cfg["d_model"]) == \
        ("sparkv-qwen3-4b-smoke", 4, 64)
    assert [r["tokens"] for r in _phase(records, "register")] == [256, 128]
    (kernel,) = _phase(records, "dequant_kernel")
    assert kernel["kernel"] == "kv_dequant" and kernel["interpret"]
    assert kernel["parity_bits"] == [3, 5, 8]
    (alloc,) = _phase(records, "allocation")
    assert len(alloc["bit_widths"]) > 1
    assert _phase(records, "compile")[0]["compile_s"] >= 0
    assert len(_phase(records, "memory")) == 1


def test_smoke_requests_answer(records):
    reqs = _phase(records, "request")
    assert [(r["policy"], r["alloc_schedule"]) for r in reqs] == [
        ("sparkv", "uniform"), ("cachegen", "attention"),
        ("sparkv", "attention")]
    for r in reqs:
        assert r["n_streamed"] > 0
        # the bounds tests/test_serving.py holds the reduced model to
        assert r["top1_agreement"] >= 0.8
        assert 0 <= r["mean_kl"] < 0.5
        assert len(r["tokens"]) == 4
        assert r["host_load_s"] > 0 and r["host_decode_s"] > 0
        assert "ttft_s" not in r
    # cachegen streams every chunk of its context
    assert reqs[1]["n_computed"] == 0


def test_smoke_check_raises(chip_smoke):
    with pytest.raises(RuntimeError, match="chip smoke check failed"):
        chip_smoke._check(False, "deliberate")


def test_smoke_refuses_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert json.loads(line).get("ok") is not True
