"""Model families: the dense Qwen family draws the same weights and gives
the same reference as before it had a module of its own, bit for bit; a
new family is new files only; a configuration whose family the harness or
the program lacks is refused at set-up, before any weights are drawn."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, run, spec, weights
from chipbench.tests import tiny
from chipbench.tests.conftest import ROOT

# sha256 of tiny.config()'s weights drawn from seed 0 (every leaf's bytes
# in flatten order), and of its reference logits at positions 16..39 of
# 48 tokens, float32 and float8, as the harness gave them before the
# families had modules of their own (CPU)
WEIGHTS_SHA = \
    "13b09fc0c134be19a90b1c5b55edac282045bdf40eac93ec5accbdaa07a73819"
REFERENCE_SHA = {
    False: "2cbd0c95904d8d7386483eaf8a7dc25714387a2965858f99986e1e5ffd6463f2",
    True: "4182b028ce48e0796401b8b5f7bdc50b313b60f53aae3e83ee3dc087fd99131e"}


def _qwen_tree(n, d, hq, hkv, f, bias):
    attn = {"wq": (n, d, hq, 128), "wk": (n, d, hkv, 128),
            "wv": (n, d, hkv, 128), "wo": (n, hq, 128, d)}
    if bias:
        attn.update(bq=(n, hq, 128), bk=(n, hkv, 128), bv=(n, hkv, 128))
    return {"emb": (151936, d), "final_norm": {"scale": (d,)},
            "blocks": {"attn_norm": {"scale": (n, d)},
                       "mlp_norm": {"scale": (n, d)}, "attn": attn,
                       "mlp": {"w_up": (n, d, f), "w_gate": (n, d, f),
                               "w_down": (n, f, d)}}}


PUBLISHED_TREES = {
    "sparkv-qwen3-4b": _qwen_tree(36, 2560, 32, 8, 9728, bias=False),
    "qwen2.5-3b": _qwen_tree(36, 2048, 16, 2, 11008, bias=True)}


@pytest.fixture(scope="module")
def tiny_params():
    return weights.draw(tiny.config(), 0)


def test_draw_is_bitwise_the_dense_harness(tiny_params):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tiny_params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA


@pytest.mark.parametrize("fp8", [False, True])
def test_reference_is_bitwise_the_dense_harness(tiny_params, fp8):
    conf = tiny.config()
    tokens = jnp.asarray(np.arange(48, dtype=np.int32) * 37
                         % conf["vocab_size"])
    logits = np.asarray(reference.make(conf, lo=16, n=24, fp8=fp8)(
        tiny_params, tokens))
    assert logits.shape == (24, conf["vocab_size"])
    assert logits.dtype == np.float32
    assert hashlib.sha256(logits.tobytes()).hexdigest() == \
        REFERENCE_SHA[fp8]


@pytest.mark.parametrize("name", sorted(PUBLISHED_TREES))
def test_published_trees_are_unchanged(name):
    with open(ROOT / "chipbench" / "configs" / f"{name}.json") as f:
        conf = json.load(f)
    assert "family" not in conf
    assert spec.family(conf) is spec.family({"family": "qwen"})
    assert weights.shapes(conf) == PUBLISHED_TREES[name]


# A family with a router and a share of stacked experts, per-head q/k
# norms and sliding-window layers, at a size the CPU runs in seconds
TOY = {"name": "toy-moe", "family": "toy_moe", "hidden_size": 32,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
       "num_hidden_layers": 4, "vocab_size": 256,
       "moe_intermediate_size": 16, "num_experts": 8,
       "num_local_experts": 4, "num_experts_per_tok": 2,
       "sliding_window": 4,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
       "rms_norm_eps": 1e-6, "rope_theta": 10000.0}

# run in a copy of the harness with the toy family and its configuration
# added as files: the weights, the reference (causal; with every layer
# sliding, position 13 and later no longer see token 0, since four
# layers reach back 4 x 3 positions), FLOPs and dequantized rows
TOY_SCRIPT = """
import json
import jax, numpy as np
from chipbench import flops, reference, spec, weights
conf = json.load(open("chipbench/configs/toy-moe.json"))
params = weights.draw(conf, 2**31 + 5)
out = {"leaves": {jax.tree_util.keystr(p): [list(a.shape), str(a.dtype)]
                  for p, a in jax.tree_util.tree_flatten_with_path(params)[0]},
       "shapes": {jax.tree_util.keystr(p): list(s) for p, s in
                  jax.tree_util.tree_flatten_with_path(
                      weights.shapes(conf),
                      is_leaf=lambda x: isinstance(x, tuple))[0]},
       "program_config": spec.family(conf).program_config(conf)}
a = (np.arange(24, dtype=np.int32) * 7 + 1) % conf["vocab_size"]
future, first = a.copy(), a.copy()
future[16:] = 3
first[0] = 5
def logits(c, t):
    return np.asarray(reference.make(c, lo=0, n=24)(params, t))
la, lf, l0 = (logits(conf, t) for t in (a, future, first))
sliding = dict(conf, layer_types=["sliding_attention"] * 4)
sa, s0 = logits(sliding, a), logits(sliding, first)
out["reference"] = {
    "shape": list(la.shape), "dtype": str(la.dtype),
    "finite": bool(np.isfinite(la).all()),
    "past_same": bool(np.array_equal(la[:16], lf[:16])),
    "future_moves": float(np.abs(la[16:] - lf[16:]).min(-1).max()),
    "full_layer_sees_token_0": float(np.abs(la[23] - l0[23]).max()),
    "window_hides_token_0": bool(np.array_equal(sa[13:], s0[13:])),
    "window_sees_token_0": float(np.abs(sa[12] - s0[12]).max())}
out["flops"] = {"1": flops.token_forward_flops(conf, 1),
                "100": flops.token_forward_flops(conf, 100),
                "decode": flops.decode_flops(conf, 98, 1, 2),
                "rows": flops.kv_dequant_rows(conf, streamed=3,
                                              chunk_tokens=16, group=32)}
print(json.dumps(out))
"""


def _harness_copy(tmp_path):
    """The benchmark as committed, with the toy family and two
    configurations added as files: one of the toy family, which the
    program has no model for, and one naming a family that has no
    module. Each has a cell on an existing mix."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p.relative_to(bench_dir): p.read_bytes()
              for p in bench_dir.rglob("*") if p.is_file()}
    shutil.copy(ROOT / "chipbench" / "tests" / "families" / "toy_moe.py",
                bench_dir / "families" / "toy_moe.py")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for conf in (TOY, dict(TOY, name="lost", family="no_such_family")):
        (bench_dir / "configs" / f"{conf['name']}.json").write_text(
            json.dumps(conf))
        workload = f"{conf['name']}.docqa-1k"
        (bench_dir / "limits" / f"{workload}.json").write_text(
            json.dumps({"mean_logit_gap": 1.0}))
        bench["configs"].append({
            "name": conf["name"], "source": "x", "reduced": [], "why": "x",
            "file": f"chipbench/configs/{conf['name']}.json"})
        bench["workloads"].append({"name": workload, "config": conf["name"],
                                   "traffic": "docqa-1k", "chips": 1,
                                   "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return before


def _python(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(cwd))
    t = time.perf_counter()
    p = subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    return p, time.perf_counter() - t


@pytest.fixture(scope="module")
def toy_copy(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("toy")
    before = _harness_copy(tmp)
    p, _ = _python(tmp, "-c", TOY_SCRIPT)
    assert p.returncode == 0, p.stderr[-4000:]
    return tmp, before, json.loads(p.stdout.splitlines()[-1])


def test_a_new_family_is_only_new_files(toy_copy):
    tmp, before, out = toy_copy
    bench_dir = tmp / "chipbench"
    for rel, data in before.items():
        assert (bench_dir / rel).read_bytes() == data, rel
    added = {p.relative_to(bench_dir) for p in bench_dir.rglob("*.py")} \
        - set(before)
    assert {str(p) for p in added} == {"families/toy_moe.py"}
    assert out["program_config"] is None


def test_a_new_family_draws_its_tree(toy_copy):
    out = toy_copy[2]
    assert {k: s for k, (s, _) in out["leaves"].items()} == out["shapes"]
    assert {t for _, t in out["leaves"].values()} == {"bfloat16"}
    moe = {k: s for k, s in out["shapes"].items() if "'moe'" in k}
    # the router scores all 8 experts; 4 of them are held here
    assert moe["['blocks']['moe']['router']"] == [4, 32, 8]
    assert moe["['blocks']['moe']['w_gate']"] == [4, 4, 32, 16]
    assert out["shapes"]["['blocks']['attn']['q_norm']"] == [4, 8]


def test_a_new_family_gives_its_reference(toy_copy):
    ref = toy_copy[2]["reference"]
    assert ref["shape"] == [24, 256] and ref["dtype"] == "float32"
    assert ref["finite"] and ref["past_same"]
    assert ref["future_moves"] > 0
    assert ref["full_layer_sees_token_0"] > 0
    assert ref["window_hides_token_0"]
    assert ref["window_sees_token_0"] > 0


def test_a_new_family_counts_its_flops(toy_copy):
    fl = toy_copy[2]["flops"]
    # a layer reads q/k/v/o 32 x (8 + 4) x 8 = 3072, the router 32 x 8 =
    # 256 and, of 2 experts in 8 chosen, 1 of the 4 held here on average:
    # 3 x 32 x 16 = 1536; four layers and the head 32 x 256: 27648
    # parameters. Attention per layer and key: 2 x 2 x 4 x 8 = 128; three
    # layers read at most the 4 keys of their window
    assert fl["1"] == 2 * 27648 + 4 * 128
    assert fl["100"] == 2 * 27648 + 3 * 128 * 4 + 128 * 100
    assert fl["decode"] == 2 * 2 * 27648 + 3 * 2 * 128 * 4 \
        + 128 * (100 + 101)
    # a plane of 16 tokens x 2 kv heads x 8 is 8 groups of 32
    assert fl["rows"] == 3 * 2 * 8


@pytest.mark.parametrize("workload,family", [
    ("toy-moe.docqa-1k", "toy_moe"), ("lost.docqa-1k", "no_such_family")])
def test_run_refuses_a_family_it_cannot_build(toy_copy, workload, family):
    p, seconds = _python(toy_copy[0], "chipbench/run.py", "--workload",
                         workload, "--seed", "2147483659", "--seconds", "1",
                         "--trace", "0")
    assert p.returncode == 2
    assert p.stdout == ""
    assert family in p.stderr and "weights drawn" not in p.stderr
    assert seconds < 60


@pytest.mark.parametrize("case,family", [("missing", "no_such_family"),
                                         ("no program model", "qwen")])
def test_set_up_stops_before_any_weights(monkeypatch, case, family):
    cell = tiny.cell()
    if case == "missing":
        cell.config["family"] = family
    else:
        monkeypatch.setattr(spec.family(cell.config), "program_config",
                            lambda conf: None)

    def drawn(*a, **kw):
        raise AssertionError("weights drawn")

    monkeypatch.setattr(weights, "draw", drawn)
    with pytest.raises(spec.NoFamily, match=family):
        run.run_cell(cell, seed=1, seconds=0.001, trace=False, dev=tiny.CPU,
                     log=lambda m: None)
