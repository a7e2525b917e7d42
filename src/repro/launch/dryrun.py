import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step with AdamW
update / prefill / decode serve_step), lowers it with in/out shardings on
the production mesh, compiles, and records memory_analysis, cost_analysis,
and the parsed collective schedule into a JSON file for the roofline
analysis (EXPERIMENTS.md reads these).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun [--skip-existing]
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import (ASSIGNED_ARCHS, SHAPES, TrainConfig, get_config,
                           shape_applicable)
from repro.distributed.roofline import parse_collectives, roofline_terms
from repro.distributed.sharding import make_rules, use_rules
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.training.optimizer import AdamW


# per-arch gradient-accumulation factors for train_4k (see EXPERIMENTS.md
# §Perf iteration 7): divides activation residuals + MoE dispatch buffers
MICROBATCHES = {
    "qwen3-moe-235b-a22b": 16,
    "chameleon-34b": 8,
    "granite-moe-3b-a800m": 4,
    "phi3-medium-14b": 4,
    "gemma-2b": 2,
}


def _prefill_out_axes(model):
    fam = model.cfg.family
    logits = ("batch", "act_vocab")
    ca = model.cache_axes()
    if fam in ("dense", "moe"):
        return (logits, {"k": ca["k"], "v": ca["v"]})
    if fam == "ssm":
        return (logits, ca)
    if fam == "hybrid":
        return (logits, {"ssm": ca["ssm"], "attn_k": ca["attn_k"],
                         "attn_v": ca["attn_v"]})
    if fam == "encdec":
        return {"cross_k": ca["cross_k"], "cross_v": ca["cross_v"]}
    raise ValueError(fam)


def _spec_of_axes(rules, axes_tree, shape_tree):
    return jax.tree.map(
        lambda ax, sds: rules.spec(ax, sds.shape),
        axes_tree, shape_tree,
        is_leaf=lambda x: (isinstance(x, tuple)
                           and all(isinstance(e, (str, type(None)))
                                   for e in x)))


def build_cell(arch: str, shape_name: str, mesh, *, sp_activations=None,
               attn_kv_chunk=None):
    """Returns (fn, example_args, in_shardings, out_shardings, donate)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    if sp_activations is None:
        # Megatron-SP inter-block activations by default for training:
        # layer-boundary remat residuals are L x (b,s,d) per device and do
        # not fit HBM replicated over the model axis (§Perf iteration 1).
        sp_activations = shape.kind == "train"
    # decode: weight-stationary layout — per-step FSDP weight gathers
    # dominate serve_step collectives otherwise (§Perf iteration 5)
    rules = make_rules(cfg, mesh, sp_activations=sp_activations,
                       weight_stationary=shape.kind == "decode")

    param_specs = model.param_pspecs(rules)
    abstract_params = model.abstract_params()
    inputs = model.input_specs(shape)
    input_specs = model.input_pspecs(shape, rules)
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)

    if shape.kind == "train":
        # gradient accumulation for the cells whose remat residuals +
        # MoE buffers exceed 16 GiB/chip at global batch 256 (semantics
        # preserved — equivalence tested in test_training)
        micro = MICROBATCHES.get(arch, 1)
        # each microbatch must still shard over the data axes
        n_data = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                n_data *= mesh.shape[a]
        while micro > 1 and (shape.global_batch // micro) % n_data:
            micro //= 2
        tcfg = TrainConfig(microbatches=micro)
        opt = AdamW(tcfg, cfg.moment_dtype)
        abs_opt = opt.abstract_state(abstract_params)
        opt_specs = opt.state_pspecs(param_specs)

        from repro.training.trainer import build_train_step
        train_step, opt = build_train_step(model, tcfg, rules)

        args = (abstract_params, abs_opt, inputs)
        in_sh = (ns(param_specs), ns(opt_specs), ns(input_specs))
        metric_specs = {"grad_norm": P(), "lr": P(), "loss": P()}
        out_sh = (ns(param_specs), ns(opt_specs), ns(metric_specs))
        return train_step, args, in_sh, out_sh, (0, 1)

    if shape.kind == "prefill":
        def prefill_step(params, inputs):
            with use_rules(rules):
                return model.prefill(params, inputs)

        args = (abstract_params, inputs)
        in_sh = (ns(param_specs), ns(input_specs))
        out_shapes = jax.eval_shape(prefill_step, *args)
        out_axes = _prefill_out_axes(model)
        out_sh = ns(_spec_of_axes(rules, out_axes, out_shapes))
        return prefill_step, args, in_sh, out_sh, ()

    # decode / serve_step
    def serve_step(params, cache, token, pos):
        with use_rules(rules):
            return model.decode_step(params, cache, token, pos)

    args = (abstract_params, inputs["cache"], inputs["token"], inputs["pos"])
    in_sh = (ns(param_specs), ns(input_specs["cache"]),
             ns(input_specs["token"]), NamedSharding(mesh, P()))
    logits_spec = rules.spec(("batch", "act_vocab"),
                             (shape.global_batch, cfg.padded_vocab))
    out_sh = (NamedSharding(mesh, logits_spec), ns(input_specs["cache"]))
    return serve_step, args, in_sh, out_sh, (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             *, skip_existing=False, verbose=True, sp_activations=None):
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(os.path.join(out_dir, mesh_tag), exist_ok=True)
    path = os.path.join(out_dir, mesh_tag, f"{arch}__{shape_name}.json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                 "kind": shape.kind}
    if not ok:
        rec.update(status="SKIP", reason=why)
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    try:
        t0 = time.time()
        fn, args, in_sh, out_sh, donate = build_cell(
            arch, shape_name, mesh, sp_activations=sp_activations)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = parse_collectives(hlo)
        flops = float(ca.get("flops", 0.0))
        bytes_acc = float(ca.get("bytes accessed", 0.0))
        rl = roofline_terms(flops, bytes_acc, coll)

        n_par = cfg.param_count()
        n_act = cfg.active_param_count()
        if shape.kind == "train":
            tokens = shape.global_batch * shape.seq_len
            model_flops = 6 * n_act * tokens
        elif shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
            model_flops = 2 * n_act * tokens
        else:
            model_flops = 2 * n_act * shape.global_batch

        rec.update(
            status="OK",
            chips=chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "generated_code_bytes": ma.generated_code_size_in_bytes,
                "peak_est_bytes": (ma.argument_size_in_bytes
                                   + ma.output_size_in_bytes
                                   + ma.temp_size_in_bytes),
            },
            cost={"flops_per_dev": flops, "bytes_per_dev": bytes_acc},
            collectives=coll.as_dict(),
            roofline=rl,
            model_flops=model_flops,
            useful_flops_ratio=(model_flops / (flops * chips)
                                if flops else 0.0),
            params=n_par,
            active_params=n_act,
        )
        if verbose:
            print(f"[{mesh_tag}] {arch} x {shape_name}: compile "
                  f"{t_compile:.1f}s")
            print("  memory_analysis:", rec["memory"])
            print("  cost_analysis: flops/dev=%.3e bytes/dev=%.3e"
                  % (flops, bytes_acc))
            print("  collectives:", json.dumps(coll.as_dict()["by_kind"]))
            print("  roofline:", {k: (round(v, 6) if isinstance(v, float)
                                      else v) for k, v in rl.items()})
    except Exception as e:  # noqa: BLE001 - record failures as results
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        if verbose:
            print(f"[{mesh_tag}] {arch} x {shape_name}: FAIL {e}")

    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--sp-activations", default="auto",
                    choices=["auto", "on", "off"],
                    help="Megatron-SP inter-block activations "
                         "(auto = on for train shapes)")
    args = ap.parse_args()
    sp = None if args.sp_activations == "auto" else args.sp_activations == "on"

    archs = ASSIGNED_ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, multi, args.out,
                               skip_existing=args.skip_existing,
                               sp_activations=sp)
                st = rec["status"]
                n_ok += st == "OK"
                n_fail += st == "FAIL"
                n_skip += st == "SKIP"
    print(f"\ndry-run complete: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
