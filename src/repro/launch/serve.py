"""Serving launcher: spin up a SparKVServer, register one reusable
context, and serve requests under each loading policy, reporting host
wall times and response fidelity against the exact cache.

The model runs at its published widths with random bf16 weights drawn
from ``--seed`` (the default; a 4B model needs the TPU). ``--reduced``
shrinks it to a CPU-sized model (4 layers, d_model 64, 64-token chunks).

  PYTHONPATH=src python -m repro.launch.serve --arch sparkv-qwen3-4b \
      --context-chunks 4 --policies sparkv,cachegen
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced
"""
from __future__ import annotations

import argparse


def build_model_params(arch: str, *, reduced: bool, seed: int):
    """(model, params): the published config, or its CPU-sized shrink."""
    import jax
    from repro.configs import get_config, get_smoke
    from repro.models import build_model

    cfg = (get_smoke(arch, layers=4, d_model=64, heads=4, d_ff=128,
                     vocab=512) if reduced else get_config(arch))
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def sparkv_config(*, reduced: bool, **overrides):
    """The paper's SparKVConfig (1024-token chunks, 5-bit, group 64), or
    the chunking that fits the reduced model."""
    from repro.configs import SparKVConfig

    small = dict(chunk_tokens=64, q_block=32, kv_block=32, quant_group=32)
    return SparKVConfig(**({**small, **overrides} if reduced else overrides))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sparkv-qwen3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized model instead of the published widths")
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--context-chunks", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--policies",
                    default="sparkv,strong_hybrid,cachegen,local_prefill")
    ap.add_argument("--profile", default="jetson-orin")
    ap.add_argument("--network", default="campus-wifi")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import numpy as np
    from repro.launch.compile_cache import setup_compile_cache
    from repro.serving.engine import SparKVServer

    setup_compile_cache()
    model, params = build_model_params(args.arch, reduced=args.reduced,
                                       seed=args.seed)
    cfg = model.cfg
    spcfg = sparkv_config(reduced=args.reduced)
    srv = SparKVServer(model, params, spcfg, profile=args.profile,
                       network=args.network, seed=args.seed)

    rng = np.random.default_rng(args.seed)
    ctx = rng.integers(0, cfg.vocab_size,
                       size=(1, args.context_chunks * spcfg.chunk_tokens))
    cid = srv.register_context(ctx)
    print(f"registered context {cid}: {ctx.shape[1]} tokens, "
          f"{srv.contexts[cid].n_chunks} chunks, "
          f"{srv.contexts[cid].wl.total_bytes() / 1e6:.2f} MB compressed")

    for policy in args.policies.split(","):
        res = [srv.generate(cid, rng.integers(0, cfg.vocab_size, size=4),
                            max_new=args.max_new, policy=policy,
                            seed=args.seed + r)
               for r in range(args.requests)]
        m = {f: np.mean([getattr(r, f) for r in res]) for f in
             ("load_wall_s", "decode_wall_s", "ttft_s", "top1_agreement",
              "mean_kl")}
        print(f"{policy:14s} host load={m['load_wall_s']:7.3f}s  "
              f"host decode={m['decode_wall_s']:7.3f}s  "
              f"planned TTFT (virtual clock)={m['ttft_s']:7.3f}s  "
              f"top1-fidelity={m['top1_agreement']:.3f}  "
              f"KL={m['mean_kl']:.4f}")


if __name__ == "__main__":
    main()
