"""Smoke run of the SparKV served path on one TPU chip.

Drives the normal entry points once at the published widths of
sparkv-qwen3-4b with random bf16 weights drawn from ``--seed``:
``register_context`` of a 4096-token context and ``generate()`` under the
sparkv loading policy (Huffman decode, then one compiled ``kv_dequant``
launch into the cache, then greedy decode); then a 1024-token context
registered with per-chunk bit widths (``alloc_schedule="attention"``),
served under cachegen (every chunk streamed) and sparkv, so one launch
dequantizes planes of several widths.

Checks: ``kv_dequant`` compiles to a TPU custom call and matches the
numpy dequantizer on a real chunk, and a launch over 3-, 5- and 8-bit
planes equals one launch per plane (the load itself asserts that every
Huffman-decoded plane equals its quantized codes); every request streams
chunks, loads every chunk, and decodes tokens in the vocabulary with a
finite KL against decoding over the exact cache. Top-1 agreement is
printed, not bounded: with random weights the logits are near-tied, and
one flipped token changes every later step's input.

Phase times are host-clock wall seconds of work that has finished on the
device. ``ServeResult.ttft_s`` is the planner's virtual-clock estimate and
is not printed. Exits non-zero, printing no result, unless the backend is
a TPU. The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--seed 0] [--context-tokens 4096]
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ARCH = "sparkv-qwen3-4b"


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own compile events."""

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def _dequant_parity(srv, cid, *, interpret: bool, log) -> None:
    """The dequant kernel on a real registered chunk: equal to numpy
    dequantize; one launch over planes of three widths equal to one
    launch per plane; and, when compiled, lowered to a TPU custom call."""
    import jax.numpy as jnp
    import numpy as np
    from repro.compression.quantize import dequantize, quantize
    from repro.core.chunks import Chunk
    from repro.kernels.kv_dequant.kernel import kv_dequant
    from repro.kernels.kv_dequant.ops import (dequantize_chunk,
                                              dequantize_chunks,
                                              group_rows)

    st = srv.contexts[cid]
    _, _, qk, _ = st.encoded[Chunk(0, 0, 0)]
    one = dequantize_chunk(qk, interpret=interpret, out_dtype=jnp.float32)
    _check(np.allclose(one, dequantize(qk), atol=1e-5, rtol=0),
           "kv_dequant vs numpy dequantize")
    kc = st.exact_k[0, 0, :srv.chunk_tokens]
    qts = [quantize(kc, b, srv.spcfg.quant_group) for b in (3, 5, 8)]
    mixed = dequantize_chunks(qts, interpret=interpret,
                              out_dtype=jnp.float32)
    for qt, m in zip(qts, mixed):
        single = dequantize_chunk(qt, interpret=interpret,
                                  out_dtype=jnp.float32)
        _check(np.array_equal(m, single),
               f"mixed-width launch == per-plane launch at {qt.bits} bits")
        _check(np.allclose(m, dequantize(qt), atol=1e-5, rtol=0),
               f"mixed-width launch vs numpy at {qt.bits} bits")
    rows = group_rows(qk)
    col = np.zeros((rows.shape[0], 1), np.float32)
    text = kv_dequant.lower(rows, col, col, interpret=interpret,
                            out_dtype=jnp.float32).compile().as_text()
    custom = "tpu_custom_call" in text
    log({"phase": "dequant_kernel", "kernel": "kv_dequant",
         "rows": int(rows.shape[0]), "group": int(rows.shape[1]),
         "interpret": interpret, "tpu_custom_call": custom,
         "parity_bits": [qt.bits for qt in qts]})
    _check(interpret or custom, "kv_dequant compiled to a tpu_custom_call")


def _serve(srv, cid, policy, *, seed, max_new, vocab, clock, log) -> None:
    import numpy as np

    prompt = np.random.default_rng(seed).integers(0, vocab, size=4)
    c0 = clock.seconds
    res = srv.generate(cid, prompt, max_new=max_new, policy=policy,
                       seed=seed)
    n_chunks = srv.contexts[cid].n_chunks
    log({"phase": "request", "context": cid, "policy": policy,
         "alloc_schedule": srv.spcfg.alloc_schedule,
         "host_load_s": res.load_wall_s, "host_decode_s": res.decode_wall_s,
         "host_request_s": res.wall_s, "compile_s": clock.seconds - c0,
         "n_streamed": res.n_streamed,
         "n_computed": res.n_computed,
         "top1_agreement": res.top1_agreement, "mean_kl": res.mean_kl,
         "tokens": [int(t) for t in res.tokens]})
    _check(res.n_streamed > 0, f"{policy} streamed no chunk")
    _check(res.n_streamed + res.n_computed == n_chunks,
           f"{policy} loaded every chunk")
    _check(len(res.tokens) == max_new and all(0 <= t < vocab
                                              for t in res.tokens),
           f"{policy} tokens in the vocabulary")
    _check(math.isfinite(res.mean_kl) and res.mean_kl >= -1e-4,
           f"{policy} mean KL {res.mean_kl} finite and >= 0")
    _check(0.0 <= res.top1_agreement <= 1.0,
           f"{policy} top-1 agreement {res.top1_agreement} in [0, 1]")


def run(*, reduced: bool, interpret: bool, seed: int, context_tokens: int,
        mixed_tokens: int, max_new: int, log=print) -> None:
    """Every phase of the smoke, in this process. Raises on a failed
    check; ``log`` receives one dict per finished phase."""
    import jax
    import numpy as np
    from repro.launch.serve import build_model_params, sparkv_config
    from repro.serving.engine import SparKVServer

    clock = CompileClock()

    def timed(phase, fn, **extra):
        c0 = clock.seconds
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        log({"phase": phase, "host_wall_s": time.perf_counter() - t0,
             "compile_s": clock.seconds - c0, **extra})
        return out

    model, params = timed("init_params", lambda: build_model_params(
        ARCH, reduced=reduced, seed=seed))
    cfg = model.cfg
    log({"phase": "config", "arch": cfg.name, "layers": cfg.num_layers,
         "d_model": cfg.d_model, "heads": cfg.num_heads,
         "kv_heads": cfg.num_kv_heads, "head_dim": cfg.resolved_head_dim,
         "vocab": cfg.vocab_size,
         "params": int(sum(x.size for x in jax.tree.leaves(params)))})
    rng = np.random.default_rng(seed)

    srv = SparKVServer(model, params, sparkv_config(reduced=reduced),
                       seed=seed, interpret=interpret)
    ctx = rng.integers(0, cfg.vocab_size, size=(1, context_tokens))
    cid = timed("register", lambda: srv.register_context(ctx),
                tokens=context_tokens)
    _dequant_parity(srv, cid, interpret=interpret, log=log)
    _serve(srv, cid, "sparkv", seed=seed, max_new=max_new,
           vocab=cfg.vocab_size, clock=clock, log=log)

    # per-chunk widths: cachegen streams every chunk, so its load is one
    # launch over planes of every width the allocation chose
    srv_mixed = SparKVServer(
        model, params,
        sparkv_config(reduced=reduced, alloc_schedule="attention"),
        seed=seed, interpret=interpret)
    ctx = rng.integers(0, cfg.vocab_size, size=(1, mixed_tokens))
    cid = timed("register", lambda: srv_mixed.register_context(ctx),
                tokens=mixed_tokens, alloc_schedule="attention")
    widths = sorted({q.bits for enc in srv_mixed.contexts[cid].encoded
                     .values() for q in enc[2:]})
    log({"phase": "allocation", "bit_widths": widths})
    _check(len(widths) > 1, "attention allocation gives mixed widths")
    for i, policy in enumerate(("cachegen", "sparkv")):
        _serve(srv_mixed, cid, policy, seed=seed + 1 + i, max_new=max_new,
               vocab=cfg.vocab_size, clock=clock, log=log)

    stats = jax.devices()[0].memory_stats() or {}
    log({"phase": "memory",
         "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
         "bytes_limit": stats.get("bytes_limit")})
    log({"phase": "compile", "compile_s": clock.seconds,
         "compiles": clock.count})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--context-tokens", type=int, default=4096)
    ap.add_argument("--mixed-tokens", type=int, default=1024)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import setup_compile_cache

    def log(rec):
        print(json.dumps(rec), flush=True)

    log({"phase": "device", **device,
         "compile_cache": setup_compile_cache()})
    run(reduced=False, interpret=False, seed=args.seed,
        context_tokens=args.context_tokens, mixed_tokens=args.mixed_tokens,
        max_new=args.max_new, log=log)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
