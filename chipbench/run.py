"""Run one cell of BENCHMARK.json on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up draws the weights from the seed on the device, registers the
mix's document (the cloud side: prefill, quantization, Huffman encoding)
and serves one warm-up request, so every shape the window uses is
compiled before it opens. The window then serves requests back to back
from one client until ``--seconds`` have passed. After it, the sampled
requests are checked against the plain reference.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, ``readings`` (every number the check computes, compared or
not), and last ``checks``: each number compared with its limit, which
also end stderr. Exits non-zero and prints no result where the
configuration's model family has no module or no model in the program
(before JAX looks for a chip), or where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # the checkout's root, not this directory, heads the path, so the
    # benchmark's modules import as ``chipbench.*`` and shadow nothing
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from chipbench import check, device, generator, serve, spec  # noqa: E402
from chipbench import trace as tracing  # noqa: E402
from chipbench import weights  # noqa: E402
from chipbench.peaks import peaks  # noqa: E402

CHECK_SAMPLE = 12       # requests compared with the reference, at most


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _quantile(xs: list, q: float) -> float:
    """The q-quantile, interpolated between order statistics."""
    if len(xs) == 1:
        return xs[0]
    return statistics.quantiles(xs, n=100, method="inclusive")[
        round(q * 100) - 1]


def end_to_end(served: list, setup_s: float) -> dict:
    ttft = [r.ttft_s for r in served]
    decode_s = sum(r.t_end - r.t_first for r in served)
    return {"ttft_p50_s": statistics.median(ttft),
            "ttft_p95_s": _quantile(ttft, 0.95),
            "tpot_ms": 1e3 * decode_s / sum(r.max_new for r in served),
            "setup_s": setup_s}


def _start_trace(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             dev: dict, log=_log, control: bool = False,
             t_start: float = T_START) -> dict:
    """One run of a cell: set-up, window, check. Returns the result line's
    object, with every reading of the served answers beside the checks;
    with ``control`` also the float8 control's readings and its verdict
    under the same limits (``calibrate.py``; never a benchmark run)."""
    import jax

    conf, mix = cell.config, cell.traffic
    clock = device.CompileClock()

    model = weights.program_model(conf)
    params = jax.block_until_ready(weights.draw(conf, seed))
    log(f"weights drawn: {time.perf_counter() - t_start:.1f} s")
    traffic = generator.make(mix, vocab=conf["vocab_size"], seed=seed)
    srv, steps = serve.build_server(conf, model, params,
                                    seed=mix["link_seed"])
    t = time.perf_counter()
    cid = srv.register_context(traffic.document)
    log(f"registered {traffic.document.shape[1]} tokens: "
        f"{time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    serve.serve_one(srv, steps, cid, traffic.warmup, traffic.policy,
                    mix["link_seed"])
    log(f"warm-up request: {time.perf_counter() - t:.1f} s")
    setup_s = time.perf_counter() - t_start

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        _start_trace(log_dir)
    try:
        served, closed_s, compiles, failed = serve.window(
            srv, steps, cid, traffic, seconds=seconds,
            seed=mix["link_seed"], compile_clock=clock, log=log)
    finally:
        if trace:
            jax.profiler.stop_trace()
    summary = None
    if trace:
        summary = tracing.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
    log(f"window: {len(served)} requests in {closed_s:.1f} s, "
        f"{compiles} compiles")
    mem = device.memory_peak_bytes(cell.workload["chips"])

    win = serve.Window(config=conf, traffic=mix, served=served,
                       compiles=compiles, trace=summary,
                       peaks=peaks(dev["kind"]))
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(win)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    elif served:
        e2e = end_to_end(served, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the program's state goes before the reference runs; the weights are
    # the benchmark's own and stay for it
    answers = [r.answer() for r in served]
    mismatch = sum([int(t) for t in r.tokens[:-1]] != a[1:-1]
                   for r, a in zip(served, answers))
    picked = check.sample(served, seed, CHECK_SAMPLE)
    vocab = conf["vocab_size"]
    logits = {i: served[i].answer_logits(vocab) for i in picked}
    for r in served:
        r.logits = None
    del srv, steps, win
    gc.collect()
    judge = check.Judge(conf, mix, control=control)
    compared = [judge.compare(params, traffic.document, served[i].question,
                              answers[i], logits.pop(i)) for i in picked]
    read = check.readings(compared, "served")
    checks, correct = check.verdict(read, cell.limits,
                                    failed_requests=failed,
                                    answer_mismatch=mismatch)
    correct = correct and bool(served)
    dev_out = dict(dev, memory_peak_bytes=mem)
    if summary is not None:
        dev_out.update(busy_s=summary.busy_s, window_s=summary.window_s)
    result = {"correct": correct, "attempted": len(served) + failed,
              "failed": failed, "metrics": metrics, "device": dev_out}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    if control:
        ctl_read = check.readings(compared, "control")
        ctl_checks, ctl_correct = check.verdict(ctl_read, cell.limits)
        result["control"] = {"correct": ctl_correct, "checks": ctl_checks,
                             "readings": ctl_read}
    result["readings"] = read
    result["checks"] = checks
    return result


def _json_safe(x):
    """``x`` with every float that JSON cannot hold (a reading of a run
    that produced nothing to compare) written as a string."""
    if isinstance(x, dict):
        return {k: _json_safe(v) for k, v in x.items()}
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


def compile_cache() -> str:
    """The program's persistent compilation cache (the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``),
    keeping every program, however quick its compile."""
    import jax
    from repro.launch.compile_cache import setup_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return setup_compile_cache()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(ROOT, args.workload)
    try:
        weights.program_config(cell.config)
        dev = device.require_chips(cell.workload["chips"])
    except (spec.NoFamily, device.NoChip) as e:
        _log(f"chipbench: {e}")
        return 2
    _log(f"device {dev}, compile cache {compile_cache()}")
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), dev=dev)
    for name, v in result["readings"].items():
        _log(f"reading {name}: {v}")
    for name, c in result["checks"].items():
        _log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(_json_safe(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
