"""Readings that set a cell's correctness limit, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 51

For each seed, in one process: a whole run of the cell (set-up, window,
check), and the control at the same positions: the reference in float8
(``reference.make(fp8=True)``) in the program's place, whose own first
choices and logits are judged by the float32 reference exactly as the
served ones are, under the cell's limits. Prints one JSON line per seed
with the served readings and verdict and the control's (``check.NUMBERS``).
A number's lower reading is the largest served one over a dozen seeds or
more; its upper is the smallest control one. Exits 1 where the control
comes out correct on any seed, or the served run does not. Benchmark runs
never run the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from chipbench import device, run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = spec.load_cell(ROOT, args.workload)
    try:
        dev = device.require_chips(cell.workload["chips"])
    except device.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    run.compile_cache()
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed=seed, seconds=args.seconds,
                           trace=False, dev=dev, control=True,
                           t_start=time.perf_counter())
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"], "attempted": res["attempted"],
            "control_correct": res["control"]["correct"],
            "served": res["readings"], "control": res["control"]["readings"],
            "checks": res["checks"],
            "control_checks": res["control"]["checks"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
            flush=True)
        if res["control"]["correct"] or not res["correct"]:
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
