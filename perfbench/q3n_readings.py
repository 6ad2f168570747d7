"""Readings for the limits of a sequence-policy configuration on the `qwen3_next` trunk:
`python3 perfbench/q3n_readings.py --workload qwen3_next_80b_a3b_ep16.ppo_64x512 --seeds 1,2,3
[--seconds 1] [--control matmul] [--fault top9,no_decay] [--out <file>]` runs the cell's
set-up and a short window for several seeds in one process and writes each seed's compared
numbers as a JSON line. `--control matmul` runs float32 with matmuls in one bf16 pass
(`float32_matmul_precision=default`, against three at `high`); `--fault` plants the faults
of perfbench/harness/q3n_faults.py it names, one after the other, each over every seed; a
run that raises (a fault the compiler refuses, say) is written down as such and the next
goes on. As `lm_readings.py` does for the LFM2 cell, with this trunk's faults; not part of
a benchmark run. PERF.md says how the limits in the configuration's file were set from these."""

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import bench  # noqa: E402
from perfbench.harness import q3n_faults  # noqa: E402

CONTROLS = {"matmul": "float32_matmul_precision=default"}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--control", choices=sorted(CONTROLS))
    parser.add_argument("--fault", default=None, help=f"one or more of {q3n_faults.KINDS}, comma-separated")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    faults = args.fault.split(",") if args.fault else [None]
    if args.control and args.fault:
        parser.error("--control and --fault are separate readings")
    if set(faults) - {None, *q3n_faults.KINDS}:
        parser.error(f"--fault takes {q3n_faults.KINDS}")
    extra = [CONTROLS[args.control]] if args.control else []
    for fault in faults:
        kind = f"control_{args.control}" if args.control else (fault or "program")
        out = args.out or os.path.join("chiprun_out", "readings", f"{args.workload}.{kind}.jsonl")
        os.makedirs(os.path.dirname(out), exist_ok=True)
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            try:
                with q3n_faults.planted(fault) if fault else contextlib.nullcontext():
                    result = bench.run_cell(args.workload, seed, args.seconds, False, extra_overrides=extra, t_start=t0)
            except (Exception, SystemExit) as err:  # noqa: BLE001  (the next reading is worth having)
                traceback.print_exc()
                line = {"workload": args.workload, "kind": kind, "seed": seed, "raised": f"{type(err).__name__}: {err}"[:2000]}
                print(json.dumps(line), flush=True)
                with open(out, "a") as fh:
                    fh.write(json.dumps(line) + "\n")
                continue
            line = {"workload": args.workload, "kind": kind, "seed": seed, "correct": result["correct"],
                    "compared": {k: v["value"] for k, v in result["compared"].items()},
                    "metrics": {k: v["value"] for k, v in result["metrics"].items()},
                    "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
            print(json.dumps(line), flush=True)
            with open(out, "a") as fh:
                fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
