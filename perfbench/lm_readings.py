"""Readings for the limits of a sequence-policy configuration: `python3 perfbench/lm_readings.py
--workload lfm2_8b_a1b_ep4.ppo_64x256 --seeds 1,2,3 [--seconds 1] [--control matmul]
[--fault top3,softmax_scores] [--out <file>]` runs the cell's set-up and a short window for
several seeds in one process and writes each seed's compared numbers as a JSON line.
`--control matmul` runs float32 with matmuls in one bf16 pass
(`float32_matmul_precision=default`, against three at `high`); `--fault` plants the faults
of perfbench/harness/lm_faults.py it names, one after the other, each over every seed. As
`readings.py` does for the Dreamer-V3 cells; not part of a benchmark run. PERF.md says how
the limits in the configuration's file were set from these."""

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import bench, lm_faults  # noqa: E402

CONTROLS = {"matmul": "float32_matmul_precision=default"}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--control", choices=sorted(CONTROLS))
    parser.add_argument("--fault", default=None, help=f"one or more of {lm_faults.KINDS}, comma-separated")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    faults = args.fault.split(",") if args.fault else [None]
    if args.control and args.fault:
        parser.error("--control and --fault are separate readings")
    if set(faults) - {None, *lm_faults.KINDS}:
        parser.error(f"--fault takes {lm_faults.KINDS}")
    extra = [CONTROLS[args.control]] if args.control else []
    for fault in faults:
        kind = f"control_{args.control}" if args.control else (fault or "program")
        out = args.out or os.path.join("chiprun_out", "readings", f"{args.workload}.{kind}.jsonl")
        os.makedirs(os.path.dirname(out), exist_ok=True)
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            with lm_faults.planted(fault) if fault else contextlib.nullcontext():
                result = bench.run_cell(args.workload, seed, args.seconds, False, extra_overrides=extra, t_start=t0)
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
