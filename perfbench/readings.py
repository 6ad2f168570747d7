"""Readings for a limit: `python3 perfbench/readings.py --workload <name> --seeds 1,2,3
[--seconds 1] [--control bf16|matmul] [--fault half_batch] [--out <file>]` runs the
cell's set-up and a short window for several seeds in one process and writes each
seed's compared numbers as a JSON line. `--control bf16` runs the program's own
bfloat16 path (`fabric.precision=bf16-mixed`: bfloat16 compute, float32 parameters),
`--control matmul` the step between (float32 with matmuls in one bf16 pass,
`float32_matmul_precision=default`, against three at `high`); `--fault` plants a fault
in the train call (perfbench/harness/faults.py). Not part of a benchmark run: PERF.md says how the
limits in the configuration files were set from these."""

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import bench, faults  # noqa: E402


CONTROLS = {"bf16": "fabric.precision=bf16-mixed", "matmul": "float32_matmul_precision=default"}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--control", choices=sorted(CONTROLS))
    parser.add_argument("--fault", choices=faults.KINDS)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    kind = f"control_{args.control}" if args.control else (args.fault or "program")
    out = args.out or os.path.join("chiprun_out", "readings", f"{args.workload}.{kind}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    extra = [CONTROLS[args.control]] if args.control else []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        with faults.planted(args.fault) if args.fault else contextlib.nullcontext():
            result = bench.run_cell(args.workload, seed, args.seconds, False, extra_overrides=extra, t_start=t0)
        line = {"workload": args.workload, "kind": kind, "seed": seed, "correct": result["correct"],
                "compared": {k: v["value"] for k, v in result["compared"].items()},
                "metrics": {k: v["value"] for k, v in result["metrics"].items()}}
        print(json.dumps(line), flush=True)
        with open(out, "a") as fh:
            fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
