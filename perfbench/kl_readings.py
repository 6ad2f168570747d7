"""Readings for the limits of a sequence-policy configuration on the `kimi_linear` trunk:
`python3 perfbench/kl_readings.py --workload kimi_linear_48b_a3b_ep32.ppo_64x512 --seeds 1,2,3
[--seconds 1] [--control matmul] [--fault scalar_decay,mla_rope] [--out <file>]`. It is
`q3n_readings.py` (the cell's set-up and a short window for several seeds in one process, each
seed's compared numbers as a JSON line; `--control matmul` for float32 with matmuls in one bf16
pass; `--fault` for the faults it names, one after the other) with this trunk's faults,
perfbench/harness/kl_faults.py, in the place of that trunk's: a further instance of that
file, so the script is kept in one place. Not part of a benchmark run. PERF.md says how the
limits in the configuration's file were set from these."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import bench, kl_faults  # noqa: E402

readings = bench.load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)), "q3n_readings.py"))
readings.q3n_faults = kl_faults  # the name its `main` looks the faults up by

if __name__ == "__main__":
    sys.exit(readings.main())
