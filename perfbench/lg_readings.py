"""Readings for the limits of a sequence-policy configuration on the `laguna` trunk:
`python3 perfbench/lg_readings.py --workload laguna_xs2_ep16.ppo_32x2048 --seeds 1,2,3
[--seconds 1] [--control matmul] [--fault no_window,top7] [--out <file>]`. It is `q3n_readings.py`
(the cell's set-up and a short window, each reading's compared numbers as a JSON line; `--control
matmul` for float32 with matmuls in one bf16 pass; `--fault` for the faults it names, one after the
other) with this trunk's faults, perfbench/harness/lg_faults.py, in the place of that trunk's: a
further instance of that file, so the script is kept in one place. The compared numbers are all of
the FIRST fused call, so a reading warms one cycle before its window and not the traffic's three: an
iteration of this cell is some 18.5 s. Each reading runs in a process of its own, one after the
other: on the chip, readings after the first in one process came out with a reference gradient that
was not finite, where the same readings each first in a process were finite (PERF.md, PR 46). Not
part of a benchmark run. PERF.md says how the limits in the configuration's file were set from these."""

import argparse
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.harness import bench, lg_faults  # noqa: E402

readings = bench.load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)), "q3n_readings.py"))
readings.q3n_faults = lg_faults  # the name its `main` looks the faults up by
_load_cell = bench.load_cell


def _one_warm_cycle(workload, root=bench.ROOT):
    data = _load_cell(workload, root)
    data["traffic"] = {**data["traffic"], "warmup_cycles": 1}
    return data


def one_reading_each(argv):
    """The command lines of one reading each that ``argv`` asks for, in its order (fault by fault,
    seed by seed); ``argv`` itself where it asks for one."""
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--fault", default=None)
    known, rest = parser.parse_known_args(argv)
    seeds, faults = known.seeds.split(","), known.fault.split(",") if known.fault else [None]
    if len(seeds) * len(faults) == 1:
        return [list(argv)]
    return [[*rest, "--seeds", seed, *(["--fault", fault] if fault else [])] for fault in faults for seed in seeds]


if __name__ == "__main__":
    lines = one_reading_each(sys.argv[1:])
    if len(lines) > 1:
        sys.exit(max(subprocess.run([sys.executable, os.path.abspath(__file__), *line]).returncode for line in lines))
    bench.load_cell = _one_warm_cycle
    sys.exit(readings.main())
