"""Device time an iteration under the `experts` scope: the held experts' products, batched in
the rollout, grouped (the kernels of `ops/grouped_matmul.py`) in the update."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, ("experts",))
