"""Device time an iteration under the `experts` scope: the held experts' products, batched in the
rollout, grouped (the kernels of `ops/grouped_matmul.py`, at width 1408) in the update."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, ("experts",))
