"""Device time an iteration under the `experts` scope: the 16 held experts' products, batched in the
rollout, grouped (the kernels of `ops/grouped_matmul.py`, at width 512) in the update."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, ("experts",))
