"""Device time an iteration under the `experts` scope: the held experts' products, batched in the
rollout, grouped (the kernels of `ops/grouped_matmul.py`, at width 1024) in the update."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, ("experts",))
