"""Device time an iteration under the `optimizer` scope of the fused program's update: Adam
over every parameter, once a gradient step."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.part_ms, ("optimizer",))
