"""Device time of one iteration's update (GAE and the gradient steps over whole sequences):
leaf-op time of the fused program's ops under its `update` scope, an execution."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.part_ms, phase="update")
