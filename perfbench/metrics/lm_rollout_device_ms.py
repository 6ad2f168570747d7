"""Device time of one iteration's rollout (256 decode steps through the caches): leaf-op time
of the fused program's ops under its `rollout` scope, an execution."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.part_ms, phase="rollout")
