"""Device time an iteration under the expert layers' `experts` scope, rollout and update: the
grouped products over the experts held and the gate between them."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.part_ms, ("experts",))
