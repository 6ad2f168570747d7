"""Device time an iteration under the `short_conv` and `attention` scopes (the two kinds of
token mixer, each with its own state in the rollout), rollout and update."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.part_ms, ("short_conv", "attention"))
