"""Device time an iteration under the `lm_head` and `value_head` scopes (the head over the
vocabulary held, and the critic), rollout and update."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.part_ms, ("lm_head", "value_head"))
