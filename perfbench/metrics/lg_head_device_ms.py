"""Device time an iteration under the `lm_head` and `value_head` scopes."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, ("lm_head", "value_head"))
