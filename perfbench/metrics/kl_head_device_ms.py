"""Device time an iteration under the `lm_head` and `value_head` scopes."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, ("lm_head", "value_head"))
