"""Device time an iteration under the `lm_head` and `value_head` scopes."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, ("lm_head", "value_head"))
