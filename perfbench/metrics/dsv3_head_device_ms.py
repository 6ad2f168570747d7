"""Device time an iteration under the `lm_head` and `value_head` scopes."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, ("lm_head", "value_head"))
