"""Device time an iteration under the `optimizer` scope (Adam over 668.9M parameters, four steps)."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, ("optimizer",))
