"""Device time an iteration under the `optimizer` scope (Adam over 625.7M parameters, four steps)."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, ("optimizer",))
