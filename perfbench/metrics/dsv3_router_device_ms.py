"""Device time an iteration under the `router` scope: the sigmoid scores over 64, the top 6 of `s + b`,
the sort of the pairs and the bounded dispatch's gathers in and out of its buffers."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, ("router",))
