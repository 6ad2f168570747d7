"""Device time an iteration under the `router` scope: the sigmoid scores over 256, the top 8 of `s + b`,
the sort of the pairs and the bounded dispatch's gathers in and out of its buffers."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, ("router",))
