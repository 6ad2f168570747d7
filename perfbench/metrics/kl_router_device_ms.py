"""Device time an iteration under the `router` scope: the sigmoid scores over 256, the top 8 of `s + b`,
the sort of the pairs and the bounded dispatch's gathers in and out of its buffers."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, ("router",))
