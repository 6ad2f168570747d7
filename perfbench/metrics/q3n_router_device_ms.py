"""Device time an iteration under the `router` scope: the softmax over 512, the top 10, the
sort of the pairs and the bounded dispatch's gathers in and out of its buffers."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, ("router",))
