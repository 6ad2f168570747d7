"""Device time an iteration under the `shared_expert` scope: the one ungated shared expert of width 512,
which every token takes."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, ("shared_expert",))
