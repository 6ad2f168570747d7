"""Device time an iteration under the `shared_expert` scope: the one ungated shared expert of width 1024,
which every token takes."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, ("shared_expert",))
