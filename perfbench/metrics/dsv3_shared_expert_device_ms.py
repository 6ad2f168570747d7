"""Device time an iteration under the `shared_expert` scope: the two shared experts' one ungated SwiGLU
of width 2816, which every token takes."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, ("shared_expert",))
