"""Device time an iteration under the `dense_ffn` scope: the leading layer's SwiGLU of width 11264."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, ("dense_ffn",))
