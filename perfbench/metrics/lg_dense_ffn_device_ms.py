"""Device time an iteration under the `dense_ffn` scope: the leading layer's SwiGLU of width 8192."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, ("dense_ffn",))
