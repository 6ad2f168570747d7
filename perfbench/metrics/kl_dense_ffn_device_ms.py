"""Device time an iteration under the `dense_ffn` scope: the leading layer's SwiGLU of width 9216."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, ("dense_ffn",))
