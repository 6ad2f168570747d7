"""Device time an iteration under the `dense_ffn` scope (the leading layer's SwiGLU of
width `intermediate_size`, every token), rollout and update, the recomputed forward and
the backward pass with it."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.part_ms, ("dense_ffn",))
