"""Device time an iteration under the `update` scope: GAE, the gradient steps' forward (the band on
the sliding layers, blocked causal attention on the full ones) and backward over whole sequences,
Adam (leaf-op time, one execution)."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, None, phase="update")
