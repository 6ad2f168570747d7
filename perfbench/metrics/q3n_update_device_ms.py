"""Device time an iteration under the `update` scope: GAE, the gradient steps' forward (chunked)
and backward over whole sequences, Adam (leaf-op time, one execution)."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, None, phase="update")
