"""Device time an iteration under the `shared_expert` scope: the SwiGLU every token takes and its gate."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, ("shared_expert",))
