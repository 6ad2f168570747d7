"""Device time an iteration under the `attention` scope (gated softmax attention: the KV cache
in the rollout, whole sequences in the update)."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, ("attention",))
