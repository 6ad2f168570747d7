"""Device time an iteration under the `rollout` scope of the fused program on the `qwen3_next` trunk:
512 decode steps of batch 64 through the three kinds of state (leaf-op time, one execution)."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, None, phase="rollout")
