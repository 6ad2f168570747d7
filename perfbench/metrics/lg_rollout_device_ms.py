"""Device time an iteration under the `rollout` scope of the fused program on the `laguna` trunk:
2,048 decode steps of batch 32 through three rings of 512 rows and two KV caches that grow to 2,048
(leaf-op time, one execution)."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, None, phase="rollout")
