"""Device time an iteration under the `rollout` scope of the fused program on the `deepseek_v3` trunk:
512 decode steps of batch 64 through six latent caches in the absorbed form (leaf-op time, one execution)."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, None, phase="rollout")
