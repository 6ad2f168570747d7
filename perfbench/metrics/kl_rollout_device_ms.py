"""Device time an iteration under the `rollout` scope of the fused program on the `kimi_linear` trunk:
512 decode steps of batch 64 through four KDA matrix states and one latent cache (leaf-op time, one execution)."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, None, phase="rollout")
