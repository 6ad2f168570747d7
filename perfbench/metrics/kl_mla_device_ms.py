"""Device time an iteration in the NoPE latent-attention mixer, rollout and update: the scope `mla`
(projections, the latent's norm, the output product) with `mla_attend` inside it."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, ("mla", "mla_attend"))
