"""Device time an iteration in the latent-attention mixer, rollout and update: the scope `mla`
(projections, the latent's norm, RoPE, the output product) with `mla_attend` inside it."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, ("mla", "mla_attend"))
