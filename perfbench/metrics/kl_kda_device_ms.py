"""Device time an iteration in the Kimi delta attention mixers, rollout and update: the scope `kda`
(projections, the three convolutions, the decay and the gate, the output norm and product) with
`kda_rule` inside it."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, ("kda", "kda_rule"))
