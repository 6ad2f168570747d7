"""Device time an iteration in the sliding-window attention layers, rollout and update: the scope `swa`
(projections, rotation, the gate a head, the output product) with `swa_attend` inside it."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, ("swa", "swa_attend"))
