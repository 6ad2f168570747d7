"""Device time an iteration in the gated delta-rule mixer, rollout and update: the scope
`linear_attention` with the `delta_rule` inside it (projections, convolution, norms, gate and the rule)."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, ("linear_attention", "delta_rule"))
