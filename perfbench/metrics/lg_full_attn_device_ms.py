"""Device time an iteration in the two full-attention layers (YaRN, 48 query heads), rollout and update:
the scope `full_attn` (projections, rotation, the gate a head, the output product) with `attend` inside
it (the KV cache's write and read of up to 2,048 rows, the blocked causal attention and its backward)."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, ("full_attn", "attend"))
