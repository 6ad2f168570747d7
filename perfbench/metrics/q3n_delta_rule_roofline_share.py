"""The `delta_rule` scope of the update against its roofline: the larger of its FLOPs over the
chip's bf16 peak and its bytes over the HBM bandwidth (perfbench/harness/q3n_flops.py: the
recurrent form's three products a token a head and a token's operands in and out, never the
chunking's own work), over the scope's device time in one execution. The time includes the
recomputed forward and everything the chunked form adds (the triangular solves, the chunk
states), which is what the share is there to show."""

from perfbench.harness import q3n_flops, q3n_spans


def read(run):
    return q3n_spans.roofline_share(run, "delta_rule", *q3n_flops.update_delta_rule_flops_bytes(run.model))
