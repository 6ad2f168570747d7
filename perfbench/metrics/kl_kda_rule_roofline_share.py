"""The `kda_rule` scope of the UPDATE against its roofline: the larger of its FLOPs over the chip's
bf16 peak and its bytes over the HBM bandwidth (perfbench/harness/kl_flops.py: the recurrent form's three
products a token a head, forward and backward, and a token's q, k, g, v and beta in and its output out;
the chunking's own work is not counted), over the scope's device time in one execution: what a better
implementation of the chunked rule could win."""

from perfbench.harness import kl_flops, kl_spans


def read(run):
    return kl_spans.roofline_share(run, "kda_rule", "update", *kl_flops.update_kda_rule_flops_bytes(run.model))
