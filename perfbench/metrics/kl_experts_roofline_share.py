"""The `experts` scope of the update against its roofline: the larger of its FLOPs over the chip's
bf16 peak and its bytes over the HBM bandwidth (perfbench/harness/kl_flops.py: FLOPs from the pairs the
program counted, never from a buffer's rows; bytes from the weights held and the rows moved), over the
scope's device time in one execution. At 256 pairs an expert a step the held weights' bytes bound it."""

from perfbench.harness import kl_flops, kl_spans


def read(run):
    flops, nbytes = kl_flops.update_experts_flops_bytes(run.model, kl_spans.counters_of(run))
    return kl_spans.roofline_share(run, "experts", "update", flops, nbytes)
