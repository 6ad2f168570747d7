"""The `experts` scope of the update against its roofline: the larger of its FLOPs over the chip's
bf16 peak and its bytes over the HBM bandwidth (perfbench/harness/lg_flops.py: FLOPs from the pairs the
program counted, never from a buffer's rows; bytes from the weights held and the rows moved), over the
scope's device time in one execution. At 256 pairs an expert a step the held weights' bytes bound it."""

from perfbench.harness import lg_flops, lg_spans


def read(run):
    flops, nbytes = lg_flops.update_experts_flops_bytes(run.model, lg_spans.counters_of(run))
    return lg_spans.roofline_share(run, "experts", "update", flops, nbytes)
