"""The `experts` scope of the update against its roofline: the larger of its FLOPs over the
chip's bf16 peak and its bytes over the HBM bandwidth (perfbench/harness/q3n_flops.py: FLOPs
from the pairs the program counted, never from a buffer's rows; bytes from the weights held
and the rows moved), over the scope's device time in one execution. At 160 tokens an expert
the weights' bytes bound it; the time includes the recomputed forward."""

from perfbench.harness import q3n_flops, q3n_spans


def read(run):
    flops, nbytes = q3n_flops.update_experts_flops_bytes(run.model, q3n_spans.counters_of(run))
    return q3n_spans.roofline_share(run, "experts", flops, nbytes)
