"""The `experts` scope of the update against its roofline: the larger of its FLOPs over the chip's
bf16 peak and its bytes over the HBM bandwidth (perfbench/harness/dsv3_flops.py: FLOPs from the pairs
the program counted, never from a buffer's rows; bytes from the weights held and the rows moved), over
the scope's device time in one execution. At 768 tokens an expert the weights' bytes (35 ms an iteration
at uniform routing) and the FLOPs (32 ms) bound it about alike; float32 products at three bf16 passes
cannot pass a third of the bf16 peak, and the time includes the recomputed forward."""

from perfbench.harness import dsv3_flops, dsv3_spans


def read(run):
    flops, nbytes = dsv3_flops.update_experts_flops_bytes(run.model, dsv3_spans.counters_of(run))
    return dsv3_spans.roofline_share(run, "experts", "update", flops, nbytes)
