"""The `experts` scope of the update against its roofline: the larger of its FLOPs over the
chip's bf16 peak and its bytes over the HBM bandwidth (perfbench/harness/lm_flops.py: FLOPs
from the pairs the program counted, never from a padded buffer; bytes from the weights held
and the rows moved), over the scope's device time in one execution. float32 products cannot
pass about a third of the bf16 peak, and the time includes the recomputed forward."""

from perfbench.harness import lm_flops, lm_spans


def read(run):
    ms = lm_spans.from_capture(run, lm_spans.part_ms, ("experts",), phase="update")
    if not ms or not run.peaks:
        return None
    flops, nbytes = lm_flops.update_experts_flops_bytes(run.model, lm_spans.counters_of(run))
    least = max(flops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * ms)
