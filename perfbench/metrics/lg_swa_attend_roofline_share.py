"""The `swa_attend` scope, rollout and update, against its roofline: the larger of its FLOPs over the
chip's bf16 peak and its bytes over the HBM bandwidth (perfbench/harness/lg_flops.py: the scores and the
weighted values over each query's window, and the query, the window's keys and values and the output
moved, whatever form computes them), over the scope's device time in one execution. The decode steps'
reads of the rings bound it: what a better ring read or band could win."""

from perfbench.harness import lg_flops, lg_spans


def read(run):
    return lg_spans.roofline_share(run, "swa_attend", None, *lg_flops.swa_attend_flops_bytes(run.model))
