"""The `mla_attend` scope of the ROLLOUT against its roofline: the larger of its FLOPs over the chip's
bf16 peak and its bytes over the HBM bandwidth (perfbench/harness/dsv3_flops.py: the absorbed form's
products over the latent rows written so far, `W_kvb` once a step, those rows read once and one row
written; never the whole buffer, nor a copy of it), over the scope's device time in one execution. The
bytes bound it; what the time holds beyond them (a whole block of 128 rows where the position has filled a
part of it, a second pass over a block's rows, whatever the cache's one-row write copies) is what the
share is there to show."""

from perfbench.harness import dsv3_flops, dsv3_spans


def read(run):
    return dsv3_spans.roofline_share(run, "mla_attend", "rollout", *dsv3_flops.rollout_mla_attend_flops_bytes(run.model))
