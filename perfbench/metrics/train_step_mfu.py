"""The whole gradient step's share of the chip's bf16 peak: the FLOPs one step needs,
counted from shapes (perfbench/harness/flops.py), over the device time of one
`train_step` execution, over the peak in perfbench/harness/devices.py. Model FLOPs: the
three bf16 passes that a float32 matmul at `high` costs are not counted three times, so
this configuration cannot pass about a third."""

from perfbench.harness.capture import module_mean_s


def read(run):
    seconds = module_mean_s(run.capture, "train_step")
    if seconds is None or not run.peaks:
        return None
    return 100.0 * run.flops / seconds / run.peaks["bf16_flops_per_s"]
