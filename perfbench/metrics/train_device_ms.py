"""Device time of one execution of the fused `train_step` program: the mean length of
its events on the capture's `XLA Modules` line (whole executions inside the traced
window only)."""

from perfbench.harness.capture import module_mean_s


def read(run):
    seconds = module_mean_s(run.capture, "train_step")
    return None if seconds is None else 1e3 * seconds
