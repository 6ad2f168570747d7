"""Device-idle time while the loop thread is inside `loop_tail` (telemetry, resilience, logging, checkpoint),
as a share of the traced whole cycles."""

from perfbench.harness.host_idle import share


def read(run):
    return share(run, "loop_tail")
