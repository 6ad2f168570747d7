"""Device time an iteration under the `swa_attend` scope alone, rollout and update: the ring's write and
read of 512 rows at most in a decode step, the band over whole sequences and its backward pass. A part
of `lg_swa_device_ms`."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, ("swa_attend",))
