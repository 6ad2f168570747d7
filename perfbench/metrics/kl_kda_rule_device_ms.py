"""Device time an iteration under the `kda_rule` scope alone, rollout and update: the step form's state
update (on the chip the delta-rule decode kernel, its decay a key channel) and the chunked form with its
backward pass. A part of `kl_kda_device_ms`."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, ("kda_rule",))
