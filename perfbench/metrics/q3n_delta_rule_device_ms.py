"""Device time an iteration under the `delta_rule` scope alone, rollout and update: the step
form's state update, the chunked form and its backward. A part of `q3n_linear_attn_device_ms`."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, ("delta_rule",))
