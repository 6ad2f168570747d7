"""Device time an iteration under the `mla_attend` scope alone, rollout and update: scores, softmax and
weighted sum; in the step form also the latent cache's write and the two absorbed products. A part of
`dsv3_mla_device_ms`."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, ("mla_attend",))
