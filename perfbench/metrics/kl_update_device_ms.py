"""Device time an iteration under the `update` scope: GAE, the gradient steps' forward (the chunked rule, the
latent attention in its expanded form) and backward over whole sequences, Adam (leaf-op time, one execution)."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, None, phase="update")
