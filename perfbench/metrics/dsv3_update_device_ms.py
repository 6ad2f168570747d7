"""Device time an iteration under the `update` scope: GAE, the gradient steps' forward (latent attention
in its expanded form) and backward over whole sequences, Adam (leaf-op time, one execution)."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, None, phase="update")
