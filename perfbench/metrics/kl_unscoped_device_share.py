"""Share of the fused program's leaf-op time under none of its scopes: the guard that a
refactor did not lose the names."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.unscoped_share)
