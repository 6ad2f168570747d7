"""Share of the fused program's leaf-op time under none of its scopes: the guard that a
refactor did not lose the names."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.unscoped_share)
