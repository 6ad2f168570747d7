"""Share of the fused program's leaf-op time under none of its scopes: the guard that a
refactor did not lose the names."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.unscoped_share)
