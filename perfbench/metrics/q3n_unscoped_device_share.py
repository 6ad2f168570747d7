"""Share of the fused program's leaf-op time under none of its scopes: the guard that a
refactor did not lose the names."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.unscoped_share)
