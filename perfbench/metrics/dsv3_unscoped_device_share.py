"""Share of the fused program's leaf-op time under none of its scopes: the guard that a
refactor did not lose the names."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.unscoped_share)
