"""Share of the train program's leaf-op time under none of `make_train_phase`'s scopes: the guard that a refactor
did not lose the names."""

from perfbench.harness import program_spans
from perfbench.harness.program_spans import from_capture


def read(run):
    return from_capture(run, program_spans.unscoped_share)
