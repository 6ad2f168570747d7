"""Device time of one gradient step under the `actor` and `critic` scopes of `make_train_phase`: leaf-op time of the
capture's ops whose name stack holds either, forward and backward."""

from perfbench.harness import program_spans
from perfbench.harness.program_spans import from_capture


def read(run):
    return from_capture(run, program_spans.part_ms, "actor", "critic")
