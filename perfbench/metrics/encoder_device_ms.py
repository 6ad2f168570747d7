"""Device time of one gradient step under the `encoder` scope of `make_train_phase` (the encoder): leaf-op time of the
capture's ops whose name stack holds the scope, forward and backward."""

from perfbench.harness import program_spans
from perfbench.harness.program_spans import from_capture


def read(run):
    return from_capture(run, program_spans.part_ms, "encoder")
