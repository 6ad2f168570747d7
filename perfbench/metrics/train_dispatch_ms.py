"""Host time a train call spends dispatching its G gradient steps (`window.spans.train_dispatch`): the call of
`train_phase` returns before the device is done."""

from perfbench.harness.program_spans import span_ms_a_train_call


def read(run):
    return span_ms_a_train_call(run, "train_dispatch")
