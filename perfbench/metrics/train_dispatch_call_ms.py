"""Host time a train call spends inside the calls of the jitted train step (`window.spans["train_dispatch.call"]`,
one span a gradient step): `train_dispatch_ms` less its argument work."""

from perfbench.harness.program_spans import span_ms_a_train_call


def read(run):
    return span_ms_a_train_call(run, "train_dispatch.call")
