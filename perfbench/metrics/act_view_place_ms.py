"""Host time a train call spends placing the fetched act view on the CPU device, one `device_put` a leaf
(`window.spans["act_view.place"]`)."""

from perfbench.harness.program_spans import span_ms_a_train_call


def read(run):
    return span_ms_a_train_call(run, "act_view.place")
