"""Host time of `sampler.sample(n)` a train call: prefetch wait plus staging (`window.spans.replay_sample`)."""

from perfbench.harness.program_spans import span_ms_a_train_call


def read(run):
    return span_ms_a_train_call(run, "replay_sample")
