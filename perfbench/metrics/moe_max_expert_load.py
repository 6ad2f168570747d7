"""The fullest held expert's load over the mean load of the held experts, in the update's
gradient steps (mean over expert layers and steps): the program's counter
`moe/update_max_load` in the window's telemetry. 1 is perfect balance."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.counter_mean(run, "moe/update_max_load")
