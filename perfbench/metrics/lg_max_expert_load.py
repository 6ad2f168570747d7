"""The fullest held expert's load over the mean load of the 16 held, in the update's gradient
steps (mean over layers and steps): the program's counter `moe/update_max_load`. 1 is perfect balance."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.counter_mean(run, "moe/update_max_load")
