"""The fullest held expert's load over the mean load of the 8 held, in the update's gradient
steps (mean over layers and steps): the program's counter `moe/update_max_load`. 1 is perfect balance."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.counter_mean(run, "moe/update_max_load")
