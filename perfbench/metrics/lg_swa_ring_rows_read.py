"""The ring rows a sliding layer's decode step reads, in the mean over the rollout's steps and the
sliding layers: the program's counter `swa/rollout_ring_rows_read`. 512, the window, where the step
reads its whole ring and masks the slots not yet written; 448.1 over an episode of 2,048 where it
reads the slots written alone; 2,048 where the ring is a cache as long as the episode."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.counter_mean(run, "swa/rollout_ring_rows_read")
