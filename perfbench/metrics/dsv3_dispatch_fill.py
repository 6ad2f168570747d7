"""Pairs landed on the held experts over the rows of the buffers the update's grouped products
ran on (every round's): the program's counter `moe/update_dispatch_fill`. Half at uniform routing
and a slack of 2; over 1 cannot be."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.counter_mean(run, "moe/update_dispatch_fill")
