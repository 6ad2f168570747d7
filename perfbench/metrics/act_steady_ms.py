"""Median length of the loop's `act` span where no `act_view` came before it: acting on parameters already used
(`spans.jsonl`, the timed window's iterations). Nothing to read in a cell that trains every iteration."""

from perfbench.harness.program_spans import act_ms


def read(run):
    return act_ms(run, "steady")
