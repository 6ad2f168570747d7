"""Median length of the loop's `act` span where it is the first after an `act_view`: the act program's first
call on the parameters a train call just sent back (`spans.jsonl`, the timed window's iterations)."""

from perfbench.harness.program_spans import act_ms


def read(run):
    return act_ms(run, "first")
