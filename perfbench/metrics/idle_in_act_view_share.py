"""Device-idle time inside the host's `act_view` spans, as a share of the traced whole cycles (taken from the
program's own spans on the capture, never first-op-to-last-op)."""

from perfbench.harness.program_spans import idle_share


def read(run):
    return idle_share(run, "act_view")
