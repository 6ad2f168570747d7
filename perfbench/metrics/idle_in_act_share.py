"""Device-idle time inside the host's `act` spans, as a share of the traced whole cycles."""

from perfbench.harness.program_spans import idle_share


def read(run):
    return idle_share(run, "act")
