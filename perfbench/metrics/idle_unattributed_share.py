"""Device-idle time inside neither an `act_view` nor an `act` span of the host, as a share of the traced whole cycles."""

from perfbench.harness.program_spans import idle_share


def read(run):
    return idle_share(run, "unattributed")
