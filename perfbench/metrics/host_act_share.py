"""Share of the windows' wall time inside the loop's `act` span: `prepare_obs`, `player.get_actions` on the
host CPU and the copy of the actions (`window.spans.act` of telemetry.jsonl)."""

from perfbench.harness.program_spans import span_share


def read(run):
    return span_share(run, "act")
