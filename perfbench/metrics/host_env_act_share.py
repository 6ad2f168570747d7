"""Share of the window the loop spent in its env phase (env step, the act program on
the host CPU, replay add): telemetry `window.phases.env` over the windows' wall time."""


def read(run):
    if not run.phases:
        return None
    return 100.0 * run.phases["env"] / run.phases["wall"]
