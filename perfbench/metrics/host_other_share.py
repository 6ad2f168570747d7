"""Share of the window outside every named phase (`window.phases.other`)."""


def read(run):
    if not run.phases:
        return None
    return 100.0 * run.phases["other"] / run.phases["wall"]
