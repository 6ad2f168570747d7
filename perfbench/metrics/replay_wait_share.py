"""Share of the window the train call waited for a staged replay block
(`window.phases.replay_wait`, from the prefetcher's own wait counter)."""


def read(run):
    if not run.phases:
        return None
    return 100.0 * run.phases["replay_wait"] / run.phases["wall"]
