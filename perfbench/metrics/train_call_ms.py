"""Host time of one train call: sample, the train program and the return of the act
view (`window.phases.train` over the window's train calls)."""


def read(run):
    if not run.phases or not run.phases["train_calls"]:
        return None
    return 1e3 * run.phases["train"] / run.phases["train_calls"]
