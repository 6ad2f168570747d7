"""1 minus the union of the device's op intervals over the traced window."""


def read(run):
    if not run.capture or not run.capture["busy_s"]:
        return None
    return 100.0 * (1.0 - run.capture["busy_s"] / run.capture["window_s"])
