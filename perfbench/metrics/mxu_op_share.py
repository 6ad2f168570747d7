"""Share of the device's op time spent in ops the capture reduction classes as `mxu`
(convolutions, dots and the fusions rooted at them)."""


def read(run):
    if not run.capture or not run.capture["leaf_op_s"]:
        return None
    return 100.0 * run.capture["categories"]["mxu"] / run.capture["leaf_op_s"]
