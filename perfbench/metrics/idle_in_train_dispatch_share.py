"""Device-idle time while the loop thread's innermost span is `train_dispatch` or one of its `train_dispatch.call`
children (the argument work and the calls of the train program), as a share of the traced whole cycles."""

from perfbench.harness.host_idle import share


def read(run):
    return share(run, "train_dispatch")
