"""Device-idle time while the loop thread is inside `Time/train_time` but not in `train_dispatch` or `act_view`:
`replay_sample`, `train_key`, `train_observe` and the call's own host work, as a share of the traced whole cycles."""

from perfbench.harness.host_idle import share


def read(run):
    return share(run, "train_prep")
