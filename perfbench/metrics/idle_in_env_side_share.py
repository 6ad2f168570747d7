"""Device-idle time while the loop thread is inside `Time/env_interaction_time` but not in `act`, or in
`step_bookkeeping`: `env_step`, `replay_add`, `player_reset`, as a share of the traced whole cycles."""

from perfbench.harness.host_idle import share


def read(run):
    return share(run, "env_side")
