"""The cycle-boundary window arithmetic, on a made-up clock."""

import pytest

from perfbench.harness.window import CycleWindow, cycle_of


def test_cycle_of_the_three_cells():
    assert cycle_of(1, 0.5) == (2, 1)  # dv3_XL_crafter.train
    assert cycle_of(4, 0.5) == (1, 2)  # dv3_XL_crafter.train_4env
    assert cycle_of(4, 0.125) == (2, 1)  # dv3_L_doapp128.train
    with pytest.raises(ValueError):
        cycle_of(4, 0.0)


class Loop:
    """A loop that trains every `every` iterations, `steps` gradient steps a call."""

    def __init__(self, every, steps, envs, seconds, iteration_s=1.0, warmup=3):
        self.now, self.compiles, self.synced = 0.0, 0, []
        self.every, self.steps, self.iteration_s = every, steps, iteration_s
        self.window = CycleWindow(
            cycle_iterations=every, gradient_steps_per_cycle=steps, env_steps_per_iteration=envs,
            seconds=seconds, warmup_cycles=warmup, clock=lambda: self.now,
            sync=lambda: self.synced.append(self.now), compiles=lambda: self.compiles,
        )
        self.i = 0

    def iterate(self, stall=0.0):
        self.i += 1
        self.now += self.iteration_s + stall
        if self.i % self.every == 0:
            self.window.on_train(self.steps)
        return self.window.on_iteration_end()


def run_to_close(loop, stall_at=None, stall=0.0, limit=10_000):
    for n in range(limit):
        if loop.iterate(stall if n == stall_at else 0.0):
            return
    raise AssertionError("the window never closed")


def test_window_is_whole_cycles_over_at_least_the_seconds():
    loop = Loop(every=2, steps=1, envs=1, seconds=9.0)
    loop.window.arm()
    run_to_close(loop)
    w = loop.window
    assert w.window_seconds >= 9.0 and w.window_seconds == 10.0  # first boundary at or after 9 s
    assert w.cycles == 5 and w.env_steps == 10 and w.gradient_steps == 5 and w.train_calls == 5
    assert w.env_steps_per_s == pytest.approx(1.0)
    assert len(loop.synced) == 2  # the device is waited for at the two ends only
    assert w.cycle_seconds() == [2.0] * 5


def test_boundaries_follow_a_train_call_and_warmup_is_whole_quiet_cycles():
    loop = Loop(every=2, steps=1, envs=4, seconds=4.0, warmup=3)
    loop.iterate()  # not armed yet: nothing moves
    assert loop.window.state == "idle"
    loop.window.arm()
    loop.iterate()  # iteration 2 trains: the anchor
    assert loop.window.state == "warmup"
    for _ in range(4):
        loop.iterate()  # two quiet cycles
    loop.compiles += 1  # a compilation: the count of quiet cycles starts again
    for _ in range(4):
        loop.iterate()
    assert loop.window.state == "warmup"
    for _ in range(4):
        loop.iterate()
    assert loop.window.state == "open" and loop.window.t_open == loop.now
    assert loop.i % 2 == 0  # opened right after a train call
    run_to_close(loop)
    assert loop.window.compiles_at_close == loop.window.compiles_at_open


def test_a_stall_inside_the_window_lowers_the_rate():
    steady, stalled = (Loop(every=1, steps=2, envs=4, seconds=20.0) for _ in range(2))
    for loop in (steady, stalled):
        loop.window.arm()
    run_to_close(steady)
    run_to_close(stalled, stall_at=10, stall=7.5)
    assert steady.window.env_steps_per_s == pytest.approx(4.0)
    assert stalled.window.env_steps_per_s < 0.75 * steady.window.env_steps_per_s
    # all the work over all the time: the stalled cycle is in both
    assert max(stalled.window.cycle_seconds()) == pytest.approx(8.5)


def test_a_partial_cycle_is_never_counted():
    loop = Loop(every=2, steps=1, envs=1, seconds=2.5)
    loop.window.arm()
    run_to_close(loop)
    w = loop.window
    assert w.window_seconds == 4.0 and w.env_steps == 4  # not 3 steps over 2.5 s or 3 s
    assert w.env_steps == w.cycles * 2


def test_a_cycle_that_is_not_what_the_ratio_says_is_an_error():
    loop = Loop(every=1, steps=2, envs=4, seconds=5.0)
    loop.window.gradient_steps_per_cycle = 3
    loop.window.arm()
    loop.iterate()
    with pytest.raises(RuntimeError, match="gradient step"):
        loop.iterate()
