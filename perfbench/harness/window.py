"""The timed window: whole train cycles over at least `seconds` of wall time.

A cycle is the least whole number of loop iterations after which the replay-ratio
governor has issued a whole number of gradient steps (`cycle_of`). The window opens
at a cycle boundary that directly follows a train call, after `warmup_cycles` whole
cycles without a compilation, and closes at the first cycle boundary at or after
`seconds`. Both ends wait for the device (`sync`). The rate is every env step of
those cycles over all of that time: a stall inside the window lowers it, and a
partial cycle is never counted. Nothing here touches JAX, so it is tested with a
made-up clock.
"""

from __future__ import annotations

from fractions import Fraction
from typing import Callable, List, Optional, Tuple


def cycle_of(num_envs: int, replay_ratio: float) -> Tuple[int, int]:
    """(iterations, gradient steps) of one cycle: 1 env at ratio 0.5 gives (2, 1),
    4 envs at 0.5 give (1, 2), 4 envs at 0.125 give (2, 1)."""
    per_iteration = Fraction(str(replay_ratio)) * num_envs
    if per_iteration <= 0:
        raise ValueError("a training cell needs a positive replay ratio")
    return per_iteration.denominator, per_iteration.numerator


class CycleWindow:
    def __init__(
        self,
        *,
        cycle_iterations: int,
        gradient_steps_per_cycle: int,
        env_steps_per_iteration: int,
        seconds: float,
        warmup_cycles: int = 10,
        clock: Callable[[], float],
        sync: Callable[[], None] = lambda: None,
        compiles: Callable[[], int] = lambda: 0,
        on_cycle: Optional[Callable[[int], None]] = None,
    ):
        self.cycle_iterations = int(cycle_iterations)
        self.gradient_steps_per_cycle = int(gradient_steps_per_cycle)
        self.env_steps_per_iteration = int(env_steps_per_iteration)
        self.seconds = float(seconds)
        self.warmup_cycles = int(warmup_cycles)
        self._clock, self._sync, self._compiles, self._on_cycle = clock, sync, compiles, on_cycle
        self.state = "idle"  # idle -> align -> warmup -> open -> closed
        self._trained = 0  # gradient steps in the current iteration
        self._iterations = self._cycle_gradient_steps = self._quiet = 0
        self._compile_count = 0
        self.t_open = self.t_close = None
        self.compiles_at_open = self.compiles_at_close = None
        self.boundaries: List[float] = []  # clock at every cycle boundary of the window
        self.train_calls = self.gradient_steps = 0

    def arm(self) -> None:
        """Warm-up may begin (the steps the correctness check follows are done)."""
        if self.state == "idle":
            self.state = "align"

    def on_train(self, gradient_steps: int) -> None:
        self._trained += int(gradient_steps)
        if self.state == "open":
            self.train_calls += 1
            self.gradient_steps += int(gradient_steps)

    def on_iteration_end(self) -> bool:
        """Call once at the end of every loop iteration; True once the window closed."""
        trained, self._trained = self._trained, 0
        if self.state in ("idle", "closed"):
            return self.state == "closed"
        if self.state == "align":
            if trained:  # anchor: boundaries follow a train call, so the device is drained
                self.state = "warmup"
                self._iterations = self._cycle_gradient_steps = self._quiet = 0
                self._compile_count = self._compiles()
            return False
        self._iterations += 1
        self._cycle_gradient_steps += trained
        if self._iterations % self.cycle_iterations:
            return False
        steps, self._cycle_gradient_steps = self._cycle_gradient_steps, 0
        if steps != self.gradient_steps_per_cycle:
            raise RuntimeError(
                f"a cycle of {self.cycle_iterations} iteration(s) ran {steps} gradient step(s), "
                f"not {self.gradient_steps_per_cycle}: the cell's cycle is not what its ratio says"
            )
        if self.state == "warmup":
            count = self._compiles()
            self._quiet = self._quiet + 1 if count == self._compile_count else 0
            self._compile_count = count
            if self._quiet >= self.warmup_cycles:
                self._sync()
                self.t_open = self._clock()
                self.compiles_at_open = self._compiles()
                self.boundaries = [self.t_open]
                self.state = "open"
            return False
        now = self._clock()
        self.boundaries.append(now)
        if self._on_cycle is not None:
            self._on_cycle(len(self.boundaries) - 1)
        if now - self.t_open >= self.seconds:
            self._sync()
            self.t_close = self.boundaries[-1] = self._clock()
            self.compiles_at_close = self._compiles()
            self.state = "closed"
            return True
        return False

    # -- what the window measured ------------------------------------------------
    @property
    def cycles(self) -> int:
        return max(len(self.boundaries) - 1, 0)

    @property
    def env_steps(self) -> int:
        return self.cycles * self.cycle_iterations * self.env_steps_per_iteration

    @property
    def window_seconds(self) -> float:
        return self.t_close - self.t_open

    @property
    def env_steps_per_s(self) -> float:
        return self.env_steps / self.window_seconds

    def cycle_seconds(self) -> List[float]:
        return [b - a for a, b in zip(self.boundaries, self.boundaries[1:])]
