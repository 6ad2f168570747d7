"""Named-span timing — drives the ``Time/sps_*`` throughput metrics, and is the
program's one span primitive.

Same contract as the reference's timer (sheeprl/utils/timer.py:16-84): a context
manager/decorator with a class-level registry of named accumulating timers; reduced at
log time into `sps_train` / `sps_env_interaction` (the BASELINE north-star metrics,
logged e.g. at sheeprl/algos/ppo/ppo.py:393-408).

While not ``disabled``, every enter/exit is also a span: ``(name, start, end, parent,
iter)`` on ``time.perf_counter()`` goes into one bounded ring (``timer.ring``, oldest
dropped), and a ``jax.profiler.TraceAnnotation`` of the same name is open for the
span's length, so whenever a profiler session is open the span sits on the capture's
clock beside the device ops. ``parent`` is the enclosing open span on this thread,
``iter`` the loop's iteration (``timer.iteration``, set once at the top of a loop
body). ``RunTelemetry`` reads the ring (``window.spans``, ``spans.jsonl``).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import ContextDecorator
from typing import Any, ClassVar, Deque, Dict, List, Optional, Tuple

# jax.profiler.TraceAnnotation, bound by the first span that is not disabled: this
# module imports nothing at start-up that it did not before it had spans
TraceAnnotation: Any = None

# spans kept in memory: a week-long run must not grow. The Dreamer-V3 loop records about
# 9.5 spans an iteration at 4 envs and a train call every second iteration (6 every
# iteration, 6 a train call and 1 a gradient step), so this is its last ~1,700
# iterations, about 1.5 MB
RING_CAPACITY = 16384

Span = Tuple[str, float, float, Optional[str], int]  # name, start, end, parent, iter


class timer(ContextDecorator):
    disabled: ClassVar[bool] = False
    timers: ClassVar[Dict[str, "timer"]] = {}
    ring: ClassVar[Deque[Span]] = deque(maxlen=RING_CAPACITY)
    counters: ClassVar[Dict[str, List[float]]] = {}  # name -> [count, total]
    iteration: ClassVar[int] = 0
    _open: ClassVar[threading.local] = threading.local()  # .stack: names of the open spans
    _count_lock: ClassVar[threading.Lock] = threading.Lock()

    def __new__(cls, name: str, **kwargs: Any) -> "timer":
        if name not in cls.timers:
            inst = super().__new__(cls)
            inst._init(name)
            cls.timers[name] = inst
        return cls.timers[name]

    def _init(self, name: str) -> None:
        self.name = name
        self._total = 0.0
        self._count = 0
        self._start: Optional[float] = None
        self._annotation: Any = None
        # reset generation, bumped by reset(): lets non-destructive readers (the
        # telemetry window accounting) distinguish "total shrank because of a
        # reset" from "total grew past my last sample" exactly, not heuristically
        self._resets = 0

    def __init__(self, name: str, **kwargs: Any) -> None:
        # __new__ handles registry; nothing to do (kwargs accepted for reference parity)
        pass

    def __enter__(self) -> "timer":
        if not timer.disabled:
            try:
                timer._open.stack.append(self.name)
            except AttributeError:
                timer._open.stack = [self.name]
            self._annotation = (TraceAnnotation or _bind_annotation())(self.name)
            self._annotation.__enter__()
            self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type: Any = None, exc: Any = None, tb: Any = None) -> bool:
        if not timer.disabled and self._start is not None:
            end = time.perf_counter()
            self._total += end - self._start
            self._count += 1
            stack = getattr(timer._open, "stack", None)
            if stack and stack[-1] == self.name:
                stack.pop()
            elif stack and self.name in stack:  # exited out of order: still leave the stack
                stack.remove(self.name)
            timer.ring.append(
                (self.name, self._start, end, stack[-1] if stack else None, timer.iteration)
            )
            self._start = None
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
                self._annotation = None
        return False

    def add(self, seconds: float) -> None:
        """Account an externally measured span. The Anakin loops measure ONE
        fused rollout+train program call and split its wall time across two
        phase timers by a measured rollout-only share — a context manager
        cannot express that, so they add the shares directly."""
        if not timer.disabled and seconds > 0:
            self._total += seconds
            self._count += 1

    @classmethod
    def count(cls, name: str, value: float) -> None:
        """Add ``value`` to the named counter (read per window as ``window.counters``)."""
        if not cls.disabled:
            with cls._count_lock:  # loops count from player and learner threads
                counter = cls.counters.setdefault(name, [0, 0.0])
                counter[0] += 1
                counter[1] += value

    @classmethod
    def spans_since(cls, cursor: float) -> List[Span]:
        """The ring's spans that ended after ``cursor`` (a ``perf_counter`` reading:
        the ``end`` of the last span the caller has seen), oldest first."""
        spans = list(cls.ring)  # one C-level copy: safe against appends from other threads
        first = len(spans)
        while first > 0 and spans[first - 1][2] > cursor:
            first -= 1
        return spans[first:]

    def compute(self) -> float:
        return self._total

    def reset(self) -> None:
        """Zero the accumulated totals. An in-flight span (entered but not yet
        exited — e.g. a log boundary landing inside ``with timer(...)``) keeps
        its ``_start``, so ``__exit__`` still accounts it into the new window
        instead of silently dropping the whole span."""
        self._total = 0.0
        self._count = 0
        self._resets += 1

    @classmethod
    def to_dict(cls, reset: bool = True) -> Dict[str, float]:
        out = {name: t.compute() for name, t in cls.timers.items() if t._count > 0}
        if reset:
            for t in cls.timers.values():
                t.reset()
        return out


def _bind_annotation() -> Any:
    global TraceAnnotation
    from jax.profiler import TraceAnnotation as bound

    TraceAnnotation = bound
    return bound


def aggregate_spans(spans: List[Span]) -> Dict[str, List[float]]:
    """``{name: [count, seconds, self_seconds]}`` over ``spans``: self time is a
    span's length less what the spans that name it as ``parent`` cover."""
    out: Dict[str, List[float]] = {}
    for name, start, end, _parent, _iter in spans:
        entry = out.setdefault(name, [0, 0.0, 0.0])
        entry[0] += 1
        entry[1] += end - start
        entry[2] += end - start
    for _name, start, end, parent, _iter in spans:
        if parent in out:
            out[parent][2] -= end - start
    return out
