"""Regression tests for the named-span timer (sheeprl_tpu/utils/timer.py)."""

from __future__ import annotations

import time

import pytest

from sheeprl_tpu.utils.timer import timer


@pytest.fixture(autouse=True)
def _fresh_registry():
    saved, timer.timers = timer.timers, {}
    saved_disabled, timer.disabled = timer.disabled, False
    yield
    timer.timers = saved
    timer.disabled = saved_disabled


def test_accumulates_and_resets():
    with timer("t"):
        time.sleep(0.01)
    assert timer("t").compute() > 0
    assert "t" in timer.to_dict(reset=True)
    assert timer.to_dict(reset=False) == {}  # count reset → excluded


def test_reset_preserves_in_flight_span():
    """A log boundary (to_dict(reset=True)) landing INSIDE an open span must not
    drop the span: __exit__ still accounts it into the new window."""
    t = timer("span")
    with t:
        time.sleep(0.005)
        timer.to_dict(reset=True)  # the log site's reset, mid-span
        time.sleep(0.005)
    assert t.compute() >= 0.005, "open span was dropped by reset()"
    out = timer.to_dict(reset=True)
    assert out["span"] >= 0.005


def test_explicit_reset_mid_span():
    t = timer("span2")
    with t:
        time.sleep(0.002)
        t.reset()
    assert t.compute() > 0


def test_disabled_timer_records_nothing():
    timer.disabled = True
    with timer("off"):
        time.sleep(0.002)
    assert timer.to_dict(reset=True) == {}


# ---------------------------------------------------------------------------------
# the span primitive: every enter/exit is also a span in the ring and a
# TraceAnnotation; the disabled path stays one attribute test
# ---------------------------------------------------------------------------------
@pytest.fixture
def spans(monkeypatch):
    """A fresh ring, counters and iteration, and the annotations that get built."""
    import collections

    from sheeprl_tpu.utils import timer as timer_mod

    built = []

    class Annotation:
        def __init__(self, name):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(timer_mod, "TraceAnnotation", Annotation)
    monkeypatch.setattr(timer, "ring", collections.deque(maxlen=timer_mod.RING_CAPACITY))
    monkeypatch.setattr(timer, "counters", {})
    monkeypatch.setattr(timer, "iteration", 0)
    return built


def _fake_clock(monkeypatch, step=1.0):
    """perf_counter that advances by `step` a call: spans get exact lengths."""
    from sheeprl_tpu.utils import timer as timer_mod

    now = [0.0]

    def tick():
        now[0] += step
        return now[0]

    monkeypatch.setattr(timer_mod.time, "perf_counter", tick)


def _case_nesting(monkeypatch, built):
    from sheeprl_tpu.utils.timer import aggregate_spans

    _fake_clock(monkeypatch)
    with timer("outer"):  # enters at 1
        with timer("inner"):  # 2 .. 3
            pass
        with timer("inner"):  # 4 .. 5
            pass
    # exits at 6
    records = list(timer.ring)
    assert [(r[0], r[3]) for r in records] == [("inner", "outer"), ("inner", "outer"), ("outer", None)]
    assert [(r[1], r[2]) for r in records] == [(2.0, 3.0), (4.0, 5.0), (1.0, 6.0)]
    assert aggregate_spans(records) == {"inner": [2, 2.0, 2.0], "outer": [1, 5.0, 3.0]}
    assert built == ["outer", "inner", "inner"]  # one annotation of the same name a span


def _case_iteration(monkeypatch, built):
    for iteration in (7, 8):
        timer.iteration = iteration
        with timer("a"):
            with timer("b"):
                pass
        with timer("c"):
            pass
    assert [r[4] for r in timer.ring] == [7, 7, 7, 8, 8, 8]


def _case_ring_bounded(monkeypatch, built):
    import collections

    monkeypatch.setattr(timer, "ring", collections.deque(maxlen=8))
    for _ in range(50):
        with timer("a"):
            pass
    assert len(timer.ring) == 8 and timer("a")._count == 50  # oldest dropped, totals whole


def _case_reset_mid_span(monkeypatch, built):
    _fake_clock(monkeypatch)
    with timer("outer"):
        with timer("span"):
            timer.to_dict(reset=True)  # the log site's reset, inside both spans
    assert [(r[0], r[3]) for r in timer.ring] == [("span", "outer"), ("outer", None)]
    assert timer("span").compute() == 1.0 and timer("outer").compute() == 3.0
    assert getattr(timer._open, "stack") == []  # nothing is left open


def _case_disabled(monkeypatch, built):
    import tracemalloc

    from sheeprl_tpu.utils import timer as timer_mod

    outer, inner = timer("outer"), timer("inner")
    timer.disabled = True
    def loop():
        for _ in range(200):
            with outer:
                with inner:
                    timer.count("bytes", 3)

    tracemalloc.start()
    loop()  # whatever the interpreter sets up once for this code is set up here
    before = tracemalloc.take_snapshot()
    loop()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = [
        stat for stat in after.compare_to(before, "filename")
        if stat.traceback[0].filename == timer_mod.__file__ and stat.size_diff > 0
    ]
    assert grown == []  # the untraced run: one attribute test, nothing allocated
    assert built == [] and not timer.ring and timer.counters == {} and timer.to_dict() == {}


def _case_totals_unchanged(monkeypatch, built):
    """The Time/* totals are what the accumulate-only timer gave for the same enters
    and exits: each is the sum of its own exit minus enter readings."""
    _fake_clock(monkeypatch, step=0.25)
    with timer("Time/env_interaction_time"):  # 0.25
        with timer("act"):  # 0.5 .. 0.75
            pass
    # 1.0
    with timer("Time/train_time"):  # 1.25
        with timer("replay_sample"):  # 1.5 .. 1.75
            pass
        timer("Time/train_time").reset()
    # 2.0
    with timer("Time/env_interaction_time"):  # 2.25 .. 2.5
        pass
    assert timer.to_dict(reset=False) == {
        "Time/env_interaction_time": 0.75 + 0.25, "act": 0.25, "Time/train_time": 0.75, "replay_sample": 0.25,
    }
    assert timer("Time/env_interaction_time")._count == 2


def _case_counters_and_cursor(monkeypatch, built):
    _fake_clock(monkeypatch)
    timer.count("act_view_bytes", 100)
    timer.count("act_view_bytes", 50)
    assert timer.counters == {"act_view_bytes": [2, 150.0]}
    for _ in range(3):
        with timer("a"):  # (1, 2), (3, 4), (5, 6)
            pass
    assert [r[2] for r in timer.spans_since(0.0)] == [2.0, 4.0, 6.0]
    assert [r[2] for r in timer.spans_since(2.0)] == [4.0, 6.0] and timer.spans_since(6.0) == []


def _case_threads(monkeypatch, built):
    """Several threads at once (a decoupled loop's player and learner): each keeps its
    own stack of open spans, the ring stays bounded, no count is lost."""
    import sys
    import threading

    workers, rounds = 8, 500

    def work(k):
        for _ in range(rounds):
            with timer(f"outer{k}"):
                with timer(f"inner{k}"):
                    timer.count("n", 1)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert timer.counters["n"] == [workers * rounds, float(workers * rounds)]
    assert len(timer.ring) == min(2 * workers * rounds, timer.ring.maxlen)
    for name, _start, _end, parent, _iter in timer.ring:  # never another thread's span as parent
        assert parent == (name.replace("inner", "outer") if name.startswith("inner") else None)


@pytest.mark.parametrize(
    "case",
    [_case_nesting, _case_iteration, _case_ring_bounded, _case_reset_mid_span, _case_disabled,
     _case_totals_unchanged, _case_counters_and_cursor, _case_threads],
    ids=lambda case: case.__name__[len("_case_"):],
)
def test_span_primitive(case, monkeypatch, spans):
    case(monkeypatch, spans)
