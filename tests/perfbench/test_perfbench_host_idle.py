"""The reader that puts the device's idle time down to the span the host loop's thread
was in (perfbench/harness/host_idle.py), on hand-made captures: the loop's line, a
second thread's line, JAX's and the harness's own host events, and device ops that
leave known stretches idle."""

import json
import os
import types

import jax
import pytest

from perfbench.harness import bench
from perfbench.harness import host_idle
from perfbench.harness import program_spans as ps

# one iteration in microseconds, laid out twice (at 0 and at 1000): the loop's spans
LOOP = [("Time/env_interaction_time", 0, 200), ("act", 10, 150), ("replay_add", 150, 160), ("env_step", 160, 200),
        ("step_bookkeeping", 200, 250), ("player_reset", 230, 240),
        ("Time/train_time", 260, 800), ("replay_sample", 260, 300), ("train_key", 300, 320),
        ("train_dispatch", 320, 500), ("train_dispatch.call", 340, 480), ("act_view", 500, 520),
        ("metrics_get", 520, 540), ("train_observe", 540, 800), ("loop_tail", 800, 990)]
OTHERS = [("perfbench.train_call", 250, 260), ("PjitFunction(train_step)", 990, 1000)]  # not the program's
SECOND_THREAD = [("loop_tail", 250, 260), ("replay_sample", 890, 930)]  # the program's names, on another line
# where the device is idle, and what the innermost span there is
IDLE = {"act": [(20, 40)], "env_side": [(165, 175), (205, 215), (232, 236)], "outside": [(252, 258), (992, 998)],
        "train_prep": [(270, 280), (310, 315), (525, 530), (600, 610)], "train_dispatch": [(325, 335), (350, 360)],
        "act_view": [(505, 510)], "loop_tail": [(900, 920)]}
CYCLES = (0, 1000)
WINDOW = (0, 1800)  # first iteration's start to the last train call's end: the second loop_tail lies past it
NEW = ("step_bookkeeping", "train_key", "train_dispatch.call", "train_observe", "loop_tail")


def _plane(name, lines):
    ids, out = {}, [f'planes {{ name: "{name}"']
    for line, events in lines.items():
        out.append(f'  lines {{ name: "{line}" timestamp_ns: 0')
        for event, start, end in events:
            key = ids.setdefault(event, len(ids) + 1)
            out.append(f"    events {{ metadata_id: {key} offset_ps: {start * 10**6} duration_ps: {(end - start) * 10**6} }}")
        out.append("  }")
    out += [f'  event_metadata {{ key: {key} value {{ id: {key} name: "{event}" }} }}' for event, key in ids.items()]
    return "\n".join(out) + "\n}"


def _busy(idle, lo, hi):
    """Device ops that fill [lo, hi] but for the idle stretches."""
    return [(f"%fusion.{i} = f32[8] fusion(%p.{i})", a, b) for i, (a, b) in enumerate(ps.complement(idle, lo, hi))]


def _run(tmp_path, names=None, loop=LOOP):
    trace_dir, log_dir = tmp_path / "trace", tmp_path / "log"
    idle = [(a + o, b + o) for o in CYCLES for spans in IDLE.values() for a, b in spans]
    text = "\n".join([
        _plane("/host:CPU", {
            "python3": [(n, a + o, b + o) for o in CYCLES for n, a, b in loop + OTHERS],
            "dv3-replay-prefetch": [(n, a + o, b + o) for o in CYCLES for n, a, b in SECOND_THREAD],
        }),
        _plane("/device:TPU:0", {"XLA Ops": _busy(idle, -50, 2050)}),
    ])
    path = trace_dir / "plugins" / "profile" / "2026_01_01" / "host.xplane.pb"
    os.makedirs(path.parent)
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    os.makedirs(log_dir)
    with open(log_dir / "spans.jsonl", "w") as fh:
        for name in names if names is not None else {n for n, _, _ in loop}:
            fh.write(json.dumps({"name": name, "start": 0.0, "end": 1.0, "parent": None, "iter": 1}) + "\n")
    return types.SimpleNamespace(trace_dir=str(trace_dir), log_dir=str(log_dir))


def _expected(group):
    seconds = sum(min(b + o, WINDOW[1]) - (a + o) for o in CYCLES for a, b in IDLE[group] if a + o < WINDOW[1])
    return 100.0 * seconds / (WINDOW[1] - WINDOW[0])


def test_idle_goes_to_the_innermost_group_open_on_the_loop_s_thread(tmp_path):
    """Nested spans: the innermost named group wins (`train_dispatch.call` inside `train_dispatch` inside
    `Time/train_time`; `metrics_get`, which no group names, counts to `Time/train_time`'s); idle with no span
    open is `outside`, though JAX's and the harness's events and the second thread's spans are open there."""
    run = _run(tmp_path)
    shares = host_idle.read(run)
    for group in IDLE:
        assert shares[group] == pytest.approx(_expected(group)), group
    assert shares["idle"] == pytest.approx(sum(_expected(group) for group in IDLE))
    # the same cycles, device ops and scale as `program_spans.idle_shares`: the five add up to its unattributed
    theirs = ps.idle_shares(ps.capture_of(run))
    assert shares["act"] == pytest.approx(theirs["act"]) and shares["act_view"] == pytest.approx(theirs["act_view"])
    five = ("train_dispatch", "train_prep", "env_side", "loop_tail", "outside")
    assert sum(shares[g] for g in five) == pytest.approx(theirs["unattributed"])


@pytest.mark.parametrize("name, group", [
    ("idle_in_train_dispatch_share", "train_dispatch"), ("idle_in_train_prep_share", "train_prep"),
    ("idle_in_env_side_share", "env_side"), ("idle_in_loop_tail_share", "loop_tail"),
    ("idle_outside_spans_share", "outside"),
])
def test_each_metric_file_reads_its_group(tmp_path, name, group):
    assert bench.read_metric(name, _run(tmp_path)) == pytest.approx(_expected(group))


def test_a_program_span_no_group_names_counts_outside_where_no_group_span_is_open(tmp_path):
    """A new top-level span shows in the guard until a group names it."""
    loop = [span for span in LOOP if span[0] != "loop_tail"] + [("a_new_phase", 800, 990)]
    shares = host_idle.read(_run(tmp_path, names={n for n, _, _ in LOOP} | {"a_new_phase"}, loop=loop))
    assert shares["loop_tail"] == 0.0
    assert shares["outside"] == pytest.approx(_expected("outside") + _expected("loop_tail"))


@pytest.mark.parametrize("what", ["no_new_spans", "no_spans_file", "no_capture"])
def test_a_run_without_the_tiling_spans_reads_as_nothing(tmp_path, capsys, what):
    """A loop without the tiling spans (the Dreamer-V3 loop as it was, or any other loop) reads None and says
    why; so does a run with no `spans.jsonl` or no capture. None of it raises."""
    run = _run(tmp_path, names={n for n, _, _ in LOOP} - set(NEW) if what == "no_new_spans" else None)
    if what == "no_spans_file":
        os.remove(os.path.join(run.log_dir, "spans.jsonl"))
    if what == "no_capture":
        run.trace_dir = str(tmp_path / "elsewhere")
    assert host_idle.read(run) is None
    assert all(bench.read_metric(name, run) is None for name in (
        "idle_in_train_dispatch_share", "idle_in_train_prep_share", "idle_in_env_side_share",
        "idle_in_loop_tail_share", "idle_outside_spans_share"))
    said = capsys.readouterr().err
    assert {"no_new_spans": "does not tile an iteration", "no_spans_file": "no " + run.log_dir,
            "no_capture": "no .xplane.pb"}[what] in said


def _telemetry_run(tmp_path, spans):
    """A run whose telemetry windows carry `spans`, with what `program_spans.spans_of` asks of it."""
    os.makedirs(tmp_path / "log")
    with open(tmp_path / "log" / "telemetry.jsonl", "w") as fh:
        for step in (8, 16):
            fh.write(json.dumps({"event": "window", "step": step, "wall_seconds": 1.0, "spans": spans}) + "\n")
    window = types.SimpleNamespace(cycle_iterations=1, env_steps_per_iteration=8)
    return types.SimpleNamespace(log_dir=str(tmp_path / "log"), policy_step_open=0, policy_step_close=16,
                                 window=window, trace_steps=(24, 40))  # the traced cycles: after these windows


def test_train_dispatch_call_ms_is_the_calls_host_time_a_train_call(tmp_path):
    spans = {"Time/train_time": [1, 0.2, 0.01], "train_dispatch": [1, 0.15, 0.03], "train_dispatch.call": [2, 0.12, 0.12]}
    run = _telemetry_run(tmp_path, spans)
    assert bench.read_metric("train_dispatch_call_ms", run) == pytest.approx(120.0)  # 2 x 0.12 s over 2 calls
    assert bench.read_metric("train_dispatch_ms", run) == pytest.approx(150.0)
    del spans["train_dispatch.call"]  # a loop without the per-call span
    assert bench.read_metric("train_dispatch_call_ms", _telemetry_run(tmp_path / "before", spans)) is None
