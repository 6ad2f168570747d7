"""The readers of what the program says about itself (perfbench/harness/program_spans.py):
on a synthetic capture whose host spans sit on a line that is NOT named `python`, and on
a small capture recorded on the v5e (tests/data/recorded_capture_v5e), which pins the
line names and argument keys this runtime writes."""

import glob
import gzip
import json
import os
import types

import jax
import pytest

from perfbench.harness import program_spans as ps

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "data", "recorded_capture_v5e")

# one cycle in microseconds, laid out twice (at 0 and at 400): what the loop does
HOST = [("Time/env_interaction_time", 0, 100), ("act", 10, 90), ("Time/train_time", 100, 400),
        ("train_dispatch", 100, 104), ("act_view", 256, 390), ("act_view.fetch", 256, 370),
        ("act_view.place", 370, 390)]
OPS = [  # (HLO text, name stack or None, start, end) on `XLA Ops`; the while holds the step's leaves
    ("%while.1 = (f32[8]) while(%tuple.1)", "jit(train_step)/jit(main)/while:", 105, 250),
    ("%fusion.enc = f32[8] fusion(%p.0), kind=kOutput", "jit(train_step)/jit(main)/jvp(encoder)/Encoder/conv_general_dilated:", 105, 125),
    ("%fusion.rssm = f32[8] fusion(%p.1), kind=kOutput", "jit(train_step)/jit(main)/jvp(rssm)/while/body/closed_call/dot_general:", 125, 175),
    ("%fusion.rssm_b = f32[8] fusion(%p.2), kind=kOutput", "jit(train_step)/jit(main)/transpose(jvp(rssm))/while/body/dot_general:", 175, 205),
    ("%fusion.opt = f32[8] fusion(%p.3), kind=kLoop", "jit(train_step)/jit(main)/optimizer/mul:", 205, 225),
    ("%copy.9 = f32[8] copy(%p.4)", None, 225, 235),  # the compiler's own: no name stack
    ("%fusion.imag = f32[8] fusion(%p.5), kind=kOutput", "jit(train_step)/jit(main)/jvp(imagine)/while/body/Actor/dot_general:", 235, 250),
    ("%concatenate.1 = f32[64] concatenate(%p.6)", "jit(_pack_leaves)/concatenate:", 260, 270),
]
MODULES = [("jit_train_step(123)", 105, 250), ("jit__pack_leaves(7)", 260, 270)]
CYCLES = (0, 400)


def _plane(name, lines, named):
    """Text proto of one XPlane: `lines` = {line name: [(event name, start us, end us)]}."""
    ids = {}
    out = [f'planes {{ name: "{name}"']
    for line, events in lines.items():
        out.append(f'  lines {{ name: "{line}" timestamp_ns: 0')
        for event, start, end in events:
            key = ids.setdefault(event, len(ids) + 1)
            out.append(f"    events {{ metadata_id: {key} offset_ps: {start * 10**6} duration_ps: {(end - start) * 10**6} }}")
        out.append("  }")
    for event, key in ids.items():
        stat = f' stats {{ metadata_id: 9 str_value: "{named[event]}" }}' if named.get(event) else ""
        out.append(f'  event_metadata {{ key: {key} value {{ id: {key} name: "{event}"{stat} }} }}')
    out.append('  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }\n}')
    return "\n".join(out)


def _write_capture(root, host_line="python3", host=HOST, scoped=True):
    host_events = [(n, a + o, b + o) for o in CYCLES for n, a, b in host]
    noise = [("$threading.py:1 run", 0, 800), ("PjitFunction(train_step)", 101, 103)]
    text = "\n".join([
        _plane("/host:CPU", {"tfrt-worker/12": noise[:1], host_line: noise[1:] + host_events}, {}),
        _plane("/device:TPU:0", {
            "XLA Ops": [(t, a + o, b + o) for o in CYCLES for t, _, a, b in OPS],
            "XLA Modules": [(m, a + o, b + o) for o in CYCLES for m, a, b in MODULES],
        }, {t: s for t, s, _, _ in OPS} if scoped else {}),
    ])
    path = os.path.join(root, "plugins", "profile", "2026_01_01", "host.xplane.pb")
    os.makedirs(os.path.dirname(path))
    with open(path, "wb") as fh:
        fh.write(jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_spans_are_found_on_a_line_that_is_not_named_python(tmp_path):
    _write_capture(str(tmp_path))
    capture = ps.load(str(tmp_path))
    assert set(capture.host) == ps.HOST_SPANS  # all of them on the line named `python3`
    assert capture.host["act"] == [pytest.approx((10e-6, 90e-6)), pytest.approx((410e-6, 490e-6))]
    assert ps.traced_cycles(capture) == pytest.approx((0.0, 800e-6))  # the program's spans, not first op to last op
    assert capture.carrier == "xplane.pb event metadata" and len(capture.scopes) == 7  # copy.9 has no name stack


def test_idle_time_is_split_by_what_the_host_was_inside(tmp_path):
    _write_capture(str(tmp_path))
    shares = ps.idle_shares(ps.load(str(tmp_path)))
    # a cycle of 400 us: busy [105,250] and [260,270]; idle 105 + 10 + 130 = 245, of which
    # 80 inside `act` [10,90], 4 + 120 inside `act_view` [256,390], 41 under neither
    assert shares["idle"] == pytest.approx(100 * 245 / 400)
    assert shares["act"] == pytest.approx(100 * 80 / 400)
    assert shares["act_view"] == pytest.approx(100 * 124 / 400)
    assert shares["unattributed"] == pytest.approx(100 * 41 / 400)
    assert shares["act"] + shares["act_view"] + shares["unattributed"] == pytest.approx(shares["idle"])


def test_device_time_of_a_step_by_scope_forward_and_backward(tmp_path):
    _write_capture(str(tmp_path))
    capture = ps.load(str(tmp_path))
    assert ps.part_ms(capture, "encoder") == pytest.approx(0.020)
    assert ps.part_ms(capture, "rssm") == pytest.approx(0.080)  # jvp(rssm) 50 + transpose(jvp(rssm)) 30
    assert ps.part_ms(capture, "imagine") == pytest.approx(0.015)  # the innermost scope; `Actor` is a module's name
    assert ps.part_ms(capture, "optimizer") == pytest.approx(0.020)
    assert ps.part_ms(capture, "actor", "critic") == 0.0 and ps.part_ms(capture, "decoder") == 0.0
    assert ps.unscoped_share(capture) == pytest.approx(100 * 10 / 145)  # the while is no leaf; the pack is another program
    assert ps.train_program_parts(capture)["steps"] == 2
    assert ps.act_view_sync_ms(capture) == pytest.approx(0.120)  # step ends 250, fetch ends 370


@pytest.mark.parametrize("stack, scope", [
    ("jit(train_step)/jit(main)/transpose(jvp(rssm))/while/body/dot_general:", "rssm"),
    ("jit(train_step)/jvp(heads)/MLPHead/DenseStack_0/Dense_0/dot_general:", "heads"),
    ("jit(train_step)/optimizer/mul:", "optimizer"),
    ("jit(train_step)/jvp(encoder)/Encoder/cnn_encoder/Conv_0/conv_general_dilated:", "encoder"),
    ("jit(train_step)/jvp()/reduce_sum:", None),
    ("jit(_pack_leaves)/concatenate:", None),
    ("", None),
])
def test_scope_of_a_name_stack(stack, scope):
    assert ps.scope_of(stack) == scope


@pytest.mark.parametrize("what", ["no_spans", "no_scopes", "no_capture"])
def test_a_program_without_spans_or_scopes_reads_as_nothing(tmp_path, capsys, what):
    """The parent of PR 27: every reader returns None and says why; none falls back to
    a label or to first-op-to-last-op."""
    if what == "no_capture":
        assert ps.load(str(tmp_path)) is None
        assert "no .xplane.pb" in capsys.readouterr().err
        return
    _write_capture(str(tmp_path), host=[] if what == "no_spans" else HOST, scoped=what != "no_scopes")
    capture = ps.load(str(tmp_path))
    assert ps.part_ms(capture, "rssm") is None and ps.unscoped_share(capture) is None
    if what == "no_spans":
        assert ps.traced_cycles(capture) is None and ps.idle_shares(capture) is None
        assert ps.act_view_sync_ms(capture) is None
        assert "no `Time/env_interaction_time`" in capsys.readouterr().err
    else:
        assert ps.idle_shares(capture)["idle"] == pytest.approx(100 * 245 / 400)  # spans alone suffice here
        assert "carries one of" in capsys.readouterr().err


def test_trace_json_names_the_ops_where_the_xplane_metadata_does_not(tmp_path):
    path = _write_capture(str(tmp_path), scoped=False)
    events = [{"ph": "X", "pid": 3, "tid": 3, "ts": 105, "dur": 20, "name": "fusion.enc",
               "args": {"long_name": OPS[1][0], "tf_op": OPS[1][1], "model_flops": "8"}},
              {"ph": "X", "pid": 3, "tid": 3, "ts": 225, "dur": 10, "name": "copy.9", "args": {"long_name": OPS[5][0]}},
              {"ph": "M", "pid": 3, "name": "process_name", "args": {"name": "/device:TPU:0"}}]
    with gzip.open(path.replace(".xplane.pb", ".trace.json.gz"), "wt") as fh:
        json.dump({"traceEvents": events}, fh)
    capture = ps.load(str(tmp_path))
    assert capture.carrier == "trace.json.gz" and capture.scopes == {OPS[1][0]: OPS[1][1]}
    assert ps.part_ms(capture, "encoder") == pytest.approx(0.020)


def _telemetry(path, windows):
    with open(path, "w") as fh:
        fh.write(json.dumps({"event": "start", "time": 1.0}) + "\n")
        for step, spans in windows:
            event = {"event": "window", "step": step, "wall_seconds": 2.0, "phases": {"env": 0.5, "train": 1.4}}
            if spans:
                event["spans"] = spans
                event["counters"] = {"act_view_bytes": [1, 1000.0]}
            fh.write(json.dumps(event) + "\n")


def test_window_spans_sums_the_windows_the_phases_are_summed_over(tmp_path):
    spans = {"Time/env_interaction_time": [2, 0.5, 0.1], "act": [2, 0.4, 0.4], "env_step": [2, 0.002, 0.002],
             "Time/train_time": [1, 1.4, 0.1], "replay_sample": [1, 0.003, 0.003], "train_dispatch": [1, 0.01, 0.01],
             "act_view.place": [1, 0.05, 0.05]}
    # steps 8 and 16 lie before the window; 40 and 48 hold the profiler (skipped); 24, 32, 56 count
    _telemetry(str(tmp_path / "telemetry.jsonl"), [(s, spans) for s in (8, 16, 24, 32, 40, 48, 56)])
    run = types.SimpleNamespace(
        log_dir=str(tmp_path), policy_step_open=16, policy_step_close=56, trace_steps=[32, 40], trace_dir=None,
        window=types.SimpleNamespace(cycle_iterations=2, env_steps_per_iteration=4),
    )
    read = ps.spans_of(run)
    assert read["windows"] == 3 and read["wall"] == 6.0 and read["spans"]["act"] == [6, pytest.approx(1.2), pytest.approx(1.2)]
    assert ps.span_share(run, "act") == pytest.approx(100 * 1.2 / 6.0)
    assert ps.span_share(run, "env_step") == pytest.approx(100 * 0.006 / 6.0)
    assert ps.span_ms_a_train_call(run, "replay_sample") == pytest.approx(3.0)
    assert ps.span_ms_a_train_call(run, "act_view.place") == pytest.approx(50.0)
    assert ps.span_share(run, "player_reset") is None  # a span that never ran is nothing, not 0
    assert ps.capture_of(run) is None and ps.idle_share(run, "act") is None


def test_windows_without_a_spans_block_read_as_nothing(tmp_path, capsys):
    _telemetry(str(tmp_path / "telemetry.jsonl"), [(s, None) for s in (8, 16, 24)])
    assert ps.window_spans(str(tmp_path), 0, 24) is None
    assert "carries a `spans` block" in capsys.readouterr().err


def _spans_jsonl(path, iterations, train_every, act_first=0.3, act_steady=0.02):
    """The loop's raw spans: an `act` every iteration, a train call with its `act_view`
    at the end of every `train_every`-th; the `act` after a view takes `act_first`."""
    at, fresh = 100.0, False
    with open(path, "w") as fh:
        for it in range(1, iterations + 1):
            length = act_first if fresh else act_steady
            length += 3.0 if it == 15 else 0.0  # one stalled step: medians, not means
            fh.write(json.dumps({"name": "act", "start": at, "end": at + length, "parent": "Time/env_interaction_time", "iter": it}) + "\n")
            at, fresh = at + length + 0.01, False
            if it % train_every == 0:
                fh.write(json.dumps({"name": "act_view", "start": at + 0.5, "end": at + 0.7, "parent": "Time/train_time", "iter": it}) + "\n")
                fh.write(json.dumps({"name": "act_view.fetch", "start": at + 0.5, "end": at + 0.68, "parent": "act_view", "iter": it}) + "\n")
                at, fresh = at + 0.8, True


@pytest.mark.parametrize("train_every, first, steady", [(2, 300.0, 20.0), (1, 300.0, None), (1000, None, 20.0)])
def test_act_spans_are_split_by_whether_a_view_came_before(tmp_path, capsys, train_every, first, steady):
    _spans_jsonl(str(tmp_path / "spans.jsonl"), 40, train_every)
    # iterations 11 to 30, less 19 to 22 (the profiler's): the window's first `act` (11)
    # follows the view of iteration 10, which lies outside the window
    read = ps.act_use_ms(str(tmp_path), 10, 30, skip=(18, 22))
    assert read["first"] == (pytest.approx(first) if first else None)
    assert read["steady"] == (pytest.approx(steady) if steady else None)
    assert read["n_first"] + read["n_steady"] == 16
    if train_every == 2:
        assert (read["n_first"], read["n_steady"]) == (8, 8)  # 11, 13, .. follow a view
    said = capsys.readouterr().err
    assert ("there is no steady `act`" in said) == (steady is None)
    assert ("there is no first use" in said) == (first is None)


@pytest.mark.parametrize("case", ["ring_dropped_the_start", "an_attempt_before"])
def test_act_spans_of_a_file_that_holds_less_or_more_than_the_run(tmp_path, capsys, case):
    path = str(tmp_path / "spans.jsonl")
    _spans_jsonl(path, 40, 2)
    rows = [json.loads(line) for line in open(path)]
    if case == "ring_dropped_the_start":  # the file begins inside the timed window: no partial median
        with open(path, "w") as fh:
            fh.writelines(json.dumps(r) + "\n" for r in rows if r["iter"] >= 14)
        assert ps.act_use_ms(str(tmp_path), 10, 30) is None
        assert "begins at iteration 14" in capsys.readouterr().err
    else:  # a restart appended its spans: the newest attempt's are the run's
        with open(path, "w") as fh:
            fh.writelines(json.dumps({**r, "end": r["end"] + 1.0, "attempt": 0}) + "\n" for r in rows)
            fh.writelines(json.dumps({**r, "attempt": 1}) + "\n" for r in rows)
        read = ps.act_use_ms(str(tmp_path), 10, 30, skip=(18, 22))
        assert (read["n_first"], read["n_steady"]) == (8, 8) and read["first"] == pytest.approx(300.0)


def test_act_spans_over_the_run_s_timed_window(tmp_path, capsys):
    _spans_jsonl(str(tmp_path / "spans.jsonl"), 40, 2)
    run = types.SimpleNamespace(
        log_dir=str(tmp_path), policy_step_open=40, policy_step_close=120, trace_steps=[72, 80],
        window=types.SimpleNamespace(cycle_iterations=2, env_steps_per_iteration=4),
    )
    # policy steps (40, 120] less (72, 88] are iterations 11 to 30 less 19 to 22
    assert ps.act_ms(run, "first") == pytest.approx(300.0) and ps.act_ms(run, "steady") == pytest.approx(20.0)
    assert run._program_act_use["n_first"] == 8
    empty = types.SimpleNamespace(log_dir=str(tmp_path / "none"), policy_step_open=0, policy_step_close=8, window=run.window)
    assert ps.act_ms(empty, "first") is None and "no " in capsys.readouterr().err  # a tree that writes no spans.jsonl


# ---------------------------------------------------------------------------------
# the capture recorded on the v5e (record.py beside it): 2 iterations of a toy loop with
# the program's timer spans around a jitted step with three named scopes
# ---------------------------------------------------------------------------------
def test_recorded_v5e_capture_pins_what_this_runtime_writes():
    capture = ps.load(RECORDED)
    assert capture is not None and list(capture.ops) == ["/device:TPU:0"]
    # the main thread's line is named after the process, `python3` here: none is named `python`
    profile = jax.profiler.ProfileData.from_file(glob.glob(os.path.join(RECORDED, "**", "*.xplane.pb"), recursive=True)[0])
    lines = {plane.name: [line.name for line in plane.lines] for plane in profile.planes}
    assert "python3" in lines["/host:CPU"] and "python" not in lines["/host:CPU"]
    assert set(capture.host) == ps.HOST_SPANS
    assert [len(capture.host[name]) for name in sorted(ps.HOST_SPANS)] == [2] * len(ps.HOST_SPANS)
    assert {name for name, _, _ in capture.modules["/device:TPU:0"]} >= {"jit_train_step"}
    # `tf_op` in the event metadata holds the name stack, scopes included, forward and backward
    assert capture.carrier == "xplane.pb event metadata"
    stacks = set(capture.scopes.values())
    assert all(s.endswith(":") for s in stacks)
    assert any("/jvp(rssm)/" in s for s in stacks) and any("/transpose(jvp(rssm))/" in s for s in stacks)
    assert {ps.scope_of(s) for s in stacks} >= {"encoder", "rssm", "optimizer"}
    # the trace.json.gz beside it carries the same names under `long_name` and `tf_op`
    assert ps.trace_json_scopes(RECORDED) == capture.scopes
    # ProfileData hands out an op's own stats, not its metadata's `tf_op`: hence the wire reader
    device = next(plane for plane in profile.planes if plane.name == "/device:TPU:0")
    own = {key for line in device.lines if line.name == "XLA Ops" for ev in line.events for key, _ in ev.stats}
    assert own and "tf_op" not in own


def test_recorded_v5e_capture_reads_through():
    capture = ps.load(RECORDED)
    lo, hi = ps.traced_cycles(capture)
    assert lo == capture.host[ps.CYCLE_START][0][0] and hi == capture.host[ps.CYCLE_END][-1][1]
    parts = ps.train_program_parts(capture)
    assert parts["steps"] == 2 and all(parts["seconds_a_step"][s] > 0 for s in ("encoder", "rssm", "optimizer"))
    assert 0 <= ps.unscoped_share(capture) < 50
    shares = ps.idle_shares(capture)
    assert 0 < shares["act"] < shares["idle"] < 100
    assert shares["act"] + shares["act_view"] + shares["unattributed"] == pytest.approx(shares["idle"])
    assert 0 < ps.act_view_sync_ms(capture) < 50
