"""Trace exporter round-trip (sheeprl_tpu/obs/trace.py): Perfetto-loadable
Chrome-trace JSON from recorded fixtures (old identity-less + new schema
events, 2 attempts, learner stream) and from a synthetic service-gang dir,
asserting cross-track flow-event pairing (ingest→sample, publish→refresh)."""

from __future__ import annotations

import json
import os

import pytest

from sheeprl_tpu.obs.trace import build_trace, main as trace_main, trace_run

pytestmark = pytest.mark.telemetry

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_RECORDED = os.path.join(_REPO, "tests", "data", "recorded_run")

_KNOWN_PH = {"X", "M", "C", "i", "s", "f"}


def _assert_perfetto_loadable(trace: dict) -> None:
    """The structural contract Perfetto/chrome://tracing require: a traceEvents
    list of known-phase events with numeric non-negative timestamps, complete
    events with durations, and flow endpoints that pair up by (cat, id)."""
    assert isinstance(trace, dict) and isinstance(trace["traceEvents"], list)
    assert trace["traceEvents"], "an empty trace renders nothing"
    starts, finishes = {}, {}
    for e in trace["traceEvents"]:
        assert e["ph"] in _KNOWN_PH, e
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert isinstance(e["name"], str) and e["name"]
        if e["ph"] != "M":
            assert isinstance(e["ts"], int) and e["ts"] >= 0, e
        if e["ph"] == "X":
            assert isinstance(e["dur"], int) and e["dur"] >= 1, e
        if e["ph"] == "s":
            starts[(e["cat"], e["id"])] = e
        if e["ph"] == "f":
            assert e.get("bp") == "e", "finish must bind to its enclosing slice"
            finishes[(e["cat"], e["id"])] = e
    assert set(starts) == set(finishes), "every flow start needs exactly one finish"
    # the JSON itself must round-trip (numpy leaks etc. would die here)
    json.loads(json.dumps(trace))


def test_trace_recorded_run_round_trip(tmp_path):
    """The PR 4 fixture: old identity-less events, 2 attempts, a learner
    stream — every stream gets its own thread track, windows become phase
    slices, and the output is Perfetto-loadable."""
    out = trace_run(_RECORDED, out_path=str(tmp_path / "trace.json"))
    with open(out) as fh:
        trace = json.load(fh)
    _assert_perfetto_loadable(trace)
    threads = {
        e["args"]["name"]
        for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert threads == {"rank0", "learner"}
    slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    # the first fixture window has no phases dict: one opaque "window" slice;
    # later windows carry attribution and become named phase slices
    assert {"window", "env", "train", "replay_wait"} <= {e["name"] for e in slices}
    # phase slices tile their window: widths sum to ~wall_seconds
    env_plus = sum(e["dur"] for e in slices if e["name"] != "window")
    assert env_plus > 0


def _write_stream(path, events):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        for e in events:
            fh.write(json.dumps(e) + "\n")


def _service_run_dir(tmp_path) -> str:
    """A synthetic 2-actor + learner service run: actor windows carry dataflow
    weight lag + cumulative rows, learner windows carry drained rows_per_actor
    and published versions — the shapes sac/dv3 `_service_*` roles emit."""
    base = str(tmp_path / "svc-run")
    t0 = 1_700_000_000.0

    def actor_events(rank, stream_rows, version_at):
        events = [
            {"event": "start", "time": t0, "rank": rank, "attempt": 0, "seq": 0, "every": 16}
        ]
        for i, rows in enumerate(stream_rows):
            events.append(
                {
                    "event": "window",
                    "time": t0 + 10.0 * (i + 1),
                    "rank": rank,
                    "attempt": 0,
                    "seq": i + 1,
                    "step": rows,
                    "window": i,
                    "final": False,
                    "wall_seconds": 10.0,
                    "sps": rows / (10.0 * (i + 1)),
                    "phases": {"env": 8.0, "train": 0.0, "logging": 0.5, "other": 1.5},
                    "dataflow": {
                        "role": "actor",
                        "weight_version": version_at(i),
                        "weight_latest": version_at(i) + 1,
                        "weight_lag": 1,
                        "rows": rows,
                        "messages": rows // 4,
                        "inflight": 0,
                        "flow_block_seconds": 0.0,
                    },
                }
            )
        return events

    def learner_events():
        events = [
            {"event": "start", "time": t0 + 0.5, "rank": 2, "attempt": 0, "seq": 0, "every": 16}
        ]
        for i in range(3):
            drained = {"0": 16 * (i + 1), "1": 16 * (i + 1)}
            events.append(
                {
                    "event": "window",
                    "time": t0 + 10.0 * (i + 1) + 2.0,
                    "rank": 2,
                    "attempt": 0,
                    "seq": i + 1,
                    "step": sum(drained.values()),
                    "window": i,
                    "final": False,
                    "wall_seconds": 10.0,
                    "sps": 3.2,
                    "phases": {"train": 6.0, "replay_wait": 1.0, "other": 3.0},
                    "dataflow": {
                        "role": "learner",
                        "weight_version": i + 1,
                        "weight_lag": {"per_actor": {"0": 0, "1": 1}, "max": 1, "mean": 0.5},
                        "row_age": {
                            "seconds": {"p50": 1.0, "p99": 4.0, "mean": 1.5, "max": 5.0},
                            "rounds": {"p50": 2.0, "p99": 6.0, "mean": 2.5, "max": 8.0},
                            "add_rounds": 8 * (i + 1),
                        },
                        "ingest_latency_ms": {"p50": 4.0, "p99": 15.0, "mean": 5.0, "max": 20.0},
                        "queue_depth": 0.2,
                        "queue_depth_max": 1,
                        "rows": sum(drained.values()),
                        "rows_per_actor": drained,
                        "rows_per_sec": 3.2,
                    },
                }
            )
        return events

    # actor windows land BEFORE the learner window that drains their rows;
    # actor 0 refreshes to version 1 at its second window (published at t+12)
    _write_stream(
        os.path.join(base, "telemetry.jsonl"),
        actor_events(0, [16, 32, 48], lambda i: 0 if i == 0 else 1),
    )
    _write_stream(
        os.path.join(base, "telemetry.actor1.jsonl"),
        actor_events(1, [16, 32, 48], lambda i: 0),
    )
    _write_stream(os.path.join(base, "telemetry.learner.jsonl"), learner_events())
    return base


def test_trace_service_run_emits_cross_track_flows(tmp_path):
    """The acceptance shape: flow events connect an actor's ingest span to the
    learner's sample span ACROSS process tracks, and a published weight version
    to the actor window that started acting with it."""
    base = _service_run_dir(tmp_path)
    trace = build_trace(base)
    _assert_perfetto_loadable(trace)

    tids = {
        (e["pid"], e["tid"]): e["args"]["name"]
        for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert set(tids.values()) == {"rank0", "actor1", "learner"}

    experience = [e for e in trace["traceEvents"] if e.get("cat") == "experience"]
    starts = [e for e in experience if e["ph"] == "s"]
    finishes = {(e["cat"], e["id"]): e for e in experience if e["ph"] == "f"}
    assert starts, "a service run must emit ingest→sample flows"
    for s in starts:
        f = finishes[(s["cat"], s["id"])]
        # start anchors on an actor track, finish on the learner track
        assert tids[(s["pid"], s["tid"])] in ("rank0", "actor1")
        assert tids[(f["pid"], f["tid"])] == "learner"
        assert f["ts"] >= s["ts"], "rows cannot be sampled before they were ingested"
    # BOTH actors' tracks feed the learner
    assert {tids[(s["pid"], s["tid"])] for s in starts} == {"rank0", "actor1"}

    # every flow endpoint anchors inside a thin marker slice on its track
    slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    ingest_tracks = {(e["pid"], e["tid"]) for e in slices if e["name"] == "ingest"}
    sample_tracks = {(e["pid"], e["tid"]) for e in slices if e["name"] == "sample"}
    assert {(s["pid"], s["tid"]) for s in starts} <= ingest_tracks
    assert {(f["pid"], f["tid"]) for f in finishes.values()} <= sample_tracks

    weights = [e for e in trace["traceEvents"] if e.get("cat") == "weights"]
    w_starts = [e for e in weights if e["ph"] == "s"]
    assert w_starts, "the refresh at actor window 2 must pair with version 1's publish"
    for s in w_starts:
        assert tids[(s["pid"], s["tid"])] == "learner"  # publish side


def test_trace_service_run_counts_and_cli(tmp_path):
    base = _service_run_dir(tmp_path)
    rc = trace_main([base, "--quiet"])
    assert rc == 0
    out = os.path.join(base, "trace.json")
    with open(out) as fh:
        _assert_perfetto_loadable(json.load(fh))
    # no stream -> exit 2, like diagnose/compare
    assert trace_main([str(tmp_path / "nowhere"), "--quiet"]) == 2


def test_trace_serve_stream_gets_session_counter_tracks(tmp_path):
    base = str(tmp_path / "serve-run")
    t0 = 1_700_000_100.0
    events = [{"event": "start", "time": t0, "serve": {"slots": 2}, "every": 4}]
    for i in range(3):
        events.append(
            {
                "event": "window",
                "time": t0 + 5.0 * (i + 1),
                "step": 4 * (i + 1),
                "window": i,
                "final": False,
                "wall_seconds": 5.0,
                "sps": 0.8,
                "phases": {"serve_step": 1.0, "serve_wait": 3.5, "other": 0.5},
                "serve": {
                    "latency_ms": {"p50": 1.0, "p99": 3.0, "mean": 1.2, "max": 4.0},
                    "occupancy": 0.75,
                    "sessions": {"active": 2, "started": 1, "finished": 0, "per_sec": 0.1},
                    "queue_depth": 1.0,
                    "ticks": 4,
                },
            }
        )
    _write_stream(os.path.join(base, "telemetry.jsonl"), events)
    trace = build_trace(base)
    _assert_perfetto_loadable(trace)
    slice_names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"serve_step", "serve_wait"} <= slice_names  # the batch-tick track
    counters = {e["name"] for e in trace["traceEvents"] if e["ph"] == "C"}
    assert {"sessions", "occupancy"} <= counters  # the session tracks


@pytest.mark.parametrize("recorded", ["spans", "phases_only"])
def test_trace_draws_real_spans_where_the_run_recorded_them(tmp_path, recorded):
    """A window with a `spans` block is one `window` slice and the stream's
    spans.jsonl is drawn as it happened: true starts, children inside parents, in
    order. Without the block (serving, runs from before the spans) the phase layout
    is still laid end to end."""
    base = str(tmp_path / "run")
    t0 = 1_700_000_200.0
    window = {
        "event": "window", "time": t0 + 1.0, "step": 8, "window": 0, "final": False, "wall_seconds": 1.0,
        "sps": 8.0, "phases": {"env": 0.3, "train": 0.6, "other": 0.1},
    }
    if recorded == "spans":
        window["spans"] = {"Time/env_interaction_time": [1, 0.3, 0.05], "act": [1, 0.25, 0.25],
                           "Time/train_time": [1, 0.6, 0.2], "act_view": [1, 0.4, 0.4]}
        window["counters"] = {"act_view_bytes": [1, 4096.0]}
    _write_stream(os.path.join(base, "telemetry.jsonl"), [{"event": "start", "time": t0, "every": 8}, window])
    rows = [  # written in the order the spans ENDED, as the ring holds them
        {"name": "act", "start": t0 + 0.05, "end": t0 + 0.30, "parent": "Time/env_interaction_time", "iter": 3},
        {"name": "Time/env_interaction_time", "start": t0 + 0.02, "end": t0 + 0.32, "parent": None, "iter": 3},
        {"name": "act_view", "start": t0 + 0.55, "end": t0 + 0.95, "parent": "Time/train_time", "iter": 3},
        {"name": "Time/train_time", "start": t0 + 0.35, "end": t0 + 0.95, "parent": None, "iter": 3},
    ]
    if recorded == "spans":
        with open(os.path.join(base, "spans.jsonl"), "w") as fh:
            fh.writelines(json.dumps(r) + "\n" for r in rows)
    trace = build_trace(base)
    _assert_perfetto_loadable(trace)
    slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in slices]
    if recorded == "phases_only":
        assert names == ["env", "train", "other"]  # the made-up layout, for streams without spans
        return
    assert not {"env", "train", "other"} & set(names)  # no slice order is made up
    drawn = {e["name"]: e for e in slices}
    assert drawn["window"]["dur"] == 1_000_000 and drawn["window"]["args"]["spans"]["act"] == [1, 0.25, 0.25]
    assert drawn["window"]["args"]["counters"] == {"act_view_bytes": [1, 4096.0]}
    spans = [e for e in slices if e["cat"] == "span"]
    assert [e["name"] for e in spans] == ["Time/env_interaction_time", "act", "Time/train_time", "act_view"]
    assert [(e["ts"], e["dur"]) for e in spans] == [(20_000, 300_000), (50_000, 250_000), (350_000, 600_000), (550_000, 400_000)]
    for child, parent in (("act", "Time/env_interaction_time"), ("act_view", "Time/train_time")):
        c, p = drawn[child], drawn[parent]
        assert p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"] and c["args"]["parent"] == parent
