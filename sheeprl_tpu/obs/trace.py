"""``python sheeprl.py trace <run_dir|fleet_dir>`` — telemetry → Perfetto trace.

``diagnose`` answers "what is wrong", ``watch`` answers "what is happening";
this module answers "where does a row's wall time GO" by converting the
k-way-merged telemetry streams (``obs/streams.py``) into a Chrome-trace-format
JSON that Perfetto (https://ui.perfetto.dev) and ``chrome://tracing`` load
directly. Nothing new is measured: every span is reconstructed from events the
run already wrote.

Track layout (Chrome trace ``pid``/``tid`` = process/thread rows):

- one **process track per fleet member** (plus one for the fleet runner's own
  stream) when pointed at a fleet dir; a plain run dir is one process;
- one **thread track per telemetry stream** — the rank-0 player/controller,
  each ``telemetry.actor<r>.jsonl``, the learner role stream — so a service
  gang renders as parallel actor/learner timelines;
- a run that recorded **real spans** (``utils/timer.py``: windows carry a
  ``spans`` block and the stream has a ``spans*.jsonl`` beside it, the run's
  last spans with their start, end, parent and iteration) draws those: each
  window is one ``window`` slice whose args hold its span totals, and the raw
  spans sit on the same track, nested and in their true order;
- a stream without a ``spans`` block (serving, runs from before the spans) gets
  its windows' **phase attribution** as a run of slices laid end-to-end across
  the window's wall span (env → rollout → replay_wait → train → …): shares,
  not ordering; inside one window the layout order is fixed, the widths exact;
- **serving runs** get the same treatment for their batch-tick phases
  (``serve_step`` / ``serve_wait``) plus counter tracks for the session state
  (active sessions, admission queue depth, batch occupancy);
- **flow events** stitch the dataflow lineage across tracks: an actor's
  ingested rows to the learner window that had drained them
  (``ingest→sample``), and the learner's published weight version to the first
  actor window acting with it (``publish→refresh``). Flows ride the
  ``dataflow`` blocks (``data/service.py``), so they appear exactly on
  ``buffer.backend=service`` runs.

Timestamps are wall-clock microseconds relative to the earliest event, so the
alignment caveat of the stream merge applies unchanged (single-host clock).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

__all__ = ["build_trace", "main", "trace_run"]

# fixed within-window layout order for the phase slices (a superset of
# telemetry._PHASE_TIMERS plus the derived/serving phases)
_PHASE_ORDER = (
    "env",
    "rollout",
    "replay_wait",
    "train",
    "serve_step",
    "serve_wait",
    "checkpoint",
    "logging",
    "eval",
    "analysis",
    "other",
)
_MIN_SLICE_S = 1e-4  # drop sub-0.1ms phase slivers: noise, not signal
_MARKER_DUR_US = 1000  # thin anchor slices for flow endpoints (1 ms)


def _f(value: Any) -> float:
    try:
        return float(value or 0.0)
    except (TypeError, ValueError):
        return 0.0


class _TraceBuilder:
    def __init__(self) -> None:
        self.events: List[Dict[str, Any]] = []
        self._pids: Dict[str, int] = {}
        self._tids: Dict[Tuple[int, str], int] = {}
        self._flow_ids: Dict[Tuple[str, str], int] = {}
        self.t0: Optional[float] = None

    def us(self, wall: float) -> int:
        base = self.t0 if self.t0 is not None else wall
        return max(int(round((wall - base) * 1e6)), 0)

    def pid(self, name: str) -> int:
        if name not in self._pids:
            self._pids[name] = len(self._pids) + 1
            self.events.append(
                {
                    "ph": "M",
                    "name": "process_name",
                    "pid": self._pids[name],
                    "tid": 0,
                    "args": {"name": name},
                }
            )
        return self._pids[name]

    def tid(self, pid: int, name: str) -> int:
        key = (pid, name)
        if key not in self._tids:
            self._tids[key] = sum(1 for p, _ in self._tids if p == pid) + 1
            self.events.append(
                {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": pid,
                    "tid": self._tids[key],
                    "args": {"name": name},
                }
            )
        return self._tids[key]

    def slice(self, pid: int, tid: int, name: str, ts_us: int, dur_us: int, args: Optional[Dict] = None, cat: str = "phase") -> None:
        event = {
            "ph": "X",
            "name": name,
            "cat": cat,
            "pid": pid,
            "tid": tid,
            "ts": ts_us,
            "dur": max(int(dur_us), 1),
        }
        if args:
            event["args"] = args
        self.events.append(event)

    def counter(self, pid: int, name: str, ts_us: int, values: Dict[str, float]) -> None:
        self.events.append(
            {"ph": "C", "name": name, "pid": pid, "tid": 0, "ts": ts_us, "args": values}
        )

    def flow_id(self, cat: str, key: str) -> int:
        pair = (cat, key)
        if pair not in self._flow_ids:
            self._flow_ids[pair] = len(self._flow_ids) + 1
        return self._flow_ids[pair]

    def flow(self, phase: str, cat: str, key: str, name: str, pid: int, tid: int, ts_us: int) -> None:
        event = {
            "ph": phase,  # "s" start | "f" finish
            "id": self.flow_id(cat, key),
            "cat": cat,
            "name": name,
            "pid": pid,
            "tid": tid,
            "ts": ts_us,
        }
        if phase == "f":
            event["bp"] = "e"  # bind to the enclosing slice, Perfetto-style
        self.events.append(event)


def _stream_thread_name(label: str) -> str:
    base = os.path.basename(str(label))
    if base == "telemetry.jsonl":
        return "rank0"
    if base.startswith("telemetry.") and base.endswith(".jsonl"):
        return base[len("telemetry.") : -len(".jsonl")]
    return base


def _window_spans(window: Mapping[str, Any]) -> List[Tuple[str, float]]:
    """The window's phase layout as (name, seconds) in fixed order; a window
    without a phases dict (pre-attribution recordings) is one opaque span."""
    phases = window.get("phases")
    wall = _f(window.get("wall_seconds"))
    if not isinstance(phases, Mapping):
        return [("window", wall)] if wall > 0 else []
    spans = [
        (name, _f(phases.get(name)))
        for name in _PHASE_ORDER
        if _f(phases.get(name)) >= _MIN_SLICE_S
    ]
    # phases the order list does not know yet still render (schema drift shows
    # up as an oddly-named slice, not as silently-vanished wall time)
    known = set(_PHASE_ORDER)
    spans.extend(
        (str(name), _f(value))
        for name, value in phases.items()
        if name not in known and _f(value) >= _MIN_SLICE_S
    )
    return spans


def _emit_window(tb: _TraceBuilder, pid: int, tid: int, window: Mapping[str, Any]) -> None:
    t_end = _f(window.get("time"))
    wall = _f(window.get("wall_seconds"))
    if t_end <= 0 or wall <= 0:
        return
    start = t_end - wall
    args = {
        "window": window.get("window"),
        "step": window.get("step"),
        "sps": window.get("sps"),
    }
    if window.get("mfu") is not None:
        args["mfu"] = window.get("mfu")
    if window.get("spans"):
        # the run recorded real spans: no layout is made up. The window is one
        # slice carrying its totals; the raw spans are drawn by _emit_real_spans
        args["spans"] = window["spans"]
        if window.get("counters"):
            args["counters"] = window["counters"]
        tb.slice(pid, tid, "window", tb.us(start), int(wall * 1e6), args=args, cat="window")
    else:
        cursor = start
        for name, seconds in _window_spans(window):
            tb.slice(pid, tid, name, tb.us(cursor), int(seconds * 1e6), args=args)
            cursor += seconds
    if window.get("sps") is not None:
        tb.counter(pid, "sps", tb.us(t_end), {"sps": _f(window.get("sps"))})
    serve = window.get("serve")
    if isinstance(serve, Mapping):
        # the session tracks of a serving run: admission/occupancy state per
        # batch-tick window (the phase slices above are the tick timeline)
        sessions = serve.get("sessions") or {}
        tb.counter(
            pid,
            "sessions",
            tb.us(t_end),
            {"active": _f(sessions.get("active")), "queue": _f(serve.get("queue_depth"))},
        )
        if serve.get("occupancy") is not None:
            tb.counter(pid, "occupancy", tb.us(t_end), {"occupancy": _f(serve.get("occupancy"))})


def _spans_beside(stream_path: str) -> List[Dict[str, Any]]:
    """The raw spans ``RunTelemetry.close`` wrote beside a stream
    (``telemetry.x.jsonl`` -> ``spans.x.jsonl``); none for a stream without."""
    from sheeprl_tpu.obs.jsonl import spans_path

    path = spans_path(stream_path)
    if not os.path.isfile(path):
        return []
    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return [r for r in rows if _f(r.get("end")) >= _f(r.get("start")) > 0]


def _emit_real_spans(tb: _TraceBuilder, pid: int, tid: int, spans: Sequence[Mapping[str, Any]]) -> None:
    """Real spans at their own start and length, parents before children so that
    viewers nest them (a child lies inside its parent on the recording clock)."""
    for span in sorted(spans, key=lambda r: (_f(r["start"]), -_f(r["end"]))):
        tb.slice(
            pid, tid, str(span["name"]), tb.us(_f(span["start"])),
            int(round((_f(span["end"]) - _f(span["start"])) * 1e6)),
            args={"iter": span.get("iter"), "parent": span.get("parent")}, cat="span",
        )


def _emit_dataflow_flows(
    tb: _TraceBuilder,
    windows: Sequence[Tuple[int, int, Dict[str, Any]]],
) -> None:
    """Cross-track lineage flows from the windows' ``dataflow`` blocks.

    ``ingest→sample``: an actor window reporting cumulative ingested rows R
    starts a flow that finishes at the FIRST learner window whose per-actor
    drained row count reaches R — the span of time those rows sat between env
    and buffer. ``publish→refresh``: the first learner window reporting
    published version V starts a flow finishing at the first actor window
    ACTING with V. Unmatched starts are dropped (never half-emitted)."""
    actor_rows: List[Tuple[int, int, int, float, int]] = []  # rank, rows, pid, time, tid
    learner_windows: List[Tuple[int, int, float, Dict[str, Any]]] = []
    actor_first_version: Dict[int, List[Tuple[int, int, int, float]]] = {}
    for pid, tid, w in windows:
        df = w.get("dataflow")
        if not isinstance(df, Mapping):
            continue
        t = _f(w.get("time"))
        if df.get("role") == "actor":
            rank = int(w.get("rank") or 0)
            actor_rows.append((rank, int(_f(df.get("rows"))), pid, t, tid))
            actor_first_version.setdefault(rank, []).append(
                (int(_f(df.get("weight_version"))), pid, tid, t)
            )
        elif df.get("role") == "learner":
            learner_windows.append((pid, tid, t, dict(df)))
    if not learner_windows:
        return
    learner_windows.sort(key=lambda item: item[2])

    # ingest → sample
    pending = sorted(actor_rows, key=lambda item: item[3])
    seen_rows: set = set()
    for rank, rows, a_pid, a_time, a_tid in pending:
        if rows <= 0 or (rank, rows) in seen_rows:
            continue  # an idle window (no new rows) must not duplicate a flow id
        seen_rows.add((rank, rows))
        match = None
        for l_pid, l_tid, l_time, df in learner_windows:
            drained = df.get("rows_per_actor") or {}
            if l_time >= a_time and _f(drained.get(str(rank))) >= rows:
                match = (l_pid, l_tid, l_time)
                break
        if match is None:
            continue
        key = f"rows-r{rank}-{rows}"
        ts_a = tb.us(a_time)
        tb.slice(a_pid, a_tid, "ingest", ts_a, _MARKER_DUR_US, args={"rows": rows, "rank": rank}, cat="dataflow")
        tb.flow("s", "experience", key, "ingest→sample", a_pid, a_tid, ts_a)
        l_pid, l_tid, l_time = match
        ts_l = tb.us(l_time)
        tb.slice(l_pid, l_tid, "sample", ts_l, _MARKER_DUR_US, args={"rows": rows, "rank": rank}, cat="dataflow")
        tb.flow("f", "experience", key, "ingest→sample", l_pid, l_tid, ts_l)

    # publish → refresh
    for rank, held in actor_first_version.items():
        held.sort(key=lambda item: item[3])
        seen: set = set()
        for version, a_pid, a_tid, a_time in held:
            if version <= 0 or version in seen:
                continue
            seen.add(version)
            publish = next(
                (
                    (l_pid, l_tid, l_time)
                    for l_pid, l_tid, l_time, df in learner_windows
                    if int(_f(df.get("weight_version"))) >= version and l_time <= a_time
                ),
                None,
            )
            if publish is None:
                continue
            key = f"w{version}-r{rank}"
            l_pid, l_tid, l_time = publish
            ts_l = tb.us(l_time)
            tb.slice(l_pid, l_tid, "publish", ts_l, _MARKER_DUR_US, args={"version": version}, cat="weights")
            tb.flow("s", "weights", key, "publish→refresh", l_pid, l_tid, ts_l)
            ts_a = tb.us(a_time)
            tb.slice(a_pid, a_tid, "refresh", ts_a, _MARKER_DUR_US, args={"version": version, "rank": rank}, cat="weights")
            tb.flow("f", "weights", key, "publish→refresh", a_pid, a_tid, ts_a)


def _emit_instants(tb: _TraceBuilder, pid: int, tid: int, event: Mapping[str, Any]) -> None:
    """Lifecycle markers: health/preempt/restart/service events render as
    instants so the phase timeline carries its operational context."""
    kind = event.get("event")
    t = _f(event.get("time"))
    if t <= 0:
        return
    name = None
    args: Dict[str, Any] = {}
    if kind == "health" and event.get("status") not in (None, "ok"):
        name = f"health:{event.get('status')}"
    elif kind in ("preempt", "restart", "resume", "giveup"):
        name = str(kind)
        if event.get("reason"):
            args["reason"] = event.get("reason")
    elif kind == "service":
        name = f"service:{event.get('role')}"
        args = {
            k: event.get(k)
            for k in ("rows", "gradient_steps", "weight_version", "queue_depth_mean")
            if event.get(k) is not None
        }
    elif kind == "reload":
        # the flywheel's visible heartbeat: each applied hot swap marks the
        # serving track at the moment a published version went live
        name = f"reload:{event.get('status')}"
        args = {
            k: event.get(k)
            for k in ("version", "available", "reloads", "reason", "source")
            if event.get(k) is not None
        }
    elif kind == "drain":
        name = f"drain:{event.get('status')}"
        args = {
            k: event.get(k)
            for k in ("shed", "aborted", "grace_s")
            if event.get(k) is not None
        }
    elif kind == "live":
        name = f"live:{event.get('status')}"
        args = {
            k: event.get(k)
            for k in ("servers", "sessions", "reloads", "error")
            if event.get(k) is not None
        }
    elif kind == "ingest":
        name = "ingest"
        args = {
            k: event.get(k)
            for k in (
                "rank",
                "trajectories_captured",
                "trajectories_ingested",
                "trajectories_dropped",
                "trajectory_rows",
                "weight_version",
            )
            if event.get(k) is not None
        }
    elif kind == "alert" and event.get("status") in ("firing", "resolved"):
        # SLO alert lifecycle on the timeline: pending transitions are noise
        # at trace zoom, firing/resolved mark the incident's span ends
        name = f"alert:{event.get('status')}:{event.get('name')}"
        args = {
            k: event.get(k)
            for k in ("severity", "value", "target", "budget_remaining", "burn_fast")
            if event.get(k) is not None
        }
    elif kind == "promotion":
        name = f"promotion:{event.get('verdict')}"
        args = {
            k: event.get(k)
            for k in ("version", "baseline", "samples", "reason")
            if event.get(k) is not None
        }
    if name is None:
        return
    tb.events.append(
        {
            "ph": "i",
            "name": name,
            "cat": "lifecycle",
            "s": "t",  # thread-scoped instant
            "pid": pid,
            "tid": tid,
            "ts": tb.us(t),
            "args": args,
        }
    )


def build_trace(run_dir: str) -> Dict[str, Any]:
    """The Chrome-trace JSON object for a run dir, fleet dir, or single
    ``telemetry*.jsonl`` file. Raises ``FileNotFoundError`` when no stream
    exists (the caller maps it to exit 2, like diagnose/compare)."""
    from sheeprl_tpu.obs.streams import (
        discover_streams,
        fleet_members,
        load_stream,
        member_of,
        merge_streams,
    )

    streams = discover_streams(run_dir)
    if not streams:
        raise FileNotFoundError(f"no telemetry*.jsonl stream found under {run_dir!r}")
    base = run_dir if os.path.isdir(run_dir) else os.path.dirname(run_dir)
    events = merge_streams([load_stream(p, base_dir=base) for p in streams])
    real_spans = {os.path.relpath(p, base): _spans_beside(p) for p in streams}

    tb = _TraceBuilder()
    times = [_f(e.get("time")) for e in events if _f(e.get("time")) > 0]
    times += [_f(r["start"]) for rows in real_spans.values() for r in rows]
    if times:
        # anchor at the earliest WINDOW START (window stamps mark the end)
        starts = [
            _f(e.get("time")) - _f(e.get("wall_seconds"))
            for e in events
            if e.get("event") == "window" and _f(e.get("time")) > 0
        ]
        tb.t0 = min(times + [t for t in starts if t > 0])

    members = fleet_members(run_dir)
    run_label = os.path.basename(os.path.normpath(str(run_dir))) or str(run_dir)

    def track_of(event: Mapping[str, Any]) -> Tuple[int, int]:
        stream = str(event.get("stream") or "telemetry.jsonl")
        if members is not None:
            member = member_of(stream)
            pid = tb.pid(f"member:{member}" if member else f"fleet:{run_label}")
        else:
            pid = tb.pid(run_label)
        return pid, tb.tid(pid, _stream_thread_name(stream))

    window_tracks: List[Tuple[int, int, Dict[str, Any]]] = []
    for event in events:
        pid, tid = track_of(event)
        kind = event.get("event")
        if kind == "window":
            _emit_window(tb, pid, tid, event)
            window_tracks.append((pid, tid, event))
        else:
            _emit_instants(tb, pid, tid, event)
    for stream, spans in real_spans.items():
        if spans:
            _emit_real_spans(tb, *track_of({"stream": stream}), spans)
    _emit_dataflow_flows(tb, window_tracks)

    return {
        "traceEvents": tb.events,
        "displayTimeUnit": "ms",
        "otherData": {
            "source": str(run_dir),
            "streams": [os.path.relpath(p, base) for p in streams],
            "tool": "sheeprl.py trace",
        },
    }


def _write_trace(trace: Dict[str, Any], run_dir: str, out_path: Optional[str]) -> str:
    base = run_dir if os.path.isdir(run_dir) else os.path.dirname(run_dir)
    out = out_path or os.path.join(base, "trace.json")
    with open(out, "w") as fh:
        json.dump(trace, fh)
        fh.write("\n")
    return out


def trace_run(run_dir: str, out_path: Optional[str] = None) -> str:
    """Build and write the trace JSON (default ``<run_dir>/trace.json``);
    returns the written path."""
    return _write_trace(build_trace(run_dir), run_dir, out_path)


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py trace <run_dir|fleet_dir>``: write a Perfetto-loadable
    trace JSON next to the streams (exit 2 when no stream exists)."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="sheeprl.py trace",
        description="Convert a run's telemetry.jsonl stream(s) into a Chrome-trace/"
        "Perfetto JSON: one track per member/rank/role, phase spans per window, "
        "flow events linking ingest→sample and publish→refresh across tracks. "
        "Open the output at https://ui.perfetto.dev or chrome://tracing.",
    )
    parser.add_argument("run_dir", help="run dir, fleet dir, or a telemetry*.jsonl file")
    parser.add_argument("--out", default=None, help="output path (default: <run_dir>/trace.json)")
    parser.add_argument("--quiet", action="store_true", help="suppress the summary line")
    args = parser.parse_args(list(argv) if argv is not None else sys.argv[1:])
    try:
        trace = build_trace(args.run_dir)
        out = _write_trace(trace, args.run_dir, args.out)
    except FileNotFoundError as exc:
        print(f"trace: {exc}", file=sys.stderr)
        return 2
    if not args.quiet:
        events = trace["traceEvents"]
        flows = sum(1 for e in events if e.get("ph") in ("s", "f"))
        print(
            f"wrote {out} ({len(events)} trace event(s), {flows} flow endpoint(s)) — "
            "open it at https://ui.perfetto.dev"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
