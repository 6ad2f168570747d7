"""What the program says about itself, for the per-layer readers that PR 27 added:

(a) the program's own spans on the capture's clock. `sheeprl_tpu/utils/timer.py` opens
    a `jax.profiler.TraceAnnotation` for every span, so a capture holds them on the
    host plane beside the device ops. They are looked for on EVERY line of
    `/host:CPU`: the main thread's line is named after the process (`python`,
    `python3`), not after anything the program chose;
(b) the device ops with the JAX name stack they were lowered from (`tf_op`, which
    holds the `jax.named_scope` names of `make_train_phase`), read from the
    `.xplane.pb`'s event metadata by a reader of the protobuf wire format that needs
    no generated module (`jax.profiler.ProfileData` hands out an event's own stats,
    the device offsets, and not its metadata's: a test on the recorded capture pins
    that), or else from the `trace.json.gz` beside it (`long_name` -> `tf_op`), which
    may be cut at a million events;
(c) `window.spans` of the run's `telemetry.jsonl`, over the windows
    `bench.py::telemetry_phases` sums, and the raw spans of the `spans.jsonl` beside
    it, over the iterations of the same windows.

Every reader returns None, and says why on stderr, where it finds nothing to read:
a program without spans or scopes (the parent of PR 27) reads as nothing, never as
a default label. Nothing here is imported by the harness: the metric files call it.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys
import statistics
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

CYCLE_START = "Time/env_interaction_time"  # first span of an iteration of the loop
CYCLE_END = "Time/train_time"  # last span of a cycle: a cycle ends with its train call
SCOPES = ("encoder", "rssm", "decoder", "heads", "imagine", "actor", "critic", "optimizer")
TRAIN_MODULE = "train_step"
HOST_SPANS = {CYCLE_START, CYCLE_END, "act", "act_view", "act_view.fetch"}  # what the readers use
Interval = Tuple[float, float]


def log(*parts) -> None:
    print("[perfbench] program_spans:", *parts, file=sys.stderr, flush=True)


@dataclass
class ProgramCapture:
    host: Dict[str, List[Interval]] = field(default_factory=dict)  # span name -> [(start, end)]
    ops: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)  # track -> (text, start, end)
    modules: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)
    scopes: Dict[str, str] = field(default_factory=dict)  # an op's HLO text -> its name stack
    carrier: str = "none"  # where `scopes` came from
    memo: dict = field(default_factory=dict)  # a reading of the whole capture is made once


# ---------------------------------------------------------------------------------
# the capture
# ---------------------------------------------------------------------------------
def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint, a (start,
    end) pair for a length-delimited field, None for the fixed-width ones."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire in (1, 5):
            value, pos = None, pos + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield key >> 3, value


def xplane_scopes(path: str) -> Dict[str, str]:
    """{HLO text: name stack} from the device planes' event metadata (XSpace.planes ->
    XPlane.event_metadata -> XEventMetadata.name, .stats[`tf_op`]). The lines, which
    hold the bulk of the file, are stepped over."""
    with open(path, "rb") as fh:
        buf = fh.read()
    out: Dict[str, str] = {}
    for number, span in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, metadata, stat_names = "", [], {}
        for f, v in _fields(buf, *span):
            if f == 2:
                name = buf[v[0]:v[1]].decode()
            elif f in (4, 5):  # map entries: key = 1, value = 2
                entry = dict(_fields(buf, *v))
                if 2 not in entry:
                    continue
                if f == 4:
                    metadata.append(entry[2])
                else:
                    inner = dict(_fields(buf, *entry[2]))
                    if 2 in inner:
                        stat_names[entry.get(1, inner.get(1))] = buf[inner[2][0]:inner[2][1]].decode()
        if not name.startswith("/device:"):
            continue
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        for span_md in metadata:
            text, stack = "", None
            for f, v in _fields(buf, *span_md):
                if f == 2:
                    text = buf[v[0]:v[1]].decode()
                elif f == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) == tf_op:
                        stack = buf[stat[5][0]:stat[5][1]].decode() if 5 in stat else stat_names.get(stat.get(7))
            if text and stack:
                out[text] = stack
    return out


def trace_json_scopes(root: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for path in sorted(glob.glob(os.path.join(root, "**", "*.trace.json.gz"), recursive=True)):
        with gzip.open(path, "rt") as fh:
            events = json.load(fh).get("traceEvents") or []
        for ev in events:
            args = ev.get("args") if isinstance(ev, dict) else None
            if isinstance(args, dict) and args.get("tf_op") and args.get("long_name"):
                out[str(args["long_name"])] = str(args["tf_op"])
    return out


def load(trace_dir: str) -> Optional[ProgramCapture]:
    """The newest capture under `trace_dir`; of the host's events those named in
    HOST_SPANS, from whatever line they are on."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        log(f"no .xplane.pb under {trace_dir}")
        return None
    capture = ProgramCapture()
    profile = jax.profiler.ProfileData.from_file(files[-1])
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    capture.ops[plane.name] = [
                        (ev.name, ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9) for ev in line.events
                    ]
                elif line.name == "XLA Modules":
                    capture.modules[plane.name] = [
                        (ev.name.split("(")[0], ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9)
                        for ev in line.events
                    ]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name in HOST_SPANS:
                        start = ev.start_ns / 1e9
                        capture.host.setdefault(name, []).append((start, start + ev.duration_ns / 1e9))
    for spans in capture.host.values():
        spans.sort()
    try:
        capture.scopes, capture.carrier = xplane_scopes(files[-1]), "xplane.pb event metadata"
    except (ValueError, IndexError, KeyError, UnicodeDecodeError) as err:
        log(f"the .xplane.pb's event metadata could not be read ({err!r}); trying trace.json.gz")
    if not capture.scopes:
        capture.scopes, capture.carrier = trace_json_scopes(os.path.dirname(files[-1])), "trace.json.gz"
    return capture


# ---------------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------------
def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        elif hi > lo:
            out.append((lo, hi))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def complement(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for a, b in union(clip(intervals, lo, hi)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Seconds that two unions of intervals share."""
    a, b = union(a), union(b)
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]), 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


# ---------------------------------------------------------------------------------
# readings from a capture
# ---------------------------------------------------------------------------------
def traced_cycles(capture: ProgramCapture) -> Optional[Interval]:
    """The traced whole cycles, from the program's own spans: from the start of the
    first iteration that began inside the capture to the end of the last train call
    that ended inside it. Never first-op-to-last-op."""
    starts, ends = capture.host.get(CYCLE_START), capture.host.get(CYCLE_END)
    if not starts or not ends:
        log(f"no `{CYCLE_START}` / `{CYCLE_END}` span on any line of /host:CPU: the program opens no "
            "TraceAnnotation (a tree before PR 27), or the capture lost them")
        return None
    lo, hi = starts[0][0], ends[-1][1]
    return (lo, hi) if hi > lo else None


def scope_of(stack: str) -> Optional[str]:
    """The innermost of SCOPES on a name stack such as
    `jit(train_step)/jit(main)/transpose(jvp(rssm))/while/body/dot_general:`."""
    for part in reversed(stack.split("/")):
        while part.endswith(")") and "(" in part:  # transpose(jvp(rssm)) -> rssm
            part = part[part.index("(") + 1:-1]
        if part in SCOPES:
            return part
    return None


def leaf_ops(ops: List[Tuple[str, float, float]]) -> List[Tuple[str, float, float]]:
    """The ops that hold no other op (a `while` spans its body's ops, on one line)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [op for i, op in enumerate(ops) if i + 1 == len(ops) or ops[i + 1][1] >= op[2] or op[2] <= op[1]]


def _once(reading):
    """Make a reading of a whole capture once, however many metrics ask for it."""
    def read(capture: ProgramCapture):
        if reading.__name__ not in capture.memo:
            capture.memo[reading.__name__] = reading(capture)
        return capture.memo[reading.__name__]
    read.__doc__ = reading.__doc__
    return read


@_once
def train_program_parts(capture: ProgramCapture) -> Optional[dict]:
    """Leaf-op seconds of one gradient step by scope (`None`: under none of them),
    over the whole executions of the train program inside the traced cycles."""
    window = traced_cycles(capture)
    if window is None:
        return None
    seconds: Dict[Optional[str], float] = {}
    steps = tracks = 0
    for track, ops in capture.ops.items():
        runs = [(a, b) for name, a, b in capture.modules.get(track, ())
                if TRAIN_MODULE in name and a >= window[0] and b <= window[1]]
        if not runs:
            continue
        tracks, steps = tracks + 1, steps + len(runs)
        ends = [b for _, b in runs]
        at = 0
        for text, a, b in leaf_ops(ops):  # ordered by start, as `runs` are
            while at < len(runs) and ends[at] <= a:
                at += 1
            if at == len(runs):
                break
            if a >= runs[at][0] and b <= runs[at][1]:
                scope = scope_of(capture.scopes.get(text, ""))
                seconds[scope] = seconds.get(scope, 0.0) + (b - a)
    if not steps:
        log(f"no whole `{TRAIN_MODULE}` execution inside the traced cycles")
        return None
    if not any(seconds.get(s) for s in SCOPES):
        log(f"no op of `{TRAIN_MODULE}` carries one of {SCOPES} on its name stack ({capture.carrier}, "
            f"{len(capture.scopes)} named ops): a tree before PR 27, or an executable that another tree compiled")
        return None
    return {"seconds_a_step": {k: v / steps for k, v in seconds.items()}, "steps": steps / tracks}


def part_ms(capture: ProgramCapture, *scopes: str) -> Optional[float]:
    parts = train_program_parts(capture)
    return None if parts is None else 1e3 * sum(parts["seconds_a_step"].get(s, 0.0) for s in scopes)


def unscoped_share(capture: ProgramCapture) -> Optional[float]:
    parts = train_program_parts(capture)
    if parts is None:
        return None
    return 100.0 * parts["seconds_a_step"].get(None, 0.0) / sum(parts["seconds_a_step"].values())


@_once
def idle_shares(capture: ProgramCapture) -> Optional[dict]:
    """Device-idle time over the traced whole cycles, as a share of them, split by
    what the host was inside: an `act_view` span, an `act` span, neither."""
    window = traced_cycles(capture)
    tracks = [ops for ops in capture.ops.values() if ops]
    if window is None or not tracks:
        return None
    out = {"act_view": 0.0, "act": 0.0, "idle": 0.0}
    for ops in tracks:
        idle = complement([(a, b) for _, a, b in ops], *window)
        out["idle"] += sum(b - a for a, b in idle)
        for name in ("act_view", "act"):
            out[name] += overlap(idle, capture.host.get(name, []))
    scale = 100.0 / (len(tracks) * (window[1] - window[0]))
    out = {k: v * scale for k, v in out.items()}
    out["unattributed"] = out["idle"] - out["act_view"] - out["act"]
    return out


def act_view_sync_ms(capture: ProgramCapture) -> Optional[float]:
    """Per train call: from the end of the call's last train-program execution on the
    device to the end of the host's `act_view.fetch` (pack on the device, wait, copy)."""
    window = traced_cycles(capture)
    fetches = clip(capture.host.get("act_view.fetch", []), *window) if window else []
    runs = sorted(b for mods in capture.modules.values() for name, _, b in mods if TRAIN_MODULE in name)
    if not fetches or not runs:
        if window:
            log("no `act_view.fetch` span or no train-program execution inside the traced cycles")
        return None
    waits, last = [], -float("inf")
    for start, end in fetches:
        ended = [b for b in runs if last < b <= end]
        if ended:
            waits.append(end - ended[-1])
        last = end
    return 1e3 * sum(waits) / len(waits) if waits else None


# ---------------------------------------------------------------------------------
# readings from telemetry.jsonl
# ---------------------------------------------------------------------------------
def window_spans(log_dir: str, lo: int, hi: int, skip=(0, 0)) -> Optional[dict]:
    """`window.spans` summed over the telemetry windows that `bench.py::telemetry_phases`
    sums (policy steps in `(lo, hi]`, not in `skip`)."""
    path = os.path.join(log_dir, "telemetry.jsonl")
    if not os.path.exists(path):
        log(f"no {path}")
        return None
    spans: Dict[str, List[float]] = {}
    wall, windows = 0.0, 0
    with open(path) as fh:
        for line in fh:
            event = json.loads(line)
            step = event.get("step")
            if event.get("event") != "window" or step is None or not event.get("spans"):
                continue
            if not lo < step <= hi or skip[0] < step <= skip[1]:
                continue
            for name, values in event["spans"].items():
                have = spans.setdefault(name, [0.0] * len(values))
                for i, v in enumerate(values):
                    have[i] += v
            wall += float(event["wall_seconds"])
            windows += 1
    if not windows:
        log("no telemetry window of the timed window carries a `spans` block: the program records none "
            "(a tree before PR 27)")
        return None
    return {"spans": spans, "wall": wall, "windows": windows}


def act_use_ms(log_dir: str, lo: float, hi: float, skip=(0.0, 0.0)) -> Optional[dict]:
    """Median length of the loop's `act` spans in `spans.jsonl`, of those that are the
    first after an `act_view` (`first`: the new parameters' first use) and of all others
    (`steady`, None where every `act` follows a view), over the iterations in `(lo, hi]`
    and not in `skip`. The walk is over all the file's spans: the view that makes a
    window's first `act` a first use ended in the iteration before it."""
    path = os.path.join(log_dir, "spans.jsonl")
    if not os.path.exists(path):
        log(f"no {path}: the program writes no raw spans (a tree before PR 27)")
        return None
    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    attempt = max((r.get("attempt", 0) for r in rows), default=0)  # a restart appends its own
    rows = [r for r in rows if r.get("attempt", 0) == attempt]
    oldest = min((r["iter"] for r in rows), default=None)
    if oldest is None or oldest > lo:
        log(f"{path} begins at iteration {oldest}, after the timed window's first ({lo}): the program's ring "
            "dropped the window's first spans, and a median over the rest would be another number")
        return None
    first, steady, fresh = [], [], False
    for row in sorted(rows, key=lambda r: r["start"]):
        if row["name"] == "act_view":
            fresh = True
        elif row["name"] == "act":
            inside = lo < row["iter"] <= hi and not skip[0] < row["iter"] <= skip[1]
            if inside:
                (first if fresh else steady).append(row["end"] - row["start"])
            fresh = False
    if not first and not steady:
        log(f"no `act` span of iterations {lo} to {hi} in {path}")
        return None
    if not steady:
        log(f"every one of the {len(first)} `act` spans follows an `act_view` (a train call every iteration): "
            "there is no steady `act` to read")
    if not first:
        log(f"none of the {len(steady)} `act` spans follows an `act_view`: there is no first use to read")
    return {"first": 1e3 * statistics.median(first) if first else None,
            "steady": 1e3 * statistics.median(steady) if steady else None,
            "n_first": len(first), "n_steady": len(steady)}


# ---------------------------------------------------------------------------------
# what a metric file calls: each reading is made once a run
# ---------------------------------------------------------------------------------
def capture_of(run) -> Optional[ProgramCapture]:
    if not hasattr(run, "_program_capture"):
        run._program_capture = load(run.trace_dir) if getattr(run, "trace_dir", None) else None
    return run._program_capture


def spans_of(run) -> Optional[dict]:
    if not hasattr(run, "_program_window_spans"):
        run._program_window_spans = None
        if getattr(run, "log_dir", None) and hasattr(run, "policy_step_open"):
            lo, hi, skip = _timed_steps(run)
            run._program_window_spans = window_spans(run.log_dir, lo, hi, skip=skip)
    return run._program_window_spans


def _timed_steps(run) -> Tuple[int, int, Tuple[int, int]]:
    """The policy steps `bench.py` hands `telemetry_phases`: the timed window's `(lo, hi]`
    and the steps to leave out, which hold the profiler's start, its cycles and its stop."""
    cycle_steps = run.window.cycle_iterations * run.window.env_steps_per_iteration
    start, stop = getattr(run, "trace_steps", None) or (0, 0)
    return run.policy_step_open, run.policy_step_close, (start, (stop or run.policy_step_close) + cycle_steps)


def act_ms(run, which: str) -> Optional[float]:
    """`act_use_ms` over the timed window's iterations (a policy step is an iteration's
    number times the envs: the loop counts both from 0)."""
    if not hasattr(run, "_program_act_use"):
        run._program_act_use = None
        if getattr(run, "log_dir", None) and hasattr(run, "policy_step_open"):
            lo, hi, skip = _timed_steps(run)
            envs = run.window.env_steps_per_iteration
            run._program_act_use = act_use_ms(run.log_dir, lo / envs, hi / envs, (skip[0] / envs, skip[1] / envs))
    return run._program_act_use[which] if run._program_act_use else None


def span_share(run, name: str) -> Optional[float]:
    """A span's seconds as a share of the windows' wall time, in percent."""
    read = spans_of(run)
    if not read or name not in read["spans"] or not read["wall"]:
        return None
    return 100.0 * read["spans"][name][1] / read["wall"]


def span_ms_a_train_call(run, name: str) -> Optional[float]:
    read = spans_of(run)
    calls = read["spans"].get(CYCLE_END, [0])[0] if read else 0
    if not calls or name not in read["spans"]:
        return None
    return 1e3 * read["spans"][name][1] / calls


def from_capture(run, reading, *args) -> Optional[float]:
    capture = capture_of(run)
    return None if capture is None else reading(capture, *args)


def idle_share(run, part: str) -> Optional[float]:
    shares = from_capture(run, idle_shares)
    return shares[part] if shares else None
