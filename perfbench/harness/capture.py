"""Reduction from a `jax.profiler` capture to device busy/idle time, op classes, the
top device ops and the idle gaps. The classification is copied from the program's
`sheeprl_tpu/obs/xprof.py` (`classify_op`, `_union_seconds`, the busy/idle rule) so
that no later PR can change the yardstick; it is checked on
`tests/data/recorded_capture` against the original.

Two loaders feed one reduction: `load_xplane` reads the `.xplane.pb` a TPU capture
writes (planes `/device:TPU:<n>` with lines `XLA Ops` and `XLA Modules`, and the
host's `python` line, which carries the harness's own `TraceAnnotation`s), and
`load_trace_json` reads trace-event JSON (the recorded fixture; XLA:CPU captures).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

CATEGORIES = ("comm", "mxu", "elementwise", "copy", "loop", "host")

_TRAILING_ID = re.compile(r"\.\d+$")
_COMM_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective", "send", "recv",
    "partition-id", "replica-id",
)
_MXU_PREFIXES = ("dot", "conv", "cholesky", "triangular-solve")
_COPY_PREFIXES = (
    "copy", "transpose", "bitcast", "reshape", "broadcast", "concatenate", "slice",
    "dynamic-slice", "dynamic-update-slice", "pad", "gather", "scatter", "reverse",
)
_LOOP_PREFIXES = (
    "while", "condition", "body", "call", "conditional", "tuple", "get-tuple-element",
    "parameter", "constant",
)
_HOST_PREFIXES = ("infeed", "outfeed", "host")
# `%name = type[shape]{layout} opcode(...)`: what a TPU capture names an op event
_HLO_TEXT = re.compile(r"^%(?P<name>[\w.\-]+) = \(*(?P<type>\w+)\[(?P<shape>[\d,]*)\]")


def classify_op(name: str, hlo_text: str = "") -> str:
    """HLO instruction name -> category, as `obs/xprof.py::classify_op`. One addition
    for TPU captures, whose fusions are mostly named `fusion.<n>`: a fusion whose HLO
    text says `kind=kOutput` or `kind=kConvolution` is rooted at a convolution or dot
    (XLA:TPU's output fusion), so it counts as `mxu`."""
    base = _TRAILING_ID.sub("", str(name).strip().lower())
    if base.startswith(_COMM_PREFIXES):
        return "comm"
    if base.startswith(_MXU_PREFIXES) or "gemm" in base or "conv" in base:
        return "mxu"
    if "kind=kOutput" in hlo_text or "kind=kConvolution" in hlo_text:
        return "mxu"
    if base.startswith(_COPY_PREFIXES):
        return "copy"
    if base.startswith(_LOOP_PREFIXES):
        return "loop"
    if base.startswith(_HOST_PREFIXES):
        return "host"
    return "elementwise"


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, -float("inf")
    for lo, hi in sorted(intervals):
        if hi <= end:
            continue
        total += hi - max(lo, end)
        end = hi
    return total


@dataclass
class Op:
    name: str  # HLO instruction name, e.g. `fusion.2375`
    label: str  # name plus result type and shape, for the breakdown
    text: str
    start: float  # seconds on the capture's clock
    dur: float


@dataclass
class Capture:
    ops: Dict[str, List[Op]] = field(default_factory=dict)  # device track -> ops
    modules: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)
    host_spans: List[Tuple[str, float, float]] = field(default_factory=list)  # (name, start, end)


def _label(text: str) -> Tuple[str, str]:
    match = _HLO_TEXT.match(text)
    if not match:
        return text, re.sub(r"[^\w.\-]+", "_", text)[:64]
    shape = match["shape"].replace(",", "_")
    return match["name"], f"{match['name']}_{match['type']}_{shape}_"


def find_xplane(root: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def load_xplane(path: str, host_prefix: str = "perfbench.") -> Capture:
    import jax

    capture = Capture()
    profile = jax.profiler.ProfileData.from_file(path)
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = capture.ops.setdefault(plane.name, [])
                    for ev in line.events:
                        name, label = _label(ev.name)
                        ops.append(Op(name, label, ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9))
                elif line.name == "XLA Modules":
                    mods = capture.modules.setdefault(plane.name, [])
                    for ev in line.events:
                        mods.append((ev.name.split("(")[0], ev.start_ns / 1e9, ev.duration_ns / 1e9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name != "python":
                    continue
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        start = ev.start_ns / 1e9
                        capture.host_spans.append((ev.name, start, start + ev.duration_ns / 1e9))
    return capture


def load_trace_json(root: str) -> Capture:
    """Trace-event JSON (`*.trace.json[.gz]` under `root`): ops are the events that
    carry `args.hlo_op`, one device track per `pid`."""
    capture = Capture()
    files = []
    for pattern in ("*.trace.json.gz", "*.trace.json"):
        files += glob.glob(os.path.join(root, "**", pattern), recursive=True)
    for path in sorted(files):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as fh:
            events = json.load(fh).get("traceEvents") or []
        for ev in events:
            args = ev.get("args") if isinstance(ev, dict) else None
            if ev.get("ph") != "X" or not isinstance(args, dict) or ev.get("dur") is None:
                continue
            if not args.get("hlo_op"):
                continue
            name = str(args["hlo_op"])
            start, dur = float(ev.get("ts") or 0.0) / 1e6, max(float(ev["dur"]), 0.0) / 1e6
            capture.ops.setdefault(str(ev.get("pid")), []).append(Op(name, name, "", start, dur))
    return capture


def _host_phase(spans, lo: float, hi: float) -> str:
    """What the host was doing in `[lo, hi]`, from the harness's own spans: inside a
    train call (sample, dispatch, act-view sync), outside one (`env_act`: env step, act
    on the host CPU, replay add), or `train_call+env_act` where the gap straddles."""
    inside = sum(
        max(min(hi, end) - max(lo, start), 0.0)
        for name, start, end in spans
        if name.endswith("train_call")
    )
    share = inside / (hi - lo) if hi > lo else 0.0
    return "train_call" if share > 0.75 else "env_act" if share < 0.25 else "train_call+env_act"


def reduce(capture: Capture, window: Optional[Tuple[float, float]] = None, top: int = 10) -> Optional[dict]:
    """Busy/idle time, category seconds, top ops and idle gaps. `window` is the traced
    window on the capture's clock; without it each track's own first-op-to-last-op
    span is used, as the program's `analyze_capture` does. Returns None when no
    device op was recorded. Times are averaged over the device tracks."""
    tracks = {k: v for k, v in capture.ops.items() if v}
    if not tracks:
        return None
    categories = {c: 0.0 for c in CATEGORIES}
    by_label: Dict[str, float] = {}
    gaps: Dict[str, List[float]] = {}
    busy = span = 0.0
    for ops in tracks.values():
        ops = sorted(ops, key=lambda o: o.start)
        lo = window[0] if window else ops[0].start
        hi = window[1] if window else max(o.start + o.dur for o in ops)
        span += hi - lo
        intervals = []
        for op in ops:
            a, b = max(op.start, lo), min(op.start + op.dur, hi)
            if b <= a and op.dur > 0:
                continue
            category = classify_op(op.name, op.text)
            categories[category] += b - a
            if category != "loop":  # a `while` spans its body's ops: not an op of its own
                by_label[op.label] = by_label.get(op.label, 0.0) + (b - a)
            intervals.append((a, b, op))
        busy += union_seconds([(a, b) for a, b, _ in intervals])
        # idle gaps, named by the host phase and the ops on either side
        end, prev = lo, "window"
        for a, b, op in intervals:
            if a > end:
                phase = _host_phase(capture.host_spans, end, a)
                after = _TRAILING_ID.sub("", prev)
                before = _TRAILING_ID.sub("", op.name)
                gaps.setdefault(f"{phase}:after_{after}_before_{before}", []).append(a - end)
            if b > end:
                end, prev = b, op.name
        if hi > end:
            phase = _host_phase(capture.host_spans, end, hi)
            gaps.setdefault(f"{phase}:after_{_TRAILING_ID.sub('', prev)}_before_window", []).append(hi - end)
    n = len(tracks)
    modules: Dict[str, List[float]] = {}
    for mods in capture.modules.values():
        for name, start, dur in mods:
            if window is None or (start >= window[0] and start + dur <= window[1]):
                modules.setdefault(name, []).append(dur)
    leaf_busy = sum(v for k, v in categories.items() if k != "loop")
    return {
        "devices": n,
        "busy_s": busy / n,
        "window_s": span / n,
        "categories": {k: v / n for k, v in categories.items()},
        "leaf_op_s": leaf_busy / n,
        "device_ops": [
            [k, v / n] for k, v in sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [f"{k}({len(v)}_gaps_longest_{max(v):.3g}s)", sum(v) / n]
            for k, v in sorted(gaps.items(), key=lambda kv: -sum(kv[1]))[:top]
        ],
        "module_s": modules,
    }


def module_mean_s(reduced: Optional[dict], name: str) -> Optional[float]:
    """Mean device time of the whole executions of the programs whose name holds `name`."""
    if not reduced:
        return None
    runs = [d for module, ds in reduced["module_s"].items() if name in module for d in ds if d > 0]
    return sum(runs) / len(runs) if runs else None
