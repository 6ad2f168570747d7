"""The device's idle time put down to the span the host loop's thread was in.

`program_spans.idle_shares` splits the idle time of the traced whole cycles by the
`act` and `act_view` spans and calls the rest unattributed. The Dreamer-V3 loop tiles
each iteration with spans (`Time/env_interaction_time`, `step_bookkeeping`,
`Time/train_time`, `loop_tail` and their children), so this reader takes every
device-idle instant of the same cycles and names the innermost GROUP of spans open on
the loop's thread at that instant:

- the loop's thread is the `/host:CPU` line that holds `Time/env_interaction_time`;
  the other lines (the replay prefetcher, JAX's own threads) are not read;
- the program's span names are those of the run's `spans.jsonl`, so JAX's host events
  and the harness's `perfbench.*` annotations on the same line are never taken for
  the program's;
- a program span that no group names counts to the group of the span it sits in, and
  to `outside` where it sits in none: a new top-level span shows in the guard
  `idle_outside_spans_share` until a group here names it.

The device ops, the traced cycles and the interval arithmetic are `program_spans`'s,
so `act` + `act_view` + the five other groups add up to its idle share, and the five
to its `unattributed` share. Every reading returns None, and says why on stderr,
where there is nothing to read: no capture, no device ops, no `spans.jsonl`, or a
loop that does not tile its iteration with spans.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench.harness import program_spans as ps

GROUPS: Dict[str, Tuple[str, ...]] = {  # group -> the spans that count to it
    "act": ("act",),
    "act_view": ("act_view",),
    "train_dispatch": ("train_dispatch", "train_dispatch.call"),
    "train_prep": (ps.CYCLE_END, "replay_sample", "train_key", "train_observe"),
    "env_side": (ps.CYCLE_START, "env_step", "replay_add", "step_bookkeeping", "player_reset"),
    "loop_tail": ("loop_tail",),
}
GROUP_OF = {name: group for group, names in GROUPS.items() for name in names}
OUTSIDE = "outside"
TILING = ("step_bookkeeping", "train_key", "train_dispatch.call", "train_observe", "loop_tail")
Event = Tuple[float, float, str]


def log(*parts) -> None:
    print("[perfbench] host_idle:", *parts, file=sys.stderr, flush=True)


def span_names(log_dir: str) -> Optional[set]:
    """The names of the spans the program recorded (`spans.jsonl` beside the stream)."""
    path = os.path.join(log_dir, "spans.jsonl")
    if not os.path.exists(path):
        log(f"no {path}: the program writes no raw spans")
        return None
    with open(path) as fh:
        return {json.loads(line)["name"] for line in fh if line.strip()}


def loop_line(trace_dir: str, names: set) -> Optional[List[Event]]:
    """The program's spans on the loop's thread of the newest capture, as (start, end,
    name) in seconds on the capture's clock."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        log(f"no .xplane.pb under {trace_dir}")
        return None
    profile = jax.profiler.ProfileData.from_file(files[-1])
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9, ev.name)
                      for ev in line.events if ev.name in names]
            if any(name == ps.CYCLE_START for _, _, name in events):
                return events
    log(f"no line of /host:CPU holds `{ps.CYCLE_START}`")
    return None


def label(events: Sequence[Event], lo: float, hi: float) -> Dict[str, List[ps.Interval]]:
    """{group: intervals} tiling [lo, hi]: each instant goes to the innermost open span
    that a group names (spans on one thread nest; a child is cut at its parent's end),
    or to OUTSIDE where none is open."""
    out: Dict[str, List[ps.Interval]] = {}
    stack: List[Tuple[float, str]] = []  # (end, group) of the open spans, innermost last
    at = lo

    def upto(t: float) -> None:  # [at, t] to the innermost open span's group
        nonlocal at
        if t > at:
            out.setdefault(stack[-1][1] if stack else OUTSIDE, []).append((at, t))
            at = t

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            upto(stack[-1][0])
            stack.pop()

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        upto(start)
        end = min(end, stack[-1][0]) if stack else end
        stack.append((end, GROUP_OF.get(name) or (stack[-1][1] if stack else OUTSIDE)))
    close(float("inf"))
    upto(hi)
    return {group: ps.clip(spans, lo, hi) for group, spans in out.items()}


def idle_by_group(capture: ps.ProgramCapture, events: Sequence[Event]) -> Optional[dict]:
    """Device-idle time over the traced whole cycles by group, as a share of them in
    percent, with `idle` the whole: the sum and the scale of `program_spans.idle_shares`."""
    window = ps.traced_cycles(capture)
    tracks = [ops for ops in capture.ops.values() if ops]
    if window is None or not tracks:
        log("no traced cycles or no device ops on the capture")
        return None
    groups = label(events, *window)
    out = {name: 0.0 for name in (*GROUPS, OUTSIDE, "idle")}
    for ops in tracks:
        idle = ps.complement([(a, b) for _, a, b in ops], *window)
        out["idle"] += sum(b - a for a, b in idle)
        for group, spans in groups.items():
            out[group] += ps.overlap(idle, spans)
    scale = 100.0 / (len(tracks) * (window[1] - window[0]))
    return {k: v * scale for k, v in out.items()}


def read(run) -> Optional[dict]:
    """`idle_by_group` of a run, made once however many metrics ask."""
    if not hasattr(run, "_host_idle"):
        run._host_idle = _read(run)
    return run._host_idle


def _read(run) -> Optional[dict]:
    names = span_names(run.log_dir) if getattr(run, "log_dir", None) else None
    if names is None:
        return None
    missing = sorted(set(TILING) - names)
    if missing:
        log(f"the program recorded no {missing} span: its loop does not tile an iteration with spans")
        return None
    capture = ps.capture_of(run)
    events = None if capture is None else loop_line(run.trace_dir, names)
    return None if events is None else idle_by_group(capture, events)


def share(run, group: str) -> Optional[float]:
    shares = read(run)
    return shares[group] if shares else None
