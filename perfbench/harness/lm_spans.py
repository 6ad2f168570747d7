"""What the sequence-policy PPO program says about itself (`algos/ppo/anakin.py`, the
sequence flavour, and `models/lfm2.py`), for the per-layer readers of its cells:

(a) the `jax.named_scope` names on the device ops of the fused program (`anakin_step` on
    the capture's `XLA Modules` line): `rollout` or `update` outermost, and inside them
    `embed`, `short_conv`, `attention`, `router`, `experts`, `dense_ffn`, `lm_head`,
    `value_head`, `gae`, `ppo_loss`, `optimizer`. The capture and its name stacks are read
    by `program_spans.py`'s loader; the readings here are leaf-op seconds of one whole
    execution of the program, by (phase, part);
(b) the program's counters in the run's `telemetry.jsonl` (`window.counters`, which the
    loop feeds from the scalars the program returns): `moe/<phase>_<counter>`.

Every reader returns None where it finds nothing to read: a tree without the program (the
parent of PR 31) reads as nothing."""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

from perfbench.harness import program_spans as ps

MODULE = "anakin_step"
PHASES = ("rollout", "update")
PARTS = ("embed", "short_conv", "attention", "router", "experts", "dense_ffn", "lm_head", "value_head",
         "gae", "ppo_loss", "optimizer")


def names_on(stack: str):
    """The plain names on a name stack: `transpose(jvp(experts))` -> `experts`."""
    for part in stack.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        yield part


def place_of(stack: str) -> Tuple[Optional[str], Optional[str]]:
    """(phase, innermost part) of an op's name stack; None where it carries neither."""
    phase = part = None
    for name in names_on(stack):
        if name in PHASES and phase is None:
            phase = name
        elif name in PARTS:
            part = name
    return phase, part


def program_parts(capture: ps.ProgramCapture) -> Optional[dict]:
    """Leaf-op seconds of one execution of the fused program by (phase, part), over its
    whole executions on the capture."""
    if "lm_program_parts" in capture.memo:
        return capture.memo["lm_program_parts"]
    seconds: Dict[Tuple[Optional[str], Optional[str]], float] = {}
    runs_seen = tracks = 0
    for track, ops in capture.ops.items():
        runs = [(a, b) for name, a, b in capture.modules.get(track, ()) if MODULE in name]
        if not runs:
            continue
        tracks, runs_seen = tracks + 1, runs_seen + len(runs)
        at = 0
        for text, a, b in ps.leaf_ops(ops):
            while at < len(runs) and runs[at][1] <= a:
                at += 1
            if at == len(runs):
                break
            if a >= runs[at][0] and b <= runs[at][1]:
                place = place_of(capture.scopes.get(text, ""))
                seconds[place] = seconds.get(place, 0.0) + (b - a)
    out = None
    if not runs_seen:
        ps.log(f"no whole `{MODULE}` execution on the capture")
    elif not any(phase for phase, _ in seconds):
        ps.log(f"no op of `{MODULE}` carries `rollout` or `update` on its name stack ({capture.carrier})")
    else:
        out = {"seconds": {k: v / runs_seen for k, v in seconds.items()}, "runs": runs_seen / tracks}
    capture.memo["lm_program_parts"] = out
    return out


def part_ms(capture: ps.ProgramCapture, parts=None, phase: Optional[str] = None) -> Optional[float]:
    """Milliseconds an execution under any of `parts` (all of them: None), in `phase` (both: None)."""
    read = program_parts(capture)
    if read is None:
        return None
    return 1e3 * sum(v for (ph, part), v in read["seconds"].items()
                     if (phase is None or ph == phase) and (parts is None or part in parts))


def unscoped_share(capture: ps.ProgramCapture) -> Optional[float]:
    """Share of the program's leaf-op time under neither phase and no part: the guard that
    a refactor did not lose the names."""
    read = program_parts(capture)
    if read is None:
        return None
    return 100.0 * read["seconds"].get((None, None), 0.0) / sum(read["seconds"].values())


def from_capture(run, reading, *args, **kwargs) -> Optional[float]:
    capture = ps.capture_of(run)
    return None if capture is None else reading(capture, *args, **kwargs)


def counter_mean(run, name: str) -> Optional[float]:
    """Mean of the program's counter `name` over the timed window's telemetry windows."""
    if not getattr(run, "log_dir", None) or not hasattr(run, "policy_step_open"):
        return None
    path = os.path.join(run.log_dir, "telemetry.jsonl")
    if not os.path.exists(path):
        return None
    count = total = 0.0
    with open(path) as fh:
        for line in fh:
            event = json.loads(line)
            step = event.get("step")
            if event.get("event") != "window" or step is None or not run.policy_step_open < step <= run.policy_step_close:
                continue
            seen = (event.get("counters") or {}).get(name)
            if seen:
                count, total = count + seen[0], total + seen[1]
    return total / count if count else None


def counters_of(run) -> Optional[dict]:
    """The window's mean counters under the names `lm_flops.counted_pairs` reads."""
    out = {k: counter_mean(run, f"moe/{k}") for k in ("rollout_pairs_held", "update_pairs_held")}
    return None if any(v is None for v in out.values()) else out
