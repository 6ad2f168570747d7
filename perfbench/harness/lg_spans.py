"""What the sequence-policy PPO program says about itself on the `laguna` trunk
(`models/laguna.py`), for the per-layer readers of its cell. As `lm_spans.py` (whose capture
loader, name-stack parser and counter reader these are), with this trunk's parts:

(a) the `jax.named_scope` names on the device ops of the fused program: `rollout` or `update`
    outermost, and inside them `embed`, `swa` (a sliding layer's projections, rotation, gate and
    output product) with `swa_attend` inside it (the ring's write and read in a decode step, the
    band over whole sequences and its backward), `full_attn` with `attend` inside it (the KV cache's
    write and read, the blocked causal attention), `router`, `experts`, `shared_expert`,
    `dense_ffn`, `lm_head`, `value_head`, `gae`, `ppo_loss`, `optimizer`. An op counts under its
    INNERMOST part, so a mixer's time is `swa` plus `swa_attend`, `full_attn` plus `attend`;
(b) the program's counters in the run's `telemetry.jsonl`: `moe/<phase>_<counter>` and
    `swa/rollout_ring_rows_read`.

`lm_spans.py` finds its parts in its own `PARTS`, and a file the benchmark already has is not
edited, so the readers here are a further INSTANCE of that file (`bench.load_file`, as
`kl_spans.py` is) with this trunk's parts: one reader, kept in one place. Every reader returns
None where it finds nothing to read."""

from __future__ import annotations

import dataclasses
from typing import Optional

from perfbench.harness import lm_spans
from perfbench.harness.bench import load_file

PARTS = ("embed", "swa", "swa_attend", "full_attn", "attend", "router", "experts", "shared_expert", "dense_ffn",
         "lm_head", "value_head", "gae", "ppo_loss", "optimizer")

_by_parts = load_file(lm_spans.__file__)
_by_parts.PARTS = PARTS
place_of, part_ms, unscoped_share = _by_parts.place_of, _by_parts.part_ms, _by_parts.unscoped_share
from_capture, counter_mean, counters_of = lm_spans.from_capture, lm_spans.counter_mean, lm_spans.counters_of
_lm_program_parts = _by_parts.program_parts


def program_parts(capture):
    """`lm_spans.program_parts` by this trunk's parts, memoised apart from that reader's own
    reading of the same capture."""
    return _lm_program_parts(dataclasses.replace(capture, memo=capture.memo.setdefault("lg_spans", {})))


_by_parts.program_parts = program_parts  # `part_ms` and `unscoped_share` read through it


def roofline_share(run, part: str, phase: Optional[str], flops: float, nbytes: float) -> Optional[float]:
    """The least time the chip could take for `flops` and `nbytes` (the larger of the two over
    their peaks) over the device time of the `part` scope in `phase` (both phases: None) in one
    execution."""
    ms = from_capture(run, part_ms, (part,), phase=phase)
    if not ms or not run.peaks:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * ms)
