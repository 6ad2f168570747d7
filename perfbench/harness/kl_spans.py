"""What the sequence-policy PPO program says about itself on the `kimi_linear` trunk
(`models/kimi_linear.py`), for the per-layer readers of its cell. As `lm_spans.py` (whose
capture loader, name-stack parser and counter reader these are), with this trunk's parts:

(a) the `jax.named_scope` names on the device ops of the fused program: `rollout` or
    `update` outermost, and inside them `embed`, `kda` (Kimi delta attention: projections,
    convolutions, the decay, the gate, the output norm and product) with `kda_rule` inside it
    (the rule alone: the step form's state update, the chunked form and its backward), `mla`
    (projections, the latent's norm, the output product) with `mla_attend` inside it, `router`,
    `experts`, `shared_expert`, `dense_ffn`, `lm_head`, `value_head`, `gae`, `ppo_loss`,
    `optimizer`. An op counts under its INNERMOST part, so a mixer's time is `kda` plus
    `kda_rule`, `mla` plus `mla_attend`;
(b) the program's counters in the run's `telemetry.jsonl`: `moe/<phase>_<counter>`.

`lm_spans.py` finds its parts in its own `PARTS`, and a file the benchmark already has is
not edited, so the readers here are a further INSTANCE of that file (`bench.load_file`, as
`q3n_spans.py` and `dsv3_spans.py` are) with this trunk's parts: one reader, kept in one place. Every reader
returns None where it finds nothing to read."""

from __future__ import annotations

import dataclasses
from typing import Optional

from perfbench.harness import lm_spans
from perfbench.harness.bench import load_file

PARTS = ("embed", "kda", "kda_rule", "mla", "mla_attend", "router", "experts", "shared_expert", "dense_ffn", "lm_head",
         "value_head", "gae", "ppo_loss", "optimizer")

_by_parts = load_file(lm_spans.__file__)
_by_parts.PARTS = PARTS
place_of, part_ms, unscoped_share = _by_parts.place_of, _by_parts.part_ms, _by_parts.unscoped_share
from_capture, counter_mean, counters_of = lm_spans.from_capture, lm_spans.counter_mean, lm_spans.counters_of
_lm_program_parts = _by_parts.program_parts


def program_parts(capture):
    """`lm_spans.program_parts` by this trunk's parts, memoised apart from that reader's own
    reading of the same capture."""
    return _lm_program_parts(dataclasses.replace(capture, memo=capture.memo.setdefault("kl_spans", {})))


_by_parts.program_parts = program_parts  # `part_ms` and `unscoped_share` read through it


def roofline_share(run, part: str, phase: str, flops: float, nbytes: float) -> Optional[float]:
    """The least time the chip could take for `flops` and `nbytes` (the larger of the two
    over their peaks) over the device time of `phase`'s `part` scope in one execution."""
    ms = from_capture(run, part_ms, (part,), phase=phase)
    if not ms or not run.peaks:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * ms)
