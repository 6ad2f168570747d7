"""The Dreamer-V3 loop's spans tile its iteration: a short run at tiny widths with
telemetry on, stopped from inside its telemetry's `step` as the benchmark's harness
stops a run, leaves a `spans.jsonl` whose top-level spans cover every whole iteration
that acts, and an empty stack of open spans. And the rule of the train program's
names holds for the per-step span: timer on or off, the same program under the same
cache key."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
STOP_AT = 24  # policy steps: 12 iterations of 2 envs, training from the third

TINY = [
    "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy", "env.num_envs=2", "env.sync_env=True",
    "env.capture_video=False", "fabric.accelerator=cpu", "dry_run=False", "metric.log_level=0",
    "checkpoint.save_last=False", "buffer.memmap=False", "buffer.size=512", "algo.learning_starts=4",
    "algo.run_test=False", "algo.total_steps=64", "algo.per_rank_batch_size=1", "algo.per_rank_sequence_length=1",
    "algo.replay_ratio=1", "algo.horizon=8", "algo.dense_units=8", "algo.mlp_layers=1",
    "algo.world_model.discrete_size=4", "algo.world_model.stochastic_size=4",
    "algo.world_model.encoder.cnn_channels_multiplier=2", "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8", "algo.world_model.transition_model.hidden_size=8",
    "algo.cnn_keys.encoder=[rgb]", "algo.cnn_keys.decoder=[rgb]", "algo.mlp_keys.encoder=[state]",
    "algo.mlp_keys.decoder=[state]",
    # as the benchmark's traced run has it: the program analysis shifts `Time/train_time` past itself
    "metric.telemetry.enabled=true", "metric.telemetry.every=8", "metric.telemetry.program_analysis=false",
    "root_dir=loop_spans", "run_name=tiled",
]


class StopRun(Exception):
    pass


class _StoppingTelemetry:
    """The loop's telemetry, which raises from `step` once the run has gone far enough."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def step(self, policy_step: int) -> None:
        self._inner.step(policy_step)
        if policy_step >= STOP_AT:
            raise StopRun


@pytest.fixture(scope="module")
def stopped(tmp_path_factory):
    """(open spans after the stop, rows of `spans.jsonl`) of one stopped run."""
    import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.obs import build_telemetry
    from sheeprl_tpu.utils.timer import timer

    def main(fabric, cfg):
        def telemetry(fabric, cfg, log_dir, logger=None):
            return _StoppingTelemetry(build_telemetry(fabric, cfg, log_dir, logger=logger))

        return dv3.run_dreamer(fabric, cfg, telemetry_factory=telemetry)

    here, original = os.getcwd(), dv3.main
    os.chdir(tmp_path_factory.mktemp("run"))
    dv3.main = main
    try:
        with pytest.raises(StopRun):
            run(TINY)
        stack = list(getattr(timer._open, "stack", []))
        path = glob.glob("logs/runs/loop_spans/tiled/version_*/spans.jsonl")
        assert path, "the stopped run wrote no spans.jsonl"
        with open(path[0]) as fh:
            rows = [json.loads(line) for line in fh]
    finally:
        dv3.main = original
        os.chdir(here)
    return stack, rows


def test_stop_run_raised_in_loop_tail_leaves_no_span_open(stopped):
    stack, rows = stopped
    assert stack == []
    last = max(rows, key=lambda r: r["end"])
    assert (last["name"], last["parent"], last["iter"]) == ("loop_tail", None, STOP_AT // 2)  # closed by the raise


def test_the_loop_s_spans_nest_as_documented(stopped):
    _, rows = stopped
    parents = {(r["name"], r["parent"]) for r in rows}
    assert {("Time/env_interaction_time", None), ("step_bookkeeping", None), ("Time/train_time", None),
            ("loop_tail", None), ("act", "Time/env_interaction_time"), ("env_step", "Time/env_interaction_time"),
            ("replay_sample", "Time/train_time"), ("train_key", "Time/train_time"),
            ("train_dispatch", "Time/train_time"), ("train_observe", "Time/train_time"),
            ("train_dispatch.call", "train_dispatch")} <= parents
    assert {name for name, parent in parents if parent is None} == {
        "Time/env_interaction_time", "step_bookkeeping", "Time/train_time", "loop_tail"}
    calls = [r for r in rows if r["name"] == "train_dispatch.call"]
    dispatches = [r for r in rows if r["name"] == "train_dispatch"]
    assert len(calls) == 2 * len(dispatches) > 0  # replay ratio 1 at 2 envs: two gradient steps a call


def test_top_level_spans_cover_every_whole_iteration_that_acts(stopped):
    """From one iteration's `Time/env_interaction_time` to the next's, the top-level spans cover 99% or more of
    the wall time: host work added later outside every span fails here. (An iteration of the prefill, with no
    `act`, lasts well under a millisecond, and the timer's own few microseconds between spans are over 1% of it.)"""
    _, rows = stopped
    starts = sorted((r["start"], r["iter"]) for r in rows if r["name"] == "Time/env_interaction_time")
    acting = {r["iter"] for r in rows if r["name"] == "act"}
    top = [(r["start"], r["end"]) for r in rows if r["parent"] is None]
    covered = {}
    for (lo, iteration), (hi, _) in zip(starts, starts[1:]):
        if iteration in acting:
            covered[iteration] = sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in top) / (hi - lo)
    assert len(covered) >= 8
    assert min(covered.values()) >= 0.99, covered


_PHASE_ON_AND_OFF = """
import contextlib, json, os, sys
import jax, jax.numpy as jnp
cache, capture = sys.argv[1], sys.argv[2]
jax.config.update("jax_compilation_cache_dir", cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _aot_train_step
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import foreach_gradient_step

def run(disabled, profiled):
    jax.clear_caches()  # nothing is left in memory: every program asks the directory
    timer.disabled = disabled
    fn, (params, opt_state, moments, batch, _cum, key) = _aot_train_step()
    block = jax.tree_util.tree_map(lambda a: jnp.stack([a, a]), batch)  # two gradient steps
    session = contextlib.nullcontext()
    if profiled:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        session = jax.profiler.trace(capture, profiler_options=options)
    with session:
        with timer("train_dispatch"):
            out = foreach_gradient_step(fn, (params, opt_state, moments), block, key, 3)
        jax.block_until_ready(out)
    return sorted(name for name in os.listdir(cache) if name.endswith("-cache"))

untraced = run(disabled=True, profiled=False)
traced = run(disabled=False, profiled=True)
print(json.dumps({"untraced": untraced, "traced": traced, "spans": [record[:2] for record in timer.ring]}))
"""


def test_the_per_step_span_changes_no_program_and_no_cache_key(tmp_path):
    """The train phase's two gradient steps, each a `train_dispatch.call` span, once with `timer.disabled` and
    once with the spans on inside a profiler session: the second run writes no cache entry of its own."""
    done = subprocess.run(
        [sys.executable, "-c", _PHASE_ON_AND_OFF, str(tmp_path / "cache"), str(tmp_path / "capture")],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=280,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    read = json.loads(done.stdout.strip().splitlines()[-1])
    assert any(name.startswith("jit_train_step-") for name in read["untraced"])
    assert read["traced"] == read["untraced"]
    names = [name for name, _ in read["spans"]]
    assert names == ["train_dispatch.call", "train_dispatch.call", "train_dispatch"]
    assert list(tmp_path.glob("capture/**/*.xplane.pb"))
