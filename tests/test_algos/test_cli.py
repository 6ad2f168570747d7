"""CLI contract tests (role of reference tests/test_algos/test_cli.py:14-277):
strategy/decoupled policing, optional-dependency downgrades, value sanity, and the
jax.profiler trace hook."""

from __future__ import annotations

import glob
import os

import pytest

from sheeprl_tpu.cli import check_configs, run
from sheeprl_tpu.config import compose


def _cfg(overrides):
    return compose(["exp=ppo", "env=dummy", "env.id=discrete_dummy"] + list(overrides))


def test_unknown_strategy_fails():
    cfg = _cfg(["fabric.strategy=fsdp"])
    with pytest.raises(ValueError, match="unknown fabric.strategy"):
        check_configs(cfg)


def test_single_device_with_many_devices_fails():
    cfg = _cfg(["fabric.strategy=single_device", "fabric.devices=2"])
    with pytest.raises(ValueError, match="fabric.devices=1"):
        check_configs(cfg)


def test_decoupled_single_device_strategy_fails():
    cfg = compose(
        ["exp=ppo_decoupled", "env=dummy", "env.id=discrete_dummy", "fabric.strategy=single_device"]
    )
    with pytest.raises(ValueError, match="decoupled"):
        check_configs(cfg)


def test_decoupled_dp_strategy_passes():
    cfg = compose(["exp=ppo_decoupled", "env=dummy", "env.id=discrete_dummy", "fabric.strategy=dp"])
    check_configs(cfg)


def test_gang_that_would_need_the_chip_is_refused_before_spawning():
    # a chip belongs to one process: N children on accelerator=auto/tpu would all claim it
    gang = ["resilience.distributed.gang.processes=2"]
    with pytest.raises(ValueError, match="CPU-mesh only"):
        check_configs(_cfg(gang))
    check_configs(_cfg(gang + ["fabric.accelerator=cpu"]))


def test_negative_learning_starts_fails():
    cfg = compose(["exp=sac", "env=dummy", "env.id=continuous_dummy", "algo.learning_starts=-1"])
    with pytest.raises(ValueError, match="learning_starts"):
        check_configs(cfg)


def test_action_repeat_clamped():
    cfg = _cfg(["env.action_repeat=0"])
    check_configs(cfg)
    assert cfg.env.action_repeat == 1


def test_model_manager_downgraded_without_mlflow(monkeypatch):
    import sheeprl_tpu.utils.imports as imports

    monkeypatch.setattr(imports, "_IS_MLFLOW_AVAILABLE", False)
    cfg = _cfg(["model_manager.disabled=False"])
    with pytest.warns(UserWarning, match="MLflow is not installed"):
        check_configs(cfg)
    assert cfg.model_manager.disabled is True


def test_invalid_profiler_mode_fails():
    cfg = _cfg(["metric.profiler.mode=sometimes"])
    with pytest.raises(ValueError, match="profiler.mode"):
        check_configs(cfg)


@pytest.mark.timeout(180)
def test_profiler_trace_hook_mode_run(standard_args, tmp_path):
    """metric.profiler.mode=run wraps the launch in a jax.profiler trace whose dump
    lands in the configured directory (SURVEY §5.1 tracing equivalence) — the
    pre-telemetry whole-run behavior, preserved."""
    trace_dir = str(tmp_path / "profiler")
    run(
        standard_args
        + [
            "exp=ppo",
            "env=dummy",
            "env.id=discrete_dummy",
            "metric.profiler.mode=run",
            f"metric.profiler.dir={trace_dir}",
            "root_dir=test_profiler",
            "run_name=trace",
        ]
    )
    dumps = glob.glob(os.path.join(trace_dir, "**", "*"), recursive=True)
    assert any(os.path.isfile(p) for p in dumps), f"no trace files written under {trace_dir}"


@pytest.mark.timeout(180)
def test_profiler_trace_hook_legacy_bool(standard_args, tmp_path):
    """The legacy scalar form (metric.profiler=True + metric.profiler_dir) still
    maps onto mode=run, so pre-group configs keep working."""
    trace_dir = str(tmp_path / "profiler-legacy")
    run(
        standard_args
        + [
            "exp=ppo",
            "env=dummy",
            "env.id=discrete_dummy",
            "metric.profiler=True",
            f"+metric.profiler_dir={trace_dir}",
            "root_dir=test_profiler",
            "run_name=trace-legacy",
        ]
    )
    dumps = glob.glob(os.path.join(trace_dir, "**", "*"), recursive=True)
    assert any(os.path.isfile(p) for p in dumps), f"no trace files written under {trace_dir}"


@pytest.mark.timeout(240)
def test_profiler_trace_mode_window_bounded(tmp_path):
    """metric.profiler.mode=window captures ONLY the configured policy-step window:
    the trace dump exists and the telemetry stream records start/stop steps whose
    span covers num_steps (quantized up to one loop iteration of 2 policy steps)."""
    import json

    trace_dir = str(tmp_path / "profiler-window")
    run(
        [
            "exp=sac",
            "env=dummy",
            "env.id=continuous_dummy",
            "dry_run=False",
            "env.sync_env=True",
            "env.capture_video=False",
            "fabric.accelerator=cpu",
            "metric.log_level=0",
            "checkpoint.save_last=False",
            "buffer.memmap=False",
            "buffer.size=256",
            "env.num_envs=2",
            "algo.learning_starts=4",
            "algo.run_test=False",
            "algo.mlp_keys.encoder=[state]",
            "algo.per_rank_batch_size=4",
            "algo.total_steps=40",
            "metric.telemetry.enabled=true",
            "metric.profiler.mode=window",
            "metric.profiler.start_step=16",
            "metric.profiler.num_steps=8",
            f"metric.profiler.dir={trace_dir}",
            "root_dir=test_profiler",
            "run_name=window",
        ]
    )
    dumps = glob.glob(os.path.join(trace_dir, "**", "*"), recursive=True)
    assert any(os.path.isfile(p) for p in dumps), f"no trace files written under {trace_dir}"
    jsonl = glob.glob("logs/runs/test_profiler/window/version_*/telemetry.jsonl")
    assert jsonl, "telemetry.jsonl missing"
    events = [json.loads(line) for line in open(jsonl[0])]
    prof = {e["action"]: e for e in events if e["event"] == "profiler"}
    assert prof["start"]["step"] >= 16, "trace started before the configured window"
    # stop lands at the first iteration boundary past start+num_steps: the window
    # is bounded, not whole-run (40 total steps)
    assert 8 <= prof["stop"]["covered_steps"] <= 8 + 2
    assert prof["stop"]["step"] < 40
