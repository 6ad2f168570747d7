"""Unit tests for the run telemetry subsystem (sheeprl_tpu/obs)."""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.config import dotdict
from sheeprl_tpu.obs import (
    JsonlEventSink,
    build_telemetry,
    compile_snapshot,
    install_compile_monitor,
    resolve_profiler_config,
)
from sheeprl_tpu.obs.jsonl import read_events
from sheeprl_tpu.obs.telemetry import NullTelemetry, _nonfinite_losses


class FakeFabric:
    is_global_zero = True
    world_size = 1

    def __init__(self):
        self.device = jax.devices("cpu")[0]


class FakeLogger:
    def __init__(self):
        self.metrics = []

    def log_metrics(self, metrics, step=None):
        self.metrics.append((step, dict(metrics)))


def _cfg(telemetry=None, profiler=None, log_every=100):
    return dotdict(
        {
            "metric": {
                "log_every": log_every,
                "telemetry": telemetry or {},
                "profiler": profiler or {"mode": "off"},
            }
        }
    )


# ---------------------------------------------------------------------------------
# JSONL sink
# ---------------------------------------------------------------------------------
def test_jsonl_sink_round_trip(tmp_path):
    sink = JsonlEventSink(str(tmp_path / "t.jsonl"))
    sink.emit("window", step=10, sps=np.float32(1.5), arr=np.arange(3), none=None)
    sink.close()
    events = read_events(str(tmp_path / "t.jsonl"))
    assert len(events) == 1
    e = events[0]
    assert e["event"] == "window" and e["step"] == 10
    assert e["sps"] == 1.5 and e["arr"] == [0, 1, 2] and e["none"] is None
    json.dumps(e)  # round-trips as strict JSON


# ---------------------------------------------------------------------------------
# profiler config resolution
# ---------------------------------------------------------------------------------
def test_profiler_config_legacy_and_group_forms():
    assert resolve_profiler_config({"profiler": True})["mode"] == "run"
    assert resolve_profiler_config({"profiler": False})["mode"] == "off"
    assert resolve_profiler_config({"profiler": None})["mode"] == "off"
    # YAML 1.1 parses a bare `off` as False inside the group too
    assert resolve_profiler_config({"profiler": {"mode": False}})["mode"] == "off"
    got = resolve_profiler_config(
        {"profiler": {"mode": "window", "start_step": 5, "num_steps": 7, "dir": "/tmp/d"}}
    )
    assert got == {"mode": "window", "start_step": 5, "num_steps": 7, "dir": "/tmp/d"}
    with pytest.raises(ValueError, match="profiler.mode"):
        resolve_profiler_config({"profiler": {"mode": "sometimes"}})


# ---------------------------------------------------------------------------------
# build_telemetry gating
# ---------------------------------------------------------------------------------
def test_disabled_telemetry_is_null():
    t = build_telemetry(FakeFabric(), _cfg(), None)
    assert isinstance(t, NullTelemetry)
    # the whole hook surface is a no-op
    t.attach_sampler(object())
    t.observe_train(3, np.ones(2))
    t.step(100)
    t.close(100)
    assert not t.wants_program("train")


def test_non_zero_rank_is_null():
    fabric = FakeFabric()
    fabric.is_global_zero = False
    t = build_telemetry(fabric, _cfg(telemetry={"enabled": True}), None)
    assert isinstance(t, NullTelemetry)


# ---------------------------------------------------------------------------------
# window emission
# ---------------------------------------------------------------------------------
def test_window_events_and_gauges(tmp_path):
    logger = FakeLogger()
    cfg = _cfg(telemetry={"enabled": True, "compile_warmup_steps": 0}, log_every=100)
    t = build_telemetry(FakeFabric(), cfg, str(tmp_path), logger=logger)
    assert t.enabled and t.every == 100

    t.step(0)  # anchors
    t.observe_train(4, np.asarray([0.5, 0.25]))
    t.step(50)  # below the window boundary: no event
    t.observe_train(4, np.asarray([0.5, 0.25]))
    t.step(100)  # window 0
    t.close(160)  # final partial window + summary

    events = read_events(str(tmp_path / "telemetry.jsonl"))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "start" and kinds[-1] == "summary"
    windows = [e for e in events if e["event"] == "window"]
    assert [w["step"] for w in windows] == [100, 160]
    assert windows[0]["train_units"] == 8 and windows[0]["sps"] > 0
    assert windows[0]["mfu"] is None  # CPU: no chip peak
    healths = [e for e in events if e["event"] == "health"]
    assert healths and healths[0]["status"] == "ok"
    summary = events[-1]
    assert summary["train_units"] == 8 and summary["total_steps"] == 160

    # TB gauges carry the new metric families (Mem/* via host RSS on CPU)
    gauges = logger.metrics[0][1]
    assert "Perf/sps" in gauges and "Compile/count" in gauges and "Compile/seconds" in gauges
    assert any(k.startswith("Mem/") for k in gauges)
    assert "Perf/mfu" not in gauges  # TPU-only


def test_window_train_seconds_survive_log_site_resets(tmp_path):
    """The metric log sites call timer.to_dict(reset=True) on their own cadence
    (log_every), generally misaligned with telemetry windows. Because step()
    harvests the timer registry every iteration — and the loops call it right
    before the log block — a mid-window reset must not drop the already-accrued
    train seconds (regression: the window used to read only post-reset time)."""
    import time as _time

    from sheeprl_tpu.utils.timer import timer as t

    saved, t.timers = t.timers, {}
    saved_disabled, t.disabled = t.disabled, False
    try:
        cfg = _cfg(telemetry={"enabled": True}, log_every=100)
        tel = build_telemetry(FakeFabric(), cfg, str(tmp_path))
        tel.step(0)
        for step in (25, 50, 75, 100):
            with t("Time/train_time"):
                _time.sleep(0.01)
            tel.step(step)  # harvest happens here, before the "log site"
            if step == 50:
                t.to_dict(reset=True)  # a log boundary inside the window
        tel.close(100)
        window = [e for e in read_events(str(tmp_path / "telemetry.jsonl")) if e["event"] == "window"][0]
        # all four sleeps must be accounted, not just the two after the reset
        assert window["train_seconds"] >= 0.035, window["train_seconds"]
    finally:
        t.timers = saved
        t.disabled = saved_disabled


def test_window_train_seconds_exact_with_per_iteration_resets(tmp_path):
    """log_every <= policy_steps_per_iter (or dry_run) resets the timers EVERY
    iteration; the reset-generation check must still account every span exactly
    (regression: a magnitude heuristic returned cur-last when the fresh accrual
    caught up with the pre-reset total, dropping nearly the whole span)."""
    import time as _time

    from sheeprl_tpu.utils.timer import timer as t

    saved, t.timers = t.timers, {}
    saved_disabled, t.disabled = t.disabled, False
    try:
        cfg = _cfg(telemetry={"enabled": True}, log_every=100)
        tel = build_telemetry(FakeFabric(), cfg, str(tmp_path))
        tel.step(0)
        for step in (25, 50, 75, 100):
            with t("Time/train_time"):
                _time.sleep(0.01)  # equal spans: cur always catches up with last
            tel.step(step)
            t.to_dict(reset=True)  # per-iteration log site
        tel.close(100)
        window = [e for e in read_events(str(tmp_path / "telemetry.jsonl")) if e["event"] == "window"][0]
        assert window["train_seconds"] >= 0.035, window["train_seconds"]
    finally:
        t.timers = saved
        t.disabled = saved_disabled


def test_phases_breakdown_tiles_the_window(tmp_path):
    """Named phases (env/train/checkpoint/logging/eval + replay_wait/analysis)
    plus the `other` remainder must sum to the window wall time."""
    import time as _time

    from sheeprl_tpu.utils.timer import timer as t

    saved, t.timers = t.timers, {}
    saved_disabled, t.disabled = t.disabled, False
    try:
        cfg = _cfg(telemetry={"enabled": True}, log_every=100)
        tel = build_telemetry(FakeFabric(), cfg, str(tmp_path))
        tel.step(0)
        for name in ("Time/env_interaction_time", "Time/train_time", "Time/checkpoint_time", "Time/logging_time"):
            with t(name):
                _time.sleep(0.02)
        tel.step(100)
        tel.close(100)
        window = [e for e in read_events(str(tmp_path / "telemetry.jsonl")) if e["event"] == "window"][0]
        phases = window["phases"]
        assert set(phases) == {
            "env", "rollout", "replay_wait", "train", "checkpoint", "logging", "eval", "analysis", "other",
        }
        for name in ("env", "train", "checkpoint", "logging"):
            assert phases[name] >= 0.015, (name, phases)
        assert abs(sum(phases.values()) - window["wall_seconds"]) <= 0.05 * window["wall_seconds"] + 0.005
    finally:
        t.timers = saved
        t.disabled = saved_disabled


def test_replay_wait_is_carved_out_of_train_phase(tmp_path):
    """The sampler's wait counter becomes the replay_wait phase and is
    subtracted from the train phase (train_seconds keeps the old semantics)."""
    import time as _time

    from sheeprl_tpu.utils.timer import timer as t

    class WaitySampler:
        def __init__(self):
            self.wait = 0.0
            self.empty = 0

        def telemetry_snapshot(self):
            return {
                "is_async": True,
                "wait_seconds": self.wait,
                "sample_calls": 1,
                "units": 1,
                "occupancy_sum": 0.0,
                "staleness_sum": 0.0,
                "empty_waits": self.empty,
                "pipeline_len": 2,
                "depth": 2,
            }

    saved, t.timers = t.timers, {}
    saved_disabled, t.disabled = t.disabled, False
    try:
        cfg = _cfg(telemetry={"enabled": True}, log_every=100)
        tel = build_telemetry(FakeFabric(), cfg, str(tmp_path))
        sampler = WaitySampler()
        tel.attach_sampler(sampler)
        tel.step(0)
        with t("Time/train_time"):
            _time.sleep(0.05)
        sampler.wait = 0.03  # of which 30ms was replay wait
        sampler.empty = 3
        tel.step(100)
        tel.close(100)
        window = [e for e in read_events(str(tmp_path / "telemetry.jsonl")) if e["event"] == "window"][0]
        assert window["phases"]["replay_wait"] == pytest.approx(0.03, abs=0.005)
        assert window["phases"]["train"] == pytest.approx(window["train_seconds"] - 0.03, abs=0.01)
        assert window["prefetch"]["empty_waits"] == 3 and window["prefetch"]["depth"] == 2
    finally:
        t.timers = saved
        t.disabled = saved_disabled


def test_crash_path_flushes_summary_with_clean_exit_false(tmp_path):
    """An exception that unwinds past a loop skips its telemetry.close(); the
    cli finally (close_all_live_telemetry) must flush the summary at the last
    seen step with clean_exit=false — and a later duplicate close is a no-op."""
    from sheeprl_tpu.obs.telemetry import close_all_live_telemetry

    cfg = _cfg(telemetry={"enabled": True}, log_every=100)
    tel = build_telemetry(FakeFabric(), cfg, str(tmp_path))
    tel.step(0)
    tel.observe_train(2, np.asarray([0.5]))
    tel.step(120)
    close_all_live_telemetry(clean_exit=False)  # the crash path
    tel.close(200)  # the loop's own close must now be a no-op
    events = read_events(str(tmp_path / "telemetry.jsonl"))
    summaries = [e for e in events if e["event"] == "summary"]
    assert len(summaries) == 1
    assert summaries[0]["clean_exit"] is False and summaries[0]["step"] == 120
    # nothing left live: a second sweep emits nothing
    close_all_live_telemetry(clean_exit=False)
    assert len(read_events(str(tmp_path / "telemetry.jsonl"))) == len(events)


def test_in_loop_diagnosis_emits_health_event(tmp_path):
    """With metric.telemetry.diagnosis on (default), the detector catalog runs
    over the run's own window history and emits status=diagnosis health events
    when the finding set changes."""
    import time as _time

    from sheeprl_tpu.utils.timer import timer as t

    class StarvedSampler:
        def __init__(self):
            self.wait = 0.0
            self.calls = 0

        def telemetry_snapshot(self):
            return {
                "is_async": True,
                "wait_seconds": self.wait,
                "sample_calls": self.calls,
                "units": self.calls,
                "occupancy_sum": 0.0,
                "staleness_sum": 0.0,
                "empty_waits": self.calls,
                "pipeline_len": 2,
                "depth": 2,
            }

    saved, t.timers = t.timers, {}
    saved_disabled, t.disabled = t.disabled, False
    try:
        cfg = _cfg(telemetry={"enabled": True}, log_every=100)
        tel = build_telemetry(FakeFabric(), cfg, str(tmp_path))
        sampler = StarvedSampler()
        tel.attach_sampler(sampler)
        tel.step(0)
        for step in (100, 200, 300):
            with t("Time/train_time"):
                _time.sleep(0.02)
            # nearly all "train" time was replay wait: hard starvation
            sampler.wait += 0.018
            sampler.calls += 1
            tel.observe_train(1, np.asarray([0.1]))
            tel.step(step)
        tel.close(300)
        events = read_events(str(tmp_path / "telemetry.jsonl"))
        diags = [e for e in events if e["event"] == "health" and e.get("status") == "diagnosis"]
        assert diags, events
        detectors = {f["detector"] for e in diags for f in e["findings"]}
        assert "prefetch_starvation" in detectors
        assert all(
            {"detector", "severity", "summary", "suggestion"} <= set(f)
            for e in diags
            for f in e["findings"]
        )
    finally:
        t.timers = saved
        t.disabled = saved_disabled


def test_unit_avals_preserve_sharding():
    """The dreamer-family register path abstracts one [T, B] slice of the staged
    [G, T, B] block; on a dp mesh the slice must keep its batch-axis sharding or
    program_analysis lowers a replicated variant (wrong FLOPs, cache miss)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from sheeprl_tpu.utils.mfu import unit_avals

    devices = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devices, ("data",))
    sharding = NamedSharding(mesh, PartitionSpec(None, None, "data"))
    block = jax.device_put(np.ones((2, 3, 8, 5), np.float32), sharding)
    avals = unit_avals({"x": block, "host": np.ones((2, 4), np.float32)})
    x = avals["x"]
    assert x.shape == (3, 8, 5)
    assert isinstance(x.sharding, NamedSharding)
    assert tuple(x.sharding.spec) == (None, "data")
    assert avals["host"].shape == (4,) and not hasattr(avals["host"], "mesh")


def test_profiler_window_truncated_by_run_end(tmp_path):
    """A window still open at loop exit is finalized by close() WITH a paired
    jsonl stop event (truncated=True), so start events are never orphaned."""
    import jax.numpy as jnp

    cfg = _cfg(
        telemetry={"enabled": True},
        profiler={"mode": "window", "start_step": 0, "num_steps": 10_000, "dir": str(tmp_path / "p")},
        log_every=1000,
    )
    t = build_telemetry(FakeFabric(), cfg, str(tmp_path))
    jnp.ones(4).block_until_ready()
    t.step(0)
    t.step(50)
    t.close(50)  # run ends long before num_steps
    prof = {e["action"]: e for e in read_events(str(tmp_path / "telemetry.jsonl")) if e["event"] == "profiler"}
    assert "start" in prof and "stop" in prof
    assert prof["stop"]["truncated"] is True and prof["stop"]["covered_steps"] == 50


def test_health_nonfinite_and_abort(tmp_path):
    cfg = _cfg(telemetry={"enabled": True, "abort_on_nonfinite": True}, log_every=10)
    t = build_telemetry(FakeFabric(), cfg, str(tmp_path))
    t.step(0)
    t.observe_train(1, np.asarray([1.0, math.nan]))
    with pytest.raises(RuntimeError, match="abort_on_nonfinite"):
        t.step(10)
    events = read_events(str(tmp_path / "telemetry.jsonl"))
    health = [e for e in events if e["event"] == "health"][0]
    assert health["status"] == "nonfinite" and health["nonfinite"] == ["loss[1]"]


def test_nonfinite_losses_shapes():
    assert _nonfinite_losses(np.asarray([1.0, 2.0])) == []
    assert _nonfinite_losses({"Loss/a": 1.0, "Loss/b": float("inf")}) == ["Loss/b"]
    assert _nonfinite_losses(jnp.asarray(float("nan"))) == ["loss"]


# ---------------------------------------------------------------------------------
# compile monitor + program analysis
# ---------------------------------------------------------------------------------
def test_compile_monitor_counts_backend_compiles():
    install_compile_monitor()
    before = compile_snapshot()

    @jax.jit
    def f(x):
        return x * 3.1 + 1

    f(jnp.ones(7)).block_until_ready()
    after = compile_snapshot()
    assert after["count"] >= before["count"] + 1
    assert after["seconds"] >= before["seconds"]


def test_register_program_reads_flops_donation_safe(tmp_path):
    cfg = _cfg(telemetry={"enabled": True}, log_every=10)
    t = build_telemetry(FakeFabric(), cfg, str(tmp_path))

    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def train(params, batch):
        return params + batch @ batch.T, jnp.sum(batch)

    params = jnp.zeros((4, 4))
    batch = jnp.ones((4, 8))
    params, _ = train(params, batch)  # params donated and rebound, like the loops
    assert t.wants_program("train")
    t.register_program("train", train, (params, batch), units=2)
    assert not t.wants_program("train")  # one-shot
    t.register_program("train", train, (params, batch), units=2)  # no-op, no error
    t.close(0)
    progs = [e for e in read_events(str(tmp_path / "telemetry.jsonl")) if e["event"] == "program"]
    assert len(progs) == 1
    assert progs[0]["name"] == "train" and progs[0]["flops"] > 0
    assert progs[0]["flops_per_unit"] == pytest.approx(progs[0]["flops"] / 2)


# ---------------------------------------------------------------------------------
# prefetch gauges
# ---------------------------------------------------------------------------------
def _tiny_buffer():
    from sheeprl_tpu.data.buffers import ReplayBuffer

    rb = ReplayBuffer(64, 2, obs_keys=("observations",))
    data = {
        "observations": np.ones((1, 2, 3), np.float32),
        "rewards": np.zeros((1, 2, 1), np.float32),
        "terminated": np.zeros((1, 2, 1), np.float32),
        "truncated": np.zeros((1, 2, 1), np.float32),
        "actions": np.zeros((1, 2, 2), np.float32),
    }
    for _ in range(8):
        rb.add(data)
    return rb, data


def test_prefetcher_telemetry_snapshot():
    from sheeprl_tpu.data.prefetch import ReplaySamplePrefetcher

    rb, data = _tiny_buffer()
    with ReplaySamplePrefetcher(rb, {"batch_size": 2}, depth=2) as sampler:
        sampler.sample(2)
        sampler.add(data)
        sampler.sample(2)
        snap = sampler.telemetry_snapshot()
    assert snap["is_async"] is True
    assert snap["sample_calls"] == 2 and snap["units"] == 4
    assert snap["wait_seconds"] > 0
    assert snap["pipeline_len"] >= 1 and snap["depth"] == 2
    # the staleness counter respects the bounded-staleness contract
    assert 0 <= snap["staleness_sum"] <= snap["units"] * sampler.depth


def test_sync_sampler_telemetry_snapshot():
    from sheeprl_tpu.data.prefetch import SyncReplaySampler

    rb, _ = _tiny_buffer()
    sampler = SyncReplaySampler(rb, {"batch_size": 2})
    sampler.sample(3)
    snap = sampler.telemetry_snapshot()
    assert snap["is_async"] is False
    assert snap["sample_calls"] == 1 and snap["units"] == 3
    assert snap["wait_seconds"] > 0 and snap["pipeline_len"] == 0


def test_window_prefetch_gauges(tmp_path):
    from sheeprl_tpu.data.prefetch import ReplaySamplePrefetcher

    logger = FakeLogger()
    cfg = _cfg(telemetry={"enabled": True}, log_every=10)
    t = build_telemetry(FakeFabric(), cfg, str(tmp_path), logger=logger)
    rb, data = _tiny_buffer()
    with ReplaySamplePrefetcher(rb, {"batch_size": 2}, depth=2) as sampler:
        t.attach_sampler(sampler)
        t.step(0)
        sampler.sample(2)
        sampler.add(data)
        t.step(10)
    t.close(10)
    window = [e for e in read_events(str(tmp_path / "telemetry.jsonl")) if e["event"] == "window"][0]
    assert window["prefetch"]["sample_calls"] == 1 and window["prefetch"]["units"] == 2
    assert window["prefetch"]["is_async"] is True
    gauges = logger.metrics[0][1]
    assert "Time/prefetch_wait" in gauges
    assert "Buffer/pipeline_occupancy" in gauges and "Buffer/pipeline_staleness" in gauges


# ---------------------------------------------------------------------------------
# profiler window (unit level; the CLI-driven e2e lives in test_algos/test_cli.py)
# ---------------------------------------------------------------------------------
def test_profiler_window_bounds(tmp_path):
    cfg = _cfg(
        telemetry={"enabled": False},
        profiler={"mode": "window", "start_step": 8, "num_steps": 4, "dir": str(tmp_path / "prof")},
    )
    t = build_telemetry(FakeFabric(), cfg, str(tmp_path))
    # profiler-only telemetry: not Null, but no JSONL machinery
    assert not t.enabled and t.profiler.mode == "window"
    for step in (0, 4, 8, 10, 12, 16):
        # keep some device work inside the would-be window
        jnp.ones(4).block_until_ready()
        t.step(step)
    t.close(16)
    assert t.profiler.started_at == 8
    assert t.profiler.stopped_at == 12  # first step >= start + num_steps
    dumped = list((tmp_path / "prof").rglob("*"))
    assert any(p.is_file() for p in dumped), "no trace files written"


# ---------------------------------------------------------------------------------
# mesh memory (2-D mesh satellite): max-across-mesh + per-device breakdown
# ---------------------------------------------------------------------------------
def test_mesh_device_memory_reports_max_and_per_device():
    from sheeprl_tpu.obs.telemetry import mesh_device_memory

    class _Dev:
        def __init__(self, id, in_use, peak=None):
            self.id = id
            self._stats = {"bytes_in_use": in_use}
            if peak is not None:
                self._stats["peak_bytes_in_use"] = peak

        def memory_stats(self):
            return self._stats

    class _NoStats:
        id = 99

        def memory_stats(self):
            return None

    devs = [_Dev(0, 100, peak=400), _Dev(1, 300, peak=250), _NoStats()]
    mem = mesh_device_memory(devs)
    # top-level keys report the WORST device (one hot model-axis shard OOMs a
    # run, not the mean); the breakdown names each device
    assert mem["bytes_in_use"] == 300
    assert mem["peak_bytes"] == 400
    per = {p["id"]: p for p in mem["per_device"]}
    assert per[0]["bytes_in_use"] == 100 and per[1]["bytes_in_use"] == 300
    assert 99 not in per  # stats-less devices don't pollute the breakdown

    # single reporting device: same top-level shape, no per_device noise
    solo = mesh_device_memory([_Dev(7, 42, peak=43)])
    assert solo == {"bytes_in_use": 42, "peak_bytes": 43}

    # no allocator stats anywhere (host CPU): None, exactly like device_memory
    assert mesh_device_memory([_NoStats()]) is None
    assert mesh_device_memory([]) is None


def test_telemetry_collects_local_mesh_devices():
    """A multi-device fabric's telemetry watches EVERY local mesh device, so a
    model-axis imbalance is visible in the window's hbm breakdown."""
    from sheeprl_tpu.obs.telemetry import RunTelemetry

    class _MeshFabric(FakeFabric):
        def __init__(self):
            super().__init__()
            self.devices = jax.devices("cpu")[:4]
            self.world_size = 4

    t = RunTelemetry(_MeshFabric(), _cfg(telemetry={"enabled": True, "jsonl": False}), None)
    try:
        assert len(t._devices) == 4
    finally:
        t.close(0)


def test_window_ring_gauges_ride_the_prefetch_block(tmp_path):
    from sheeprl_tpu.data.buffers import ReplayBuffer
    from sheeprl_tpu.data.device_ring import DeviceRingSampler

    logger = FakeLogger()
    cfg = _cfg(telemetry={"enabled": True}, log_every=10)
    t = build_telemetry(FakeFabric(), cfg, str(tmp_path), logger=logger)
    rb = ReplayBuffer(8, 2, obs_keys=("observations",), memmap=False)
    sampler = DeviceRingSampler(rb, {"batch_size": 2})
    rows = {
        "observations": np.ones((12, 2, 3), dtype=np.float32),
        "rewards": np.ones((12, 2, 1), dtype=np.float32),
    }
    t.attach_sampler(sampler)
    t.step(0)
    sampler.add(rows)  # 12 rows into 8: 4 x 2 envs overwritten
    t.step(10)
    t.close(10)
    window = [e for e in read_events(str(tmp_path / "telemetry.jsonl")) if e["event"] == "window"][0]
    ring = window["prefetch"]["ring"]
    assert ring["fill"] == 8 and ring["capacity"] == 8
    assert ring["occupancy"] == pytest.approx(1.0)
    assert ring["overwritten"] == 8
    gauges = dict(logger.metrics[-1][1])
    assert gauges["Buffer/ring_fill"] == 8.0
    assert gauges["Buffer/ring_occupancy"] == pytest.approx(1.0)
    assert gauges["Buffer/ring_overwritten"] == 8.0


def test_profiler_capture_dir_is_attempt_scoped(tmp_path):
    """Satellite: a supervised restart's capture must never collide with a
    prior attempt's — the dump dir is attempt-suffixed and the profiler events
    record the resolved path."""
    cfg = _cfg(
        telemetry={"enabled": True, "attempt": 2},
        profiler={"mode": "window", "start_step": 0, "num_steps": 4, "dir": str(tmp_path / "prof")},
        log_every=100,
    )
    t = build_telemetry(FakeFabric(), cfg, str(tmp_path))
    assert t.profiler.dump_dir == str(tmp_path / "prof" / "attempt_2")
    t.step(0)
    t.step(4)
    t.close(4)
    events = read_events(str(tmp_path / "telemetry.jsonl"))
    start = next(e for e in events if e["event"] == "start")
    assert start["profiler"]["dir"].endswith("attempt_2")
    prof = [e for e in events if e["event"] == "profiler"]
    assert prof and all(e["dir"].endswith("attempt_2") for e in prof)
    from sheeprl_tpu.obs.schema import validate_events

    assert validate_events(events) == []


def test_window_xla_gauges_after_a_profile_analysis(tmp_path):
    logger = FakeLogger()
    cfg = _cfg(telemetry={"enabled": True}, log_every=10)
    t = build_telemetry(FakeFabric(), cfg, str(tmp_path), logger=logger)
    t.step(0)
    # no capture yet: the xla gauges stay absent (no bogus zeros on TB)
    t.step(10)
    assert "Perf/xla_comm_fraction" not in dict(logger.metrics[-1][1])
    t._last_profile = {"fractions": {"comm": 0.31, "mxu": 0.5, "idle": 0.05}}
    t.step(20)
    gauges = dict(logger.metrics[-1][1])
    assert gauges["Perf/xla_comm_fraction"] == pytest.approx(0.31)
    assert gauges["Perf/xla_mxu_fraction"] == pytest.approx(0.5)
    assert gauges["Perf/xla_idle_fraction"] == pytest.approx(0.05)
    t.close(20)


# ---------------------------------------------------------------------------------
# the timer's real spans: window.spans / window.counters, and spans.jsonl on close
# ---------------------------------------------------------------------------------
def _one_iteration(t, iteration):
    import time as _time

    t.iteration = iteration
    with t("Time/env_interaction_time"):
        with t("act"):
            _time.sleep(0.004)
        with t("env_step"):
            _time.sleep(0.001)
    with t("Time/train_time"):
        with t("act_view"):
            t.count("act_view_bytes", 1024)
            _time.sleep(0.002)


@pytest.mark.parametrize("close", ["clean", "unclean"])
def test_window_spans_block_and_spans_jsonl(tmp_path, close):
    """Each window carries the spans that ENDED in it as {name: [count, seconds,
    self_seconds]} and the counters' gain; close (the loop's, or the crash path's that a
    StopRun from the harness takes) writes the ring's raw spans beside the stream."""
    import collections

    from sheeprl_tpu.obs.schema import validate_stream
    from sheeprl_tpu.obs.telemetry import close_all_live_telemetry
    from sheeprl_tpu.utils.timer import timer as t

    saved = (t.timers, t.disabled, t.ring, t.counters)
    t.timers, t.disabled, t.ring, t.counters = {}, False, collections.deque(maxlen=64), {}
    try:
        tel = build_telemetry(FakeFabric(), _cfg(telemetry={"enabled": True}, log_every=100), str(tmp_path))
        _one_iteration(t, 0)  # before the anchor: in no window
        tel.step(0)
        _one_iteration(t, 1)
        _one_iteration(t, 2)
        tel.step(100)
        _one_iteration(t, 3)
        tel.step(200)
        _one_iteration(t, 4)  # after the last window: only in spans.jsonl
        if close == "clean":
            tel.close(200)
        else:
            close_all_live_telemetry(clean_exit=False)
        stream = str(tmp_path / "telemetry.jsonl")
        assert validate_stream(stream) == []  # `spans` and `counters` are declared
        first, second = [e for e in read_events(stream) if e["event"] == "window"][:2]
        assert {k: v[0] for k, v in first["spans"].items()} == {
            "Time/env_interaction_time": 2, "act": 2, "env_step": 2, "Time/train_time": 2, "act_view": 2,
        }
        assert second["spans"]["act"][0] == 1 and second["counters"] == {"act_view_bytes": [1, 1024.0]}
        assert first["counters"] == {"act_view_bytes": [2, 2048.0]}
        count, seconds, self_seconds = first["spans"]["Time/env_interaction_time"]
        children = first["spans"]["act"][1] + first["spans"]["env_step"][1]
        assert seconds >= children and abs(self_seconds - (seconds - children)) < 1e-5
        assert first["spans"]["act"][1] == first["spans"]["act"][2] >= 0.008  # a leaf: all of it is its own
        # the phases are what they were: the span block adds, it does not replace
        assert abs(first["phases"]["env"] - seconds) < 1e-3
        rows = [json.loads(line) for line in open(tmp_path / "spans.jsonl")]
        assert len(rows) == 25 and {r["iter"] for r in rows} == {0, 1, 2, 3, 4}
        assert all(r["end"] >= r["start"] > 1e9 for r in rows)  # wall-clock seconds, like `time`
        assert {(r["name"], r["parent"]) for r in rows} == {
            ("Time/env_interaction_time", None), ("act", "Time/env_interaction_time"),
            ("env_step", "Time/env_interaction_time"), ("Time/train_time", None), ("act_view", "Time/train_time"),
        }
    finally:
        t.timers, t.disabled, t.ring, t.counters = saved


def test_telemetry_off_writes_no_spans_file(tmp_path):
    from sheeprl_tpu.utils.timer import timer as t

    saved_disabled, t.disabled = t.disabled, False
    try:
        tel = build_telemetry(FakeFabric(), _cfg(telemetry={"enabled": False}), str(tmp_path))
        with t("Time/train_time"):
            pass
        tel.step(0)
        tel.step(500)
        tel.close(500)
        assert list(tmp_path.iterdir()) == []
    finally:
        t.disabled = saved_disabled


@pytest.mark.parametrize("name, beside", [
    ("telemetry.jsonl", "spans.jsonl"),
    ("telemetry.learner.jsonl", "spans.learner.jsonl"),
    ("events.jsonl", "events.spans.jsonl"),  # a configured jsonl_path: any name
    ("events.learner.jsonl", "events.learner.spans.jsonl"),
    ("stream", "stream.spans.jsonl"),
    ("spans.jsonl", "spans.spans.jsonl"),
])
def test_spans_path_is_beside_the_stream_and_never_the_stream(name, beside):
    from sheeprl_tpu.obs.jsonl import spans_path

    assert spans_path(os.path.join("a", name)) == os.path.join("a", beside)


@pytest.mark.parametrize("case", ["custom_stream_name", "spans_unwritable", "restart_appends"])
def test_close_keeps_the_stream_whatever_happens_to_the_spans(tmp_path, case):
    """`close()` writes the raw spans BESIDE the stream: a stream of any name survives
    it whole, a spans file that cannot be written costs a warning and neither the
    summary nor the closing of the sink, and a restart into the same log dir appends
    its attempt's spans to those before it."""
    import collections

    from sheeprl_tpu.utils.timer import timer as t

    saved = (t.timers, t.disabled, t.ring, t.counters)
    t.timers, t.disabled, t.ring, t.counters = {}, False, collections.deque(maxlen=64), {}
    stream = tmp_path / ("events.jsonl" if case == "custom_stream_name" else "telemetry.jsonl")
    spans = tmp_path / ("events.spans.jsonl" if case == "custom_stream_name" else "spans.jsonl")
    try:
        _one_iteration(t, 0)  # before this object: another run's, in the process's ring
        for attempt in range(2 if case == "restart_appends" else 1):
            tel = build_telemetry(
                FakeFabric(),
                _cfg(telemetry={"enabled": True, "jsonl_path": str(stream), "attempt": attempt}, log_every=100),
                str(tmp_path),
            )
            tel.step(0)
            _one_iteration(t, 1)
            tel.step(100)
            if case == "spans_unwritable":
                spans.mkdir()
                with pytest.warns(UserWarning, match="raw spans could not be written"):
                    tel.close(100)
                assert tel._sink is None
            else:
                tel.close(100)
        events = read_events(str(stream))
        assert [e["event"] for e in events if e["event"] in ("start", "summary")] == (
            ["start", "summary"] * (2 if case == "restart_appends" else 1)
        )
        if case != "spans_unwritable":
            rows = [json.loads(line) for line in open(spans)]
            assert {r["iter"] for r in rows} == {1}  # only what ended while this object lived
            assert [r["attempt"] for r in rows] == [0] * 5 + ([1] * 5 if case == "restart_appends" else [])
            assert {r["rank"] for r in rows} == {0}
    finally:
        t.timers, t.disabled, t.ring, t.counters = saved
