#!/usr/bin/env python
"""Benchmark harness: prints the headline metric as ONE JSON line.

Headline (default): PPO env-steps/sec on the reference's own benchmark conditions
(sheeprl/configs/exp/ppo_benchmarks.yaml — 65536 total steps, 1 sync CartPole env,
fabric accelerator=cpu, logging/checkpoints off). The reference's published wall-clock
for this exact config is 81.27 s on 4 CPUs (README.md:99-106 / BASELINE.md) →
806.4 env-steps/sec.

The headline line is printed AND FLUSHED the moment the PPO run finishes, before any
extra workload, so an interrupted bench still reports the headline. If the extras
complete inside their budget, one final combined JSON line (headline + extras) is
printed last — a parser taking the last JSON line gets everything, a parser that
stops at the first line gets the headline.

Select a single workload with BENCH_ALGO:
- ppo / a2c / sac — the reference's *_benchmarks exp configs verbatim, whole-run
  wall-clock (compile included), like the reference's benchmarks/benchmark.py.
- dreamer_v1 / dreamer_v2 / dreamer_v3 — the reference's dreamer_*_benchmarks
  conditions (tiny model,
  replay_ratio 1/16, sequence 64, batch 16). Reported as STEADY-STATE env-steps/sec:
  wall time over the post-compile window (policy steps after
  SHEEPRL_BENCH_STEADY_START, see run_dreamer), because the reference's 16384-step
  run takes ~26 min (1589.30 s → 10.3 sps on 4 CPUs, BASELINE.md) and a bounded
  bench must finish in minutes, not tens of minutes. The measurement conditions are
  recorded in the JSON line's ``conditions`` dict (steady_window_steps /
  steady_window_seconds / total_steps / baseline_sps).
  The reference benchmarks MsPacmanNoFrameskip-v4; ale_py is not installed in this
  image, so the env falls back to the pixel dummy env (same 64x64 rgb obs shape).
  The emulator is a sub-ms slice of the reference's ~97 ms/step, so the comparison
  is dominated by framework+training cost either way.
- ppo_anakin — the on-device env plane + Anakin fused rollout/train topology
  (envs/jax + algos/ppo/anakin.py): steady-state env-steps/sec with CartPole
  stepping INSIDE the jitted program. Scale jump vs the host `ppo` workload is
  structural (~100x: no host<->device handoff per env step); the fingerprint's
  ``env_backend`` field keeps the regression gate from diffing across planes.
- sac_anakin — the fully device-resident off-policy topology (envs/jax +
  data/device_ring.py + algos/sac/anakin.py): rollout, replay-ring write,
  uniform ring sample and G gradient steps fused into ONE donated jitted
  program, Pendulum stepping inside it. Steady-state env-steps/sec, plus a
  measured device-vs-local A/B (a short host `sac_benchmarks` window run in the
  same process) under ``conditions.device_vs_local`` — the acceptance bar is a
  >= 10x speedup over the host SAC loop. ``conditions.env_backend`` /
  ``conditions.buffer_backend`` and the fingerprint's matching fields keep the
  regression gate from ever diffing across replay planes.
- dreamer_v3_mfu — flagship-size (S preset) DV3 train-program MFU on the
  accelerator: FLOPs from XLA's own cost model over achieved step time vs chip
  peak (sheeprl_tpu/utils/mfu.py). Run as an extra when the dreamer_v3 workload
  reported an accelerator.
- dv3_2d_mesh — model-parallelism dryrun: DV3-L per-device parameter footprint
  on the named [2,4] data x model mesh vs the [8] replicated mesh, on 8
  virtual CPU devices (init-time only, never claims the chip). Bytes units
  gate lower-is-better under --against. SHEEPRL_BENCH_DV3_2D_SIZE overrides
  the preset.
- serve_load — the policy serving tier (sheeprl_tpu/serve) under synthetic
  open-loop load: trains a tiny PPO checkpoint, serves it through the
  continuous-batching slot-table server, and reports sessions/sec plus a
  nested p99 step-latency workload ("ms" units gate LOWER-is-better under
  --against). CPU-only; measures the serving machinery, not the model.
- fleet_ingest — the experience data-plane A/B (sheeprl_tpu/data/service.py):
  1-actor vs 2-actor service ingestion gangs plus a buffer.backend=local
  reference, with emulator-paced actors so the scaling number measures the
  data plane rather than CPU contention. Value = 2-actor ingest rows/sec,
  vs_baseline = the 2/1-actor scaling ratio (acceptance bar >= 1.5); learner
  sps, gradient-step rates and service queue depth ride in conditions.
- live_loop — the closed-loop flywheel (sheeprl_tpu/live, howto/live.md):
  trains a tiny SAC checkpoint, then runs one ``sheeprl.py live`` gang end to
  end — serving slots doubling as experience-service actors, an in-process
  learner training on the captured sessions, published weights hot-reloading
  into serving mid-traffic. Value = sessions/sec through the closed loop;
  ingested rows/sec and learner gradient-steps/sec ride as nested extras,
  reload count + dataflow in conditions. CPU-only; measures the loop's
  machinery, not the model.

The dreamer_v3 extra also records the MFU of the benchmark-size train program in
its ``conditions.train_mfu`` block (and mirrors ``mfu`` top-level).

Every workload runs on the device its config selects — nothing here probes for
a chip or demotes a workload to the CPU — and names that device under
``conditions.device`` (platform, device_kind, count, as JAX reports them). A
workload that fails makes the bench exit non-zero.

Every workload's ``conditions`` carries a ``fingerprint`` (git sha, config hash,
device kind/count — obs/fingerprint.py), so BENCH_r*.json files are
self-describing for the regression gate:

    python bench.py --against BENCH_prev.json --fail-on regression

diffs this bench against a previous one (workloads matched by metric name +
fingerprint-compatible conditions, default 5% relative threshold, ``--threshold
0.08`` / ``--threshold metric=0.1`` to tune), attaches ``regressions`` to the
final JSON line, and exits non-zero when the gate trips. The same diff is
available offline as ``python sheeprl.py bench-diff old.json new.json``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

BASELINES = {
    # total env steps, reference wall-clock seconds for the matching *_benchmarks exp
    # (BASELINE.md; a2c/sac/ppo are the README's 1-device, 4-CPU numbers)
    "ppo": (65536, 81.27),
    "a2c": (65536, 84.76),
    "sac": (65536, 320.21),
    "dreamer_v1": (16384, 2207.13),
    "dreamer_v2": (16384, 906.42),
    "dreamer_v3": (16384, 1589.30),
}

# Dreamer steady-state windows: warm up through learning_starts (1024, where the
# first train/act compiles land) plus post-compile steps, then measure to
# total_steps — sized per algorithm so the whole run fits the extra's budget even
# on a single CPU core (dv1's Gaussian RSSM step is the slowest
# per env step, so its window holds the fewest SECONDS despite not being the
# fewest steps).
DREAMER_WINDOWS = {
    # algo: (total_steps, steady_start)
    # dv1's window was 768 steps (repeat-run spread ~±5%); 1792 halves the
    # relative noise
    "dreamer_v1": (3072, 1280),
    # repeat runs showed ~±15% variance at a 1536-step window
    "dreamer_v2": (4096, 1536),
    "dreamer_v3": (3072, 1536),
}


def _dummy_pixel_overrides() -> list:
    return [
        "env=dummy",
        "env.id=discrete_dummy",
        "env.capture_video=False",
        "algo.cnn_keys.encoder=[rgb]",
        "algo.cnn_keys.decoder=[rgb]",
        "algo.mlp_keys.encoder=[]",
        "algo.mlp_keys.decoder=[]",
        "checkpoint.save_last=False",
        "metric.log_level=0",
        "metric.disable_timer=True",
    ]


def _bench_wallclock(algo: str) -> dict:
    """Whole-run wall-clock (compile included) vs the reference's wall-clock."""
    total_steps, ref_seconds = BASELINES[algo]
    baseline_sps = total_steps / ref_seconds

    from sheeprl_tpu.cli import run

    start = time.perf_counter()
    run([f"exp={algo}_benchmarks"])
    elapsed = time.perf_counter() - start
    sps = total_steps / elapsed
    return {
        "metric": f"{algo}_env_steps_per_sec",
        "value": round(sps, 2),
        "unit": "env-steps/sec",
        "vs_baseline": round(sps / baseline_sps, 3),
    }


def _device() -> dict:
    """The device THIS workload process ran on, as JAX reports it."""
    import jax

    device = jax.local_devices()[0]
    return {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "count": jax.local_device_count(),
    }


def _peak_memory() -> dict:
    """Peak memory of THIS workload process: device HBM peak when the backend
    reports allocator stats (TPU/GPU), host peak RSS always — so every BENCH
    JSON tracks memory alongside throughput."""
    out = {}
    try:
        import jax

        from sheeprl_tpu.obs.telemetry import device_memory

        mem = device_memory(jax.local_devices()[0])
        if mem and mem.get("peak_bytes"):
            out["hbm_peak_bytes"] = int(mem["peak_bytes"])
    except Exception:
        pass
    try:
        from sheeprl_tpu.obs.telemetry import rss_peak_bytes

        rss = rss_peak_bytes()
        if rss is not None:
            out["rss_peak_bytes"] = rss
    except Exception:
        pass
    return out


def _steady_window_run(args: list, steady_start: int) -> dict:
    """One training run with the BenchWindow active; returns its {steps, seconds}
    plus the run's final telemetry summary event under "telemetry" (the loops
    stream sps/compile/prefetch/memory gauges to a JSONL sink — see
    howto/observability.md — so the bench reads them back without re-measuring).

    SHEEPRL_BENCH_PROFILE=1 additionally opens a jax.profiler window over the
    steady region and attaches its op-category attribution (obs/xprof.py
    ``profile_analysis``) under "profile" — the per-workload answer to WHERE the
    steady device time goes (comm/mxu/copy/idle shares, per-program roofline)."""
    from sheeprl_tpu.cli import run

    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        steady_file = f.name
    with tempfile.NamedTemporaryFile(suffix=".telemetry.jsonl", delete=False) as f:
        telemetry_file = f.name
    profile_dir = None
    profile_args = []
    if os.environ.get("SHEEPRL_BENCH_PROFILE") not in (None, "", "0"):
        profile_dir = tempfile.mkdtemp(suffix=".bench-profile")
        profile_args = [
            "metric.profiler.mode=window",
            f"metric.profiler.start_step={steady_start}",
            "metric.profiler.num_steps=0",  # one loop iteration past the warmup
            f"metric.profiler.dir={profile_dir}",
        ]
    os.environ["SHEEPRL_BENCH_STEADY_FILE"] = steady_file
    os.environ["SHEEPRL_BENCH_STEADY_START"] = str(steady_start)
    try:
        run(
            args
            + [
                "metric.telemetry.enabled=true",
                f"metric.telemetry.jsonl_path={telemetry_file}",
            ]
            + profile_args
        )
        with open(steady_file) as f:
            steady = json.load(f)
        try:
            from sheeprl_tpu.obs.diagnose import diagnose_events
            from sheeprl_tpu.obs.jsonl import read_events

            events = read_events(telemetry_file)
            summaries = [e for e in events if e.get("event") == "summary"]
            if summaries:
                # the learning rollup is surfaced as its own conditions.learning
                # block (below), so it is excluded from the telemetry copy
                steady["telemetry"] = {
                    k: v
                    for k, v in summaries[-1].items()
                    if k not in ("event", "time", "learning")
                }
                if summaries[-1].get("learning"):
                    steady["learning"] = summaries[-1]["learning"]
            # the run's own fingerprint (exact resolved config + live device) —
            # this is what bench-diff matches workloads on
            starts = [e for e in events if e.get("event") == "start"]
            if starts and starts[-1].get("fingerprint"):
                steady["fingerprint"] = starts[-1]["fingerprint"]
            # the in-loop capture attribution (SHEEPRL_BENCH_PROFILE=1): the
            # fractions are already unit-tiled device-time shares, ready for
            # fraction-unit bench-diff gating
            profiles = [e for e in events if e.get("event") == "profile_analysis"]
            if profiles:
                steady["profile"] = {
                    k: profiles[-1].get(k)
                    for k in ("device_seconds", "categories", "programs")
                }
            # run the diagnosis detectors over the run's stream so BENCH JSONs
            # are regression-gateable on CAUSES (recompile storm, starved
            # pipeline, checkpoint-heavy windows), not just on env-steps/sec
            diag = diagnose_events(events)
            steady["diagnosis"] = {
                "findings": [
                    {k: f[k] for k in ("detector", "severity", "summary")}
                    for f in diag["findings"]
                ],
                "attribution": (diag["attribution"] or {}).get("named_fraction"),
            }
            # SLO replay (obs/slo.py): training floors default to disabled, so
            # this is usually empty — but a bench run under an operator's
            # objectives overlay carries its error-budget view in conditions.slo
            try:
                from sheeprl_tpu.obs.slo import slo_events

                slo_eval = slo_events(events)
                slo_block = slo_eval.get("slo") or {}
                if slo_block:
                    steady["slo"] = {
                        "worst": slo_block.get("worst"),
                        "budget_remaining": {
                            name: obj.get("budget_remaining")
                            for name, obj in (slo_block.get("objectives") or {}).items()
                        },
                        "firing": slo_eval.get("alerts", {}).get("firing", []),
                    }
            except Exception:
                pass
        except Exception:
            pass
        return steady
    finally:
        os.environ.pop("SHEEPRL_BENCH_STEADY_FILE", None)
        os.environ.pop("SHEEPRL_BENCH_STEADY_START", None)
        for p in (steady_file, telemetry_file):
            try:
                os.unlink(p)
            except OSError:
                pass
        if profile_dir is not None:
            shutil.rmtree(profile_dir, ignore_errors=True)


def _prefetch_ab_enabled(algo: str) -> bool:
    """Prefetch on/off A/B knob: SHEEPRL_BENCH_PREFETCH_AB=1/0 forces it; unset
    defaults to ON for the dreamer_v3 north star and the sac steady workload (the
    two loops the prefetch acceptance gate names) and OFF elsewhere — the off-run
    doubles the workload's wall-clock."""
    ab = os.environ.get("SHEEPRL_BENCH_PREFETCH_AB")
    if ab is not None:
        return ab not in ("0", "")
    return algo in ("dreamer_v3", "sac_steady")


def _steady_ab_result(
    ab_key: str, metric: str, args: list, total: int, steady_start: int, baseline_sps: float
) -> dict:
    """Shared steady-state measurement + result assembly: one window with the
    default config (prefetch on), optionally a second with
    ``buffer.prefetch.enabled=false``, both recorded under ``conditions.prefetch``."""
    steady = _steady_window_run(args, steady_start)
    sps = steady["steps"] / steady["seconds"]
    prefetch_cond = {"enabled_sps": round(sps, 2)}
    if _prefetch_ab_enabled(ab_key):
        steady_off = _steady_window_run(args + ["buffer.prefetch.enabled=false"], steady_start)
        off_sps = steady_off["steps"] / steady_off["seconds"]
        prefetch_cond["disabled_sps"] = round(off_sps, 2)
        prefetch_cond["speedup"] = round(sps / off_sps, 3) if off_sps > 0 else None
    conditions = {
        "steady_window_steps": steady["steps"],
        "steady_window_seconds": round(steady["seconds"], 2),
        "total_steps": total,
        "baseline_sps": round(baseline_sps, 2),
        "prefetch": prefetch_cond,
    }
    if "telemetry" in steady:
        # the prefetch-ON run's final telemetry summary: whole-run sps, compile
        # count/seconds, prefetch wait totals, peak memory — measured in-loop
        conditions["telemetry"] = steady["telemetry"]
    if "fingerprint" in steady:
        conditions["fingerprint"] = steady["fingerprint"]
    if "diagnosis" in steady:
        # the diagnose verdicts for the same run: detector findings + the share
        # of steady wall time attributed to named phases (obs/diagnose.py)
        conditions["diagnosis"] = steady["diagnosis"]
    if "learning" in steady:
        # the run's training-health rollup (grad norms, entropy, episode
        # returns — obs/telemetry.py learning summary): BENCH JSONs gate on
        # whether the run LEARNS, not just how fast it steps
        conditions["learning"] = steady["learning"]
    if "profile" in steady:
        # the steady window's op-category attribution (SHEEPRL_BENCH_PROFILE=1)
        conditions["profile"] = steady["profile"]
    if "slo" in steady:
        # error-budget view of the same run (obs/slo.py replay; only present
        # when an objective with a non-null target saw its signal)
        conditions["slo"] = steady["slo"]
    result = {
        "metric": metric,
        "value": round(sps, 2),
        "unit": "env-steps/sec (steady-state)",
        "vs_baseline": round(sps / baseline_sps, 3),
        "conditions": conditions,
    }
    extras = _learning_extras(metric, steady, conditions.get("fingerprint"))
    if extras:
        result["extras"] = extras
    return result


def _learning_extras(metric: str, steady: dict, fingerprint) -> list:
    """Nested gated learning workloads derived from the steady run's learning
    rollup: episode-return mean (unit "return", higher-is-better) and policy
    entropy (unit "nats", higher-is-better — bench-diff's direction is pinned
    by unit, so entropy can never be gated backwards). Each rides the parent's
    fingerprint so --against matches them like any workload."""
    learning = steady.get("learning") or {}
    stats = learning.get("stats") or {}
    episodes = learning.get("episodes") or {}
    extras = []
    cond = {"fingerprint": fingerprint} if fingerprint else {}
    if isinstance(episodes.get("return_mean"), (int, float)):
        extras.append(
            {
                "metric": f"{metric}_ep_return",
                "value": round(float(episodes["return_mean"]), 4),
                "unit": "return (mean episode return, steady run)",
                "vs_baseline": None,
                "conditions": dict(cond, episodes=episodes.get("count")),
            }
        )
    if isinstance(stats.get("entropy"), (int, float)):
        extras.append(
            {
                "metric": f"{metric}_entropy",
                "value": round(float(stats["entropy"]), 4),
                "unit": "nats (mean policy entropy, steady run)",
                "vs_baseline": None,
                "conditions": dict(cond),
            }
        )
    return extras


def _bench_dreamer_steady(algo: str = "dreamer_v3") -> dict:
    """Dreamer-family steady-state env-steps/sec over a bounded post-compile window.

    With the A/B knob on (see _prefetch_ab_enabled) the same window is measured a
    second time with ``buffer.prefetch.enabled=false`` and both numbers land in
    ``conditions.prefetch`` so the async-prefetch win is visible in BENCH_*.json.
    """
    total_steps, ref_seconds = BASELINES[algo]
    baseline_sps = total_steps / ref_seconds  # dv3: 10.31 sps on 4 CPUs

    args = [f"exp={algo}_benchmarks"]
    try:
        import ale_py  # noqa: F401
    except ImportError:
        args += _dummy_pixel_overrides()
    total, steady_start = DREAMER_WINDOWS[algo]
    args += [f"algo.total_steps={total}"]

    result = _steady_ab_result(
        algo, f"{algo}_env_steps_per_sec", args, total, steady_start, baseline_sps
    )
    if algo == "dreamer_v3":
        # MFU of the fused train program at the exact benchmark shapes (the act
        # program is host-side by design; the train program is where the FLOPs are)
        result["conditions"]["train_mfu"] = _dv3_train_mfu(size=None)
        result["mfu"] = result["conditions"]["train_mfu"].get("mfu")
    return result


def _dv3_train_mfu(size: str | None = None, reps: int = 5) -> dict:
    """MFU of the fused Dreamer-V3 train program. ``size=None`` uses the benchmark
    exp's tiny model at the exact shapes the steady-state run compiles (cache hit);
    a preset name ('S', 'M', ...) measures a flagship-size program instead — the
    number that shows whether the design can feed the MXU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import gymnasium as gym
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_phase
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose, instantiate
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.utils.mfu import measure_mfu

    if size is None:
        overrides = ["exp=dreamer_v3_benchmarks"] + _dummy_pixel_overrides()
    else:
        overrides = [
            "exp=dreamer_v3",
            f"algo=dreamer_v3_{size}",
            "algo.per_rank_batch_size=16",
            "algo.per_rank_sequence_length=64",
        ] + _dummy_pixel_overrides()
    cfg = compose(overrides)

    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    actions_dim = (2,)  # matches DiscreteDummyEnv's action space in the steady run
    fabric = Fabric(devices=1)
    fabric._setup()
    agent, params = build_agent(fabric, actions_dim, False, cfg, obs_space, jax.random.PRNGKey(0))

    def _tx(opt_cfg, clip):
        base = instantiate(opt_cfg)
        return optax.chain(optax.clip_by_global_norm(clip), base) if clip else base

    world_tx = _tx(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
    actor_tx = _tx(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_tx = _tx(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    opt_state = {
        "world_model": world_tx.init(params["world_model"]),
        "actor": actor_tx.init(params["actor"]),
        "critic": critic_tx.init(params["critic"]),
    }
    train_phase = make_train_phase(agent, cfg, world_tx, actor_tx, critic_tx)

    T, B = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    rng = np.random.default_rng(0)
    batch = {
        "rgb": rng.integers(0, 255, (T, B, 3, 64, 64)).astype(np.uint8),
        "actions": np.eye(2, dtype=np.float32)[rng.integers(0, 2, (T, B))],
        "rewards": rng.normal(size=(T, B, 1)).astype(np.float32),
        "terminated": np.zeros((T, B, 1), np.float32),
        "truncated": np.zeros((T, B, 1), np.float32),
        "is_first": np.zeros((T, B, 1), np.float32),
    }
    # the compiled unit is the single fused gradient step the host G-loop drives
    stats = measure_mfu(
        train_phase.train_step,
        (
            params,
            opt_state,
            init_moments(),
            batch,
            jnp.asarray(1),  # cum step 1: skips the tau=1 hard target sync branch
            jnp.asarray(jax.random.PRNGKey(0)),
        ),
        reps=reps,
        device=fabric.device,
    )
    stats["shapes"] = {"T": T, "B": B, "size": size or "benchmark-tiny"}
    return stats


def _sac_host_fallback_overrides() -> list:
    """Host-SAC benchmark fallback when Box2D (LunarLanderContinuous's backend)
    is not installed: the continuous dummy env at the same MLP shapes."""
    try:
        import Box2D  # noqa: F401  (gymnasium's LunarLanderContinuous backend)

        return []
    except ImportError:
        return [
            "env=dummy",
            "env.id=continuous_dummy",
            "env.capture_video=False",
            "algo.mlp_keys.encoder=[state]",
            "checkpoint.save_last=False",
            "metric.log_level=0",
            "metric.disable_timer=True",
        ]


def _bench_sac_steady() -> dict:
    """SAC steady-state env-steps/sec over a bounded post-compile window (the
    BenchWindow in sac.py), with the prefetch on/off A/B recorded like the dreamer
    steady bench. The whole-run `sac` wall-clock workload stays untouched."""
    total_steps, ref_seconds = BASELINES["sac"]
    baseline_sps = total_steps / ref_seconds

    args = ["exp=sac_benchmarks"] + _sac_host_fallback_overrides()
    total, steady_start = 6144, 2048  # warmup spans learning_starts (100) + compiles
    args += [f"algo.total_steps={total}"]

    return _steady_ab_result(
        "sac_steady", "sac_env_steps_per_sec", args, total, steady_start, baseline_sps
    )


def _bench_ppo_anakin() -> dict:
    """ppo_anakin steady-state env-steps/sec: the on-device env plane + Anakin
    fused rollout/train topology (exp=ppo_anakin_benchmarks — CartPole inside
    the jitted program, 8192 envs x 128 rollout steps per call). Reported over
    the post-compile BenchWindow like the other steady workloads; the number is
    on a ~100x different scale than the host `ppo` workload BY DESIGN (no
    host<->device handoff per env step), and ``conditions.env_backend`` plus the
    fingerprint's ``env_backend`` keep the regression gate from ever diffing it
    against a host-env run."""
    total_steps, ref_seconds = BASELINES["ppo"]
    baseline_sps = total_steps / ref_seconds  # the reference's host PPO, 4 CPUs

    total = 16_777_216  # 16 fused iterations of 1048576 env steps
    steady_start = 2_097_152  # 2 iterations of warmup: compile + cache effects
    args = [
        "exp=ppo_anakin_benchmarks",
        f"algo.total_steps={total}",
        # one telemetry window per fused iteration, so the run's diagnosis
        # verdict gets steady windows (not just the final close window) and the
        # rollout/train attribution lands in conditions.diagnosis
        "metric.telemetry.every=1048576",
    ]

    steady = _steady_window_run(args, steady_start)
    sps = steady["steps"] / steady["seconds"]
    conditions = {
        "steady_window_steps": steady["steps"],
        "steady_window_seconds": round(steady["seconds"], 2),
        "total_steps": total,
        "baseline_sps": round(baseline_sps, 2),
        # which environment plane stepped the run — the workload's defining axis
        "env_backend": "jax",
    }
    for key in ("telemetry", "fingerprint", "diagnosis", "learning", "profile", "slo"):
        if key in steady:
            conditions[key] = steady[key]
    result = {
        "metric": "ppo_anakin_env_steps_per_sec",
        "value": round(sps, 2),
        "unit": "env-steps/sec (steady-state)",
        "vs_baseline": round(sps / baseline_sps, 3),
        "conditions": conditions,
    }
    extras = _learning_extras("ppo_anakin", steady, conditions.get("fingerprint"))
    if extras:
        result["extras"] = extras
    return result


def _bench_sac_anakin() -> dict:
    """sac_anakin steady-state env-steps/sec: the fully device-resident
    off-policy topology (exp=sac_anakin_benchmarks — Pendulum + the replay ring
    + G gradient steps inside ONE donated jitted program, 512 envs x 64 rollout
    steps per call). Reported over the post-compile BenchWindow like
    ppo_anakin, and paired with a MEASURED device-vs-local A/B: a short host
    ``sac_benchmarks`` steady window run in the same process, recorded under
    ``conditions.device_vs_local`` with the speedup ratio (acceptance bar
    >= 10x). The scale jump is structural — no host<->device handoff per env
    step AND no host replay round-trip per gradient step — and
    ``conditions.env_backend``/``conditions.buffer_backend`` plus the
    fingerprint's matching fields keep the regression gate from ever diffing it
    against a host-replay run."""
    total_steps, ref_seconds = BASELINES["sac"]
    baseline_sps = total_steps / ref_seconds  # the reference's host SAC, 4 CPUs

    total = 2_097_152  # 64 fused iterations of 32768 env steps
    steady_start = 65_536  # 2 iterations of warmup: compile + cache effects
    args = [
        "exp=sac_anakin_benchmarks",
        f"algo.total_steps={total}",
        # one telemetry window per fused iteration (see _bench_ppo_anakin)
        "metric.telemetry.every=32768",
    ]

    steady = _steady_window_run(args, steady_start)
    sps = steady["steps"] / steady["seconds"]

    # the device-vs-local A/B control: the HOST loop (gymnasium env, host
    # ReplayBuffer, per-G-step host<->device round trips) on a short window —
    # sac_steady's exact conditions, bounded so the control costs seconds
    local_total, local_start = 4096, 2048
    local_args = (
        ["exp=sac_benchmarks"]
        + _sac_host_fallback_overrides()
        + [f"algo.total_steps={local_total}"]
    )
    local_steady = _steady_window_run(local_args, local_start)
    local_sps = local_steady["steps"] / local_steady["seconds"]
    device_vs_local = {
        "device_sps": round(sps, 2),
        "local_sps": round(local_sps, 2),
        "speedup": round(sps / local_sps, 2) if local_sps > 0 else None,
        "local_window": {
            "steps": local_steady["steps"],
            "seconds": round(local_steady["seconds"], 2),
            "total_steps": local_total,
        },
    }

    conditions = {
        "steady_window_steps": steady["steps"],
        "steady_window_seconds": round(steady["seconds"], 2),
        "total_steps": total,
        "baseline_sps": round(baseline_sps, 2),
        # the workload's two defining axes: which plane stepped the envs and
        # which plane fed the gradient steps
        "env_backend": "jax",
        "buffer_backend": "device",
        "device_vs_local": device_vs_local,
    }
    for key in ("telemetry", "fingerprint", "diagnosis", "learning", "profile", "slo"):
        if key in steady:
            conditions[key] = steady[key]
    result = {
        "metric": "sac_anakin_env_steps_per_sec",
        "value": round(sps, 2),
        "unit": "env-steps/sec (steady-state)",
        "vs_baseline": round(sps / baseline_sps, 3),
        "conditions": conditions,
    }
    extras = _learning_extras("sac_anakin", steady, conditions.get("fingerprint"))
    if extras:
        result["extras"] = extras
    return result


def _bench_dv3_2d_mesh(size: str = "L") -> dict:
    """2-D mesh GSPMD dryrun workload: DV3-``size`` (default L) parameters
    built on the named ``[2, 4]`` data x model CPU mesh (8 virtual devices) vs
    the ``[8]`` replicated data mesh, recording the per-device parameter
    footprint, RSS, and (on a real chip mesh) HBM for each — the
    model-parallelism acceptance number for MULTICHIP JSONs, gateable with
    ``--against`` (bytes units are lower-is-better in bench-diff). Pure
    init-time measurement on the virtual CPU mesh: no accelerator claim, no
    train step (the train-program collectives are covered by the AOT suite,
    tests/test_parallel/test_mesh_2d.py)."""
    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
        # virtual-CPU-mesh workload by definition: it asks for the CPU backend
        # and never claims a chip
        os.environ["JAX_PLATFORMS"] = "cpu"
    import gymnasium as gym
    import jax
    import numpy as np

    if len(jax.devices("cpu")) < 8:
        raise RuntimeError(
            "dv3_2d_mesh needs 8 virtual CPU devices; run in a fresh process or set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax imports"
        )

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.obs.fingerprint import run_fingerprint
    from sheeprl_tpu.obs.telemetry import mesh_device_memory, rss_peak_bytes
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.parallel.sharding import per_device_bytes, sharding_summary

    cfg = compose(["exp=dreamer_v3", f"algo=dreamer_v3_{size}"] + _dummy_pixel_overrides())
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    actions_dim = (4,)

    def measure(mesh_shape, axis_names):
        fabric = Fabric(
            devices=-1, accelerator="cpu", mesh_shape=mesh_shape, axis_names=axis_names
        )
        fabric._setup()
        _, params = build_agent(fabric, actions_dim, False, cfg, obs_space, jax.random.PRNGKey(0))
        if not fabric.model_parallel:
            # the [8] data mesh replicates params on every device — materialize
            # that placement so the footprint/RSS numbers are measured, not assumed
            params = fabric.replicate_pytree(params)
        jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
        footprint = per_device_bytes(params)
        entry = {
            "mesh_shape": list(mesh_shape),
            "axis_names": list(axis_names),
            "param_bytes_per_device": {str(k): v for k, v in sorted(footprint.items())},
            "param_bytes_per_device_max": max(footprint.values()),
            "hbm": mesh_device_memory(fabric.devices),
            "rss_peak_bytes": rss_peak_bytes(),
            **sharding_summary(params),
        }
        fingerprint = run_fingerprint(cfg, fabric)
        del params  # free the tree before the next mesh materializes
        return entry, fingerprint

    replicated, _ = measure([8], ["data"])
    sharded, fingerprint = measure([2, 4], ["data", "model"])

    return {
        "metric": "dv3_2d_mesh_param_bytes_per_device",
        "value": sharded["param_bytes_per_device_max"],
        "unit": "bytes/device (DV3 params, [2,4] data x model mesh)",
        # vs the replicated [8] mesh: < 1.0 is the model-parallel win
        "vs_baseline": round(
            sharded["param_bytes_per_device_max"]
            / max(replicated["param_bytes_per_device_max"], 1),
            4,
        ),
        "conditions": {
            "model_size": size,
            "mesh_shape": sharded["mesh_shape"],
            "axis_names": sharded["axis_names"],
            "sharded": sharded,
            "replicated": replicated,
            "fingerprint": fingerprint,
        },
    }


def _bench_serve_load(
    slots: int = 8, sessions: int = 48, steps_per_session: int = 64
) -> dict:
    """``serve_load``: the policy serving tier under synthetic open-loop load
    (sheeprl_tpu/serve, howto/serving.md). Trains a tiny PPO checkpoint, then
    drives ``sessions`` fixed-length synthetic sessions through the
    continuous-batching server (``slots`` device-resident slots) with arrivals
    never gated on completions, and reports sessions/sec with the p99 step
    latency riding as a nested extra workload — latency units gate
    LOWER-is-better under ``--against`` (obs/compare.py ``_lower_is_better``).
    The robustness plane is exercised too: a hot reload lands MID-LOAD (a new
    checkpoint saved while sessions run; the reloader applies it — recorded
    under ``conditions.reload``), and a second bounded-queue overload burst
    measures ``serve_load_shed_rate`` (unit "fraction", lower-is-better: more
    shedding at the same offered load = capacity regressed). CPU-only by
    construction (the checkpoint is tiny); the numbers measure the serving
    machinery — batching, slot table, donated step program — not the model."""
    import shutil
    import threading

    from sheeprl_tpu.cli import run

    workdir = tempfile.mkdtemp(prefix="sheeprl-serve-load-")
    try:
        run(
            [
                "exp=ppo",
                "env=dummy",
                "env.id=discrete_dummy",
                "env.num_envs=2",
                "env.capture_video=False",
                "fabric.accelerator=cpu",
                "algo.rollout_steps=16",
                "algo.total_steps=128",
                "algo.update_epochs=1",
                "algo.cnn_keys.encoder=[]",
                "algo.mlp_keys.encoder=[state]",
                "algo.run_test=False",
                "metric.log_level=0",
                "metric.disable_timer=True",
                "checkpoint.save_last=True",
                f"hydra.run.dir={workdir}/train",
            ]
        )

        from sheeprl_tpu.parallel.fabric import Fabric
        from sheeprl_tpu.serve.drivers import run_synthetic_load
        from sheeprl_tpu.serve.main import build_serve_cfg
        from sheeprl_tpu.serve.policy import resolve_serve_policy
        from sheeprl_tpu.serve.server import PolicyServer
        from sheeprl_tpu.serve.telemetry import ServingTelemetry
        from sheeprl_tpu.utils.checkpoint import load_checkpoint
        from sheeprl_tpu.obs.jsonl import read_events

        cfg = build_serve_cfg(
            [
                f"checkpoint_path={workdir}/train",
                f"serve.slots={slots}",
                "serve.max_batch_wait_ms=2.0",
            ]
        )
        fabric = Fabric(devices=1, accelerator="cpu")
        fabric._setup()
        state = load_checkpoint(cfg.checkpoint_path)
        policy = resolve_serve_policy(fabric, cfg, state)

        telemetry_path = os.path.join(workdir, "telemetry.jsonl")
        telemetry = ServingTelemetry(
            fabric,
            cfg,
            None,
            every=max((sessions * steps_per_session) // 16, 64),
            serve_info={"slots": slots, "workload": "serve_load"},
            jsonl_path=telemetry_path,
        )
        server = PolicyServer(
            policy,
            slots=slots,
            max_batch_wait_ms=float(cfg.serve.max_batch_wait_ms),
            base_seed=int(cfg.seed),
            telemetry=telemetry,
        )
        # warm the step/attach programs BEFORE load arrives (the serve.prime
        # story): the measured latencies then reflect steady-state serving,
        # not the one-time XLA compile landing inside the first window
        import numpy as np

        server.table.step(
            {k: spec.zeros(slots) for k, spec in policy.obs_spec.items()},
            np.zeros((slots,), np.bool_),
        )
        server.table.attach({0: int(cfg.seed)})

        # hot reload, exercised mid-load: a newer checkpoint lands while the
        # open-loop sessions run and the reload thread swaps it in (same avals,
        # zero recompiles — the summary's compile count stays flat)
        from sheeprl_tpu.serve.reload import CheckpointReloadSource, WeightReloader
        from sheeprl_tpu.utils.checkpoint import save_checkpoint

        ckpt_dir = os.path.dirname(cfg.checkpoint_path)
        reloader = WeightReloader(
            server,
            CheckpointReloadSource(
                ckpt_dir, fabric, cfg, current_path=str(cfg.checkpoint_path)
            ),
            telemetry=telemetry,
            poll_s=0.1,
        )

        def _publish_newer_checkpoint() -> None:
            time.sleep(0.4)  # let the load reach steady state first
            save_checkpoint(os.path.join(ckpt_dir, "ckpt_999128_0.ckpt"), state)

        publisher = threading.Thread(target=_publish_newer_checkpoint, daemon=True)

        with server:
            reloader.start()
            publisher.start()
            load = run_synthetic_load(
                server,
                sessions=sessions,
                steps_per_session=steps_per_session,
                seed=int(cfg.seed),
            )
            publisher.join(timeout=10)
            reloader.stop()

        # overload burst phase: the SAME policy behind a bounded admission
        # queue, offered 6x its (slots + queue) capacity at once — the shed
        # fraction is the gateable overload-protection number (a faster server
        # turns sessions over during the burst and sheds less)
        burst_sessions = 6 * (slots + slots)  # 6x (slots + max_queue) below
        burst_steps = steps_per_session
        shed_server = PolicyServer(
            policy,
            slots=slots,
            max_batch_wait_ms=float(cfg.serve.max_batch_wait_ms),
            base_seed=int(cfg.seed) + 1,
            max_queue=slots,
        )
        with shed_server:
            shed_load = run_synthetic_load(
                shed_server,
                sessions=burst_sessions,
                steps_per_session=burst_steps,
                arrival_interval_s=0.001,
                seed=int(cfg.seed) + 1,
            )

        events = read_events(telemetry_path)
        summary = next((e for e in reversed(events) if e.get("event") == "summary"), {})
        start = next((e for e in events if e.get("event") == "start"), {})
        serve_summary = summary.get("serve") or {}
        latency = serve_summary.get("latency_ms") or {}
        windows = [e for e in events if e.get("event") == "window"]
        occupancy = [
            (w.get("serve") or {}).get("occupancy")
            for w in windows
            if (w.get("serve") or {}).get("occupancy") is not None
        ]
        queue_depths = [
            (w.get("serve") or {}).get("queue_depth")
            for w in windows
            if (w.get("serve") or {}).get("queue_depth") is not None
        ]
        fingerprint = start.get("fingerprint")

        # SLO replay (obs/slo.py): run the recorded stream back through the
        # exact in-loop evaluator/alert machinery so the bench row carries the
        # error-budget view of the same load it just measured
        slo_summary = None
        try:
            from sheeprl_tpu.obs.slo import slo_events

            slo_eval = slo_events(events, run_dir=workdir)
            slo_block = slo_eval.get("slo") or {}
            slo_summary = {
                "worst": slo_block.get("worst"),
                "budget_remaining": {
                    name: obj.get("budget_remaining")
                    for name, obj in (slo_block.get("objectives") or {}).items()
                },
                "firing": slo_eval.get("alerts", {}).get("firing", []),
                "worst_firing_severity": slo_eval.get("worst_firing_severity"),
                "windows": slo_eval.get("windows"),
            }
        except Exception:
            slo_summary = None

        conditions = {
            "slots": slots,
            "max_batch_wait_ms": float(cfg.serve.max_batch_wait_ms),
            "sessions": sessions,
            "steps_per_session": steps_per_session,
            "steps_per_sec": load["steps_per_sec"],
            "load_errors": load["errors"],
            "latency_ms": latency,
            "occupancy_mean": round(sum(occupancy) / len(occupancy), 4) if occupancy else None,
            # the serving tier's dataflow summary, mirroring the fleet_ingest
            # shape; latency/occupancy live in the sibling keys above — only
            # the queue/session view is new here
            "dataflow": {
                "queue_depth_mean": (
                    round(sum(queue_depths) / len(queue_depths), 3) if queue_depths else None
                ),
                "sessions_per_sec": serve_summary.get("sessions_per_sec"),
            },
            # the hot reload exercised mid-load (serve/reload.py): versions
            # applied + failures from the summary's cumulative weights block
            "reload": {
                **(serve_summary.get("weights") or {}),
                "applied_mid_load": reloader.applied,
            },
            "telemetry": {
                k: v for k, v in summary.items() if k not in ("event", "time", "seq")
            },
            "slo": slo_summary,
            "fingerprint": fingerprint,
        }
        p99 = latency.get("p99")
        result = {
            "metric": "serve_load_sessions_per_sec",
            "value": load["sessions_per_sec"],
            "unit": "sessions/sec (open-loop synthetic load)",
            "vs_baseline": None,  # first serving tier — no reference number exists
            "conditions": conditions,
        }
        extras = []
        if p99 is not None:
            # the latency companion gates independently; "ms" units are
            # lower-is-better in bench-diff (verified by test_compare)
            extras.append(
                {
                    "metric": "serve_load_step_latency_p99_ms",
                    "value": p99,
                    "unit": "ms (p99 step latency)",
                    "vs_baseline": None,
                    "conditions": {
                        "slots": slots,
                        "sessions": sessions,
                        "p50_ms": latency.get("p50"),
                        "fingerprint": fingerprint,
                    },
                }
            )
        # "fraction" units gate lower-is-better (obs/compare.py): shedding
        # MORE of the same offered burst means serving capacity regressed
        extras.append(
            {
                "metric": "serve_load_shed_rate",
                "value": shed_load["shed_rate"],
                "unit": "fraction (sessions shed / offered, 6x overload burst)",
                "vs_baseline": None,
                "conditions": {
                    "slots": slots,
                    "max_queue": slots,
                    "sessions_offered": burst_sessions,
                    "sessions_finished": shed_load["sessions_finished"],
                    "sessions_shed": shed_load["sessions_shed"],
                    "steps_per_session": burst_steps,
                    "arrival_interval_s": 0.001,
                    "fingerprint": fingerprint,
                },
            }
        )
        # the SLO companion gates the OTHER direction: "fraction" units default
        # to lower-is-better in bench-diff, so this workload pins
        # direction=higher explicitly (error budget REMAINING — burning it down
        # is the regression)
        worst = (slo_summary or {}).get("worst") or {}
        if worst.get("budget_remaining") is not None:
            extras.append(
                {
                    "metric": "serve_load_budget_remaining",
                    "value": worst["budget_remaining"],
                    "unit": "fraction (worst-objective error budget remaining)",
                    "direction": "higher",
                    "vs_baseline": None,
                    "conditions": {
                        "objective": worst.get("objective"),
                        "firing": (slo_summary or {}).get("firing"),
                        "windows": (slo_summary or {}).get("windows"),
                        "fingerprint": fingerprint,
                    },
                }
            )
        result["extras"] = extras
        return result
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _bench_live_loop(
    sessions: int = 2, session_rounds: int = 12, max_session_steps: int = 20
) -> dict:
    """``live_loop``: the closed-loop serve→experience→learn→reload flywheel
    (sheeprl_tpu/live, howto/live.md). Trains a tiny SAC checkpoint, then runs
    ONE ``sheeprl.py live`` gang to completion on the dummy env: ``sessions``
    concurrent sessions per wave for ``session_rounds`` paced waves, serving
    slots doubling as experience-service actors, the in-process service
    learner training on the captured trajectories and publishing, every
    published version hot-reloading into serving mid-traffic. Reports
    sessions/sec through the CLOSED loop (wave pacing included — it is part of
    the loop's design, recorded in conditions), with ingested rows/sec and the
    learner's gradient-step rate as nested extras and the reload count +
    dataflow view in ``conditions``. CPU-only by construction."""
    import shutil

    from sheeprl_tpu.cli import run
    from sheeprl_tpu.live.runner import live_main
    from sheeprl_tpu.obs.jsonl import read_events

    workdir = tempfile.mkdtemp(prefix="sheeprl-live-loop-")
    try:
        run(
            [
                "exp=sac",
                "env=dummy",
                "env.id=continuous_dummy",
                "env.sync_env=True",
                "env.capture_video=False",
                "fabric.accelerator=cpu",
                "metric.log_level=0",
                "buffer.memmap=False",
                "buffer.size=256",
                "env.num_envs=1",
                "algo.mlp_keys.encoder=[state]",
                "algo.learning_starts=8",
                "algo.total_steps=16",
                "algo.run_test=False",
                "algo.per_rank_batch_size=4",
                "checkpoint.save_last=True",
                "checkpoint.every=8",
                f"hydra.run.dir={workdir}/train",
            ]
        )

        live_dir = os.path.join(workdir, "live")
        spec_path = os.path.join(workdir, "live_bench.yaml")
        wave_pause_s = 0.3
        spec = {
            "name": "live_bench",
            "checkpoint_path": os.path.join(workdir, "train"),
            "servers": 1,
            "sessions": sessions,
            "session_rounds": session_rounds,
            "wave_pause_s": wave_pause_s,
            "max_session_steps": max_session_steps,
            "log_dir": live_dir,
            "serve": {
                "slots": max(sessions, 2),
                "max_batch_wait_ms": 1.0,
                "telemetry": {"every": 8},
                "explore": {"fraction": 0.5, "noise": 0.2},
            },
            # the tuned flywheel cadence (howto/live.md): publishes land
            # mid-traffic, actor weight lag stays under the staleness threshold
            "learner": [
                "buffer.memmap=false",
                "buffer.size=512",
                "algo.learning_starts=8",
                "buffer.service.publish_every=2",
                "algo.replay_ratio=0.0625",
                "metric.telemetry.every=8",
                "checkpoint.every=64",
            ],
            "reload_poll_s": 0.1,
        }
        import yaml

        with open(spec_path, "w") as fh:
            yaml.safe_dump(spec, fh)

        start = time.perf_counter()
        rc = live_main([spec_path])
        wall = time.perf_counter() - start
        if rc != 0:
            raise RuntimeError(f"live_loop gang exited {rc}")

        serve_events = read_events(os.path.join(live_dir, "telemetry.jsonl"))
        summary = next(
            (e for e in reversed(serve_events) if e.get("event") == "summary"), {}
        )
        start_event = next((e for e in serve_events if e.get("event") == "start"), {})
        serve_summary = summary.get("serve") or {}
        weights = serve_summary.get("weights") or {}
        traj = serve_summary.get("trajectories") or {}

        learner_events = read_events(os.path.join(live_dir, "telemetry.learner.jsonl"))
        service = next(
            (
                e
                for e in reversed(learner_events)
                if e.get("event") == "service" and e.get("role") == "learner"
            ),
            {},
        )
        learner_dataflow = next(
            (
                (e.get("dataflow") or {})
                for e in reversed(learner_events)
                if e.get("event") == "window" and (e.get("dataflow") or {}).get("role") == "learner"
            ),
            {},
        )

        sessions_finished = int(serve_summary.get("sessions_finished") or 0)
        rows = int(traj.get("rows") or 0)
        gradient_steps = int(service.get("gradient_steps") or 0)
        fingerprint = start_event.get("fingerprint")
        conditions = {
            "servers": 1,
            "sessions": sessions,
            "session_rounds": session_rounds,
            "wave_pause_s": wave_pause_s,
            "max_session_steps": max_session_steps,
            "wall_seconds": round(wall, 3),
            "sessions_finished": sessions_finished,
            "reloads": int(weights.get("reloads") or 0),
            "weight_version": int(weights.get("version") or 0),
            "reload_failures": int(weights.get("failures") or 0),
            "trajectories": dict(traj),
            # the loop's dataflow view: what the learner saw of its actors
            "dataflow": {
                "rows": service.get("rows"),
                "rows_per_actor": service.get("rows_per_actor"),
                "queue_depth_mean": service.get("queue_depth"),
                "weight_lag": learner_dataflow.get("weight_lag"),
                "row_age": learner_dataflow.get("row_age"),
            },
            "latency_ms": serve_summary.get("latency_ms"),
            "fingerprint": fingerprint,
        }
        result = {
            "metric": "live_loop_sessions_per_sec",
            "value": round(sessions_finished / wall, 3) if wall > 0 else None,
            "unit": "sessions/sec (closed serve→learn→reload loop, paced waves)",
            "vs_baseline": None,  # first closed-loop tier — no reference number exists
            "conditions": conditions,
        }
        extras = [
            {
                "metric": "live_loop_ingest_rows_per_sec",
                "value": round(rows / wall, 2) if wall > 0 else None,
                "unit": "rows/sec (session trajectories into the experience plane)",
                "vs_baseline": None,
                "conditions": {
                    "rows": rows,
                    "trajectories_ingested": traj.get("ingested"),
                    "trajectories_dropped": traj.get("dropped"),
                    "fingerprint": fingerprint,
                },
            },
            {
                "metric": "live_loop_gradient_steps_per_sec",
                "value": round(gradient_steps / wall, 2) if wall > 0 else None,
                "unit": "gradient-steps/sec (co-located service learner)",
                "vs_baseline": None,
                "conditions": {
                    "gradient_steps": gradient_steps,
                    "weight_version": service.get("weight_version"),
                    "fingerprint": fingerprint,
                },
            },
            {
                # a count unit gates higher-is-better; fewer hot reloads for
                # the same traffic means the loop stopped closing
                "metric": "live_loop_reloads",
                "value": int(weights.get("reloads") or 0),
                "unit": "count (hot reloads applied mid-traffic)",
                "vs_baseline": None,
                "conditions": {
                    "weight_version": int(weights.get("version") or 0),
                    "reload_failures": int(weights.get("failures") or 0),
                    "fingerprint": fingerprint,
                },
            },
        ]
        result["extras"] = extras
        return result
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _bench_fleet_ingest(
    total_steps: int = 768, step_latency_ms: float = 20.0, num_envs: int = 4
) -> dict:
    """``fleet_ingest``: the experience data-plane A/B (sheeprl_tpu/data/service.py,
    howto/fleet.md). Three tiny sac_decoupled runs on the CPU mesh:

    - ``buffer.backend=local`` (single process, threaded trainer) — the learner
      gradient-steps/train-second reference;
    - ``buffer.backend=service`` with 1 actor process + 1 learner (2-process gang);
    - ``buffer.backend=service`` with 2 actor processes + 1 learner (3-process gang).

    The actors are PACED like real emulators (``env.wrapper.step_latency_ms``,
    default 20 ms/frame, so the pacing dominates per-iteration compute even on a
    noisy 1-core host): ingestion scaling then measures the DATA PLANE — can the
    KV ingest path and the learner's drain keep K paced actors at K×? — instead
    of CPU contention between co-scheduled actor processes on a small host.
    ``value`` is the 2-actor ingestion rate (rows/sec from the learner stream's
    summary — its step axis IS ingested rows); ``vs_baseline`` is the
    2-actor/1-actor scaling ratio (the acceptance bar is ≥ 1.5). Conditions carry
    per-config learner sps, ingest rows/sec and service queue depth, so the
    ``--against`` gate can hold all three."""
    import shutil

    from sheeprl_tpu.cli import run
    from sheeprl_tpu.obs.jsonl import read_events

    os.environ.pop("XLA_FLAGS", None)  # gang children must own their device set
    workdir = tempfile.mkdtemp(prefix="sheeprl-fleet-ingest-")
    base = [
        "exp=sac_decoupled",
        "env=dummy",
        "env.id=continuous_dummy",
        "env.sync_env=True",
        "env.capture_video=False",
        f"env.wrapper.step_latency_ms={step_latency_ms}",
        f"env.num_envs={num_envs}",
        "fabric.accelerator=cpu",
        "metric.log_level=0",
        "buffer.memmap=False",
        "buffer.size=4096",
        "buffer.checkpoint=False",
        "algo.learning_starts=32",
        "algo.run_test=False",
        "algo.mlp_keys.encoder=[state]",
        "algo.per_rank_batch_size=32",
        "algo.replay_ratio=0.25",
        f"algo.total_steps={total_steps}",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
        "metric.telemetry.enabled=true",
        "metric.telemetry.every=64",
    ]

    def summarize(stream_path: str) -> dict:
        events = read_events(stream_path)
        summary = next((e for e in reversed(events) if e.get("event") == "summary"), {})
        service = next((e for e in reversed(events) if e.get("event") == "service"), {})
        start = next((e for e in events if e.get("event") == "start"), {})
        train_seconds = float(summary.get("train_seconds") or 0.0)
        # the dataflow lineage block (weight lag, row age p50/p99, ingest
        # latency) from the learner's summary: conditions carry it so
        # --against can hold staleness, not just throughput
        dataflow = summary.get("dataflow") or None
        return {
            "ingest_rows_per_sec": summary.get("sps"),
            "gradient_steps": summary.get("train_units"),
            "learner_gsteps_per_train_sec": (
                round(summary.get("train_units", 0) / train_seconds, 3)
                if train_seconds > 0
                else None
            ),
            "queue_depth_mean": service.get("queue_depth_mean"),
            "queue_depth_max": service.get("queue_depth_max"),
            "rows_per_actor": service.get("rows_per_actor"),
            "dataflow": dataflow,
            "fingerprint": start.get("fingerprint"),
        }

    try:
        # local backend reference: the threaded decoupled learner's train rate
        local_dir = os.path.join(workdir, "local")
        run(
            base
            + [
                f"hydra.run.dir={local_dir}",
                f"metric.telemetry.jsonl_path={os.path.join(local_dir, 'telemetry.jsonl')}",
            ]
        )
        local = summarize(os.path.join(local_dir, "telemetry.jsonl"))

        configs = {}
        for actors in (1, 2):
            run_dir = os.path.join(workdir, f"actors{actors}")
            run(
                base
                + [
                    f"hydra.run.dir={run_dir}",
                    "buffer.backend=service",
                    f"buffer.service.actors={actors}",
                    # amortize the weight plane: publish every 4th round (the
                    # paced actors refresh at ~env cadence either way)
                    "buffer.service.publish_every=4",
                    f"resilience.distributed.gang.processes={actors + 1}",
                    "resilience.distributed.gang.grace=60",
                    "resilience.distributed.heartbeat.interval=0.5",
                    "resilience.distributed.heartbeat.timeout=30",
                ]
            )
            configs[actors] = summarize(os.path.join(run_dir, "telemetry.learner.jsonl"))

        rate_1 = float(configs[1]["ingest_rows_per_sec"] or 0.0)
        rate_2 = float(configs[2]["ingest_rows_per_sec"] or 0.0)
        scaling = round(rate_2 / rate_1, 3) if rate_1 > 0 else None
        conditions = {
            "total_steps": total_steps,
            "env_step_latency_ms": step_latency_ms,
            "num_envs_per_actor": num_envs,
            "cpu_count": os.cpu_count(),
            "local": {
                k: local[k]
                for k in ("ingest_rows_per_sec", "gradient_steps", "learner_gsteps_per_train_sec")
            },
            "actors_1": {k: v for k, v in configs[1].items() if k != "fingerprint"},
            "actors_2": {k: v for k, v in configs[2].items() if k != "fingerprint"},
            # the 2-actor config's dataflow summary, surfaced at the top level
            # so the staleness gate does not have to dig
            "dataflow": configs[2].get("dataflow"),
            "scaling_2_actors": scaling,
            # learner train rate vs the local backend (1.0 = no regression from
            # moving the buffer behind the service; on a 1-core host the 2-actor
            # figure additionally absorbs genuine core contention with the
            # co-scheduled actor processes — see cpu_count)
            "learner_vs_local": {
                str(actors): (
                    round(
                        configs[actors]["learner_gsteps_per_train_sec"]
                        / local["learner_gsteps_per_train_sec"],
                        3,
                    )
                    if configs[actors]["learner_gsteps_per_train_sec"]
                    and local["learner_gsteps_per_train_sec"]
                    else None
                )
                for actors in (1, 2)
            },
            "fingerprint": configs[2]["fingerprint"],
        }
        result = {
            "metric": "fleet_ingest_rows_per_sec",
            "value": round(rate_2, 2),
            "unit": "rows/sec (2-actor service ingestion, emulator-paced)",
            # scaling vs the 1-actor configuration — the >= 1.5x acceptance bar
            "vs_baseline": scaling,
            "conditions": conditions,
        }
        row_age = ((configs[2].get("dataflow") or {}).get("row_age") or {}).get("seconds") or {}
        if row_age.get("p99") is not None:
            # staleness gates independently: "seconds" units are lower-is-better
            # in bench-diff, so a fresher code version cannot regress row age
            # inside the throughput threshold unnoticed
            result["extras"] = [
                {
                    "metric": "fleet_ingest_row_age_p99_s",
                    "value": row_age["p99"],
                    "unit": "seconds (p99 sampled-row age, 2-actor service)",
                    "vs_baseline": None,
                    "conditions": {
                        "row_age": configs[2]["dataflow"].get("row_age"),
                        "weight_lag": configs[2]["dataflow"].get("weight_lag"),
                        "ingest_latency_ms": configs[2]["dataflow"].get("ingest_latency_ms"),
                        "fingerprint": configs[2]["fingerprint"],
                    },
                }
            ]
        return result
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _bench_dv3_mfu_flagship(size: str = "S") -> dict:
    """Standalone extra: flagship-size DV3 train-program MFU on the accelerator."""
    stats = _dv3_train_mfu(size=size)
    mfu, fps = stats.get("mfu"), stats.get("flops_per_sec")
    if mfu:
        value, unit = round(mfu, 4), "MFU (fraction of chip peak bf16)"
    elif fps:
        value, unit = round(fps / 1e12, 3), "TFLOP/s (no chip peak table entry)"
    else:  # backend without an XLA cost model: fall back to raw step latency
        value, unit = round(stats["step_seconds"], 4), "seconds/train-step (no XLA cost model)"
    return {
        "metric": f"dreamer_v3_{size}_train_mfu",
        "value": value,
        "unit": unit,
        "vs_baseline": None,  # the reference publishes no FLOPs-utilization numbers
        "conditions": stats,
    }


def _workload_fingerprint(algo: str) -> dict | None:
    """The run fingerprint (obs/fingerprint.py: git sha, config hash over the
    workload's benchmark exp, backend and device kind of this process) for
    workloads that do not produce a telemetry stream of their own (whole-run
    wall-clock + the standalone MFU extra) — steady-window workloads take the
    exact fingerprint from their run's telemetry start event instead."""
    exp = {
        "dreamer_v3_mfu": "dreamer_v3_benchmarks",
        "sac_steady": "sac_benchmarks",
    }.get(algo, f"{algo}_benchmarks")
    try:
        from sheeprl_tpu.config import compose
        from sheeprl_tpu.obs.fingerprint import run_fingerprint

        fp = run_fingerprint(compose([f"exp={exp}"]))
        device = _device()
        fp["backend"] = device["platform"]
        fp["device_kind"] = device["device_kind"]
        return fp
    except Exception:
        return None


def _bench(algo: str) -> dict:
    if algo == "dreamer_v3_mfu":
        result = _bench_dv3_mfu_flagship()
    elif algo == "dv3_2d_mesh":
        result = _bench_dv3_2d_mesh(os.environ.get("SHEEPRL_BENCH_DV3_2D_SIZE", "L"))
    elif algo == "ppo_anakin":
        result = _bench_ppo_anakin()
    elif algo == "sac_anakin":
        result = _bench_sac_anakin()
    elif algo == "sac_steady":
        result = _bench_sac_steady()
    elif algo == "serve_load":
        result = _bench_serve_load()
    elif algo == "fleet_ingest":
        result = _bench_fleet_ingest()
    elif algo == "live_loop":
        result = _bench_live_loop()
    elif algo.startswith("dreamer_v"):
        result = _bench_dreamer_steady(algo)
    else:
        result = _bench_wallclock(algo)
    # every workload names the device it ran on, records its peak memory so the
    # BENCH_*.json trajectory tracks memory alongside throughput (HBM peak on a
    # chip, RSS on CPU), and its fingerprint so BENCH_r*.json files are
    # self-describing for `sheeprl.py bench-diff` / `bench.py --against`
    conditions = result.setdefault("conditions", {})
    conditions["device"] = _device()
    conditions["peak_memory"] = _peak_memory()
    if not conditions.get("fingerprint"):
        conditions["fingerprint"] = _workload_fingerprint(algo)
    return result


def _bench_subprocess(algo: str, timeout: int = 1200) -> dict:
    """Each workload gets a fresh process: a cpu-pinned fabric (ppo benchmark
    conditions) locks jax_platforms for the whole process, which would silently
    demote a later accelerator workload — and a chip belongs to one process at
    a time, so this parent stays off JAX and the children take turns.

    A child past its budget is terminated (then killed): it must not keep
    holding the chip, or burning cores, under the workloads that follow. Output
    goes to temp FILES, not pipes, so a child can never block on a full pipe."""
    import subprocess

    with tempfile.TemporaryDirectory(prefix=f"bench-{algo}-") as tmp:
        out_path, err_path = os.path.join(tmp, "out"), os.path.join(tmp, "err")
        with open(out_path, "w") as out_f, open(err_path, "w") as err_f:
            child = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)],
                env={**os.environ, "BENCH_ALGO": algo},
                stdout=out_f,
                stderr=err_f,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        timed_out = False
        try:
            rc = child.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
            child.terminate()
            try:
                rc = child.wait(timeout=15)
            except subprocess.TimeoutExpired:
                child.kill()
                rc = child.wait()
        with open(out_path) as f:
            stdout = f.read()
        with open(err_path) as f:
            stderr = f.read()
    if timed_out:
        raise RuntimeError(
            f"bench {algo} timed out after {timeout}s (child terminated, exit {rc}): "
            f"{stdout[-500:]}\n{stderr[-1000:]}"
        )
    if rc != 0:
        raise RuntimeError(f"bench {algo} failed: {stdout[-2000:]}\n{stderr[-2000:]}")
    return json.loads(stdout.strip().splitlines()[-1])


def _parse_args(argv=None):
    import argparse

    parser = argparse.ArgumentParser(
        description="sheeprl-tpu benchmark harness; prints one JSON line per "
        "completed stage (a parser taking the LAST JSON line gets everything).",
    )
    parser.add_argument(
        "--against",
        default=None,
        metavar="BENCH_prev.json|dir",
        help="regression-gate this bench against a previous BENCH JSON (a dir "
        "picks its newest BENCH_*.json); attaches `regressions` to the final "
        "JSON line (sheeprl_tpu/obs/compare.py bench_diff)",
    )
    parser.add_argument(
        "--threshold",
        action="append",
        default=[],
        metavar="PCT|metric=PCT",
        help="relative regression threshold for --against (default 0.05); "
        "repeatable, metric=0.1 overrides one workload",
    )
    parser.add_argument(
        "--fail-on",
        choices=("regression",),
        default=None,
        help="with --against: exit non-zero when any workload regressed",
    )
    return parser.parse_args(argv)


def _gate_against(result: dict, args) -> int:
    """The bench regression gate (--against): diff this bench's result against a
    previous BENCH JSON, attach the verdicts, reprint the final line so the
    LAST JSON line carries them, and return the exit code under --fail-on.
    The human diff report goes to stderr — stdout stays JSON-lines only."""
    if not args.against:
        return 0
    try:
        from sheeprl_tpu.obs.compare import bench_diff, format_bench_diff, parse_threshold_args

        threshold, per_metric = parse_threshold_args(args.threshold)
        diff = bench_diff(args.against, result, threshold=threshold, per_metric=per_metric)
    except Exception as exc:  # an unreadable baseline must not lose the bench numbers
        result["bench_diff_error"] = repr(exc)[:300]
        print(json.dumps(result), flush=True)
        return 1 if args.fail_on == "regression" else 0
    result["regressions"] = [w for w in diff["workloads"] if w.get("status") == "regression"]
    result["bench_diff"] = {
        k: diff[k] for k in ("threshold", "improvements", "warnings", "missing_workloads")
    }
    print(format_bench_diff(diff), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 1 if (args.fail_on == "regression" and diff["regressions"]) else 0


# The default run: (workload, budget in seconds) after the PPO headline. A budget
# is a ceiling for a hung child, not a target; the first cold compile of a
# Dreamer program is inside it.
_EXTRAS = (
    # the Dreamer-V3 north star (the default prefetch on/off A/B doubles its
    # steady workload, so the budget covers two windows)
    ("dreamer_v3", 3000),
    ("sac_steady", 900),  # the same prefetch A/B on the cheap MLP program
    ("ppo_anakin", 900),  # on-device env plane + fused rollout/train
    ("sac_anakin", 900),  # device-resident off-policy topology + host control
    ("dv3_2d_mesh", 900),  # DV3-L parameter footprint on 8 virtual CPU devices
    ("serve_load", 900),  # the serving tier under synthetic open-loop load
    ("fleet_ingest", 900),  # the experience data-plane A/B (CPU-mesh gangs)
    ("live_loop", 900),  # the closed serve→experience→learn→reload flywheel
)
# Run only after dreamer_v3 reported an accelerator: the DV1/DV2 steady states
# and the flagship-size MFU, which on the CPU cost minutes of compile for a
# number with no chip peak to hold it against.
_ACCELERATOR_EXTRAS = (("dreamer_v1", 1500), ("dreamer_v2", 1500), ("dreamer_v3_mfu", 1800))


def main() -> int:
    args = _parse_args()
    algo = os.environ.get("BENCH_ALGO")
    if algo is not None:
        result = _bench(algo)
        print(json.dumps(result), flush=True)
        return _gate_against(result, args)
    # Default: PPO headline, flushed IMMEDIATELY, then the extras one child at a
    # time; each finished extra reprints the cumulative line, so a bench cut
    # short still reports what finished and the LAST line carries everything.
    result = _bench_subprocess("ppo", timeout=600)
    # code-health fingerprint: the static graftlint pass (findings/waived/rules,
    # howto/static_analysis.md) rides the combined JSON so BENCH_r*.json records
    # which rule catalog the measured code passed — cheap (no AOT sweep here)
    from sheeprl_tpu.analysis.engine import lint_summary, run_lint

    result.setdefault("conditions", {})["lint"] = lint_summary(run_lint())
    print(json.dumps(result), flush=True)

    extras: list = []
    failed: dict = {}

    def run_extra(extra_algo: str, budget: int) -> None:
        try:
            extras.append(_bench_subprocess(extra_algo, timeout=budget))
        except RuntimeError as exc:  # the child failed or outlived its budget
            failed[extra_algo] = str(exc)[-500:]
            print(f"bench: workload {extra_algo} FAILED: {exc}", file=sys.stderr, flush=True)
        else:
            print(json.dumps({**result, "extras": extras}), flush=True)

    for extra_algo, budget in _EXTRAS:
        run_extra(extra_algo, budget)
    dv3 = next((e for e in extras if e.get("metric", "").startswith("dreamer_v3_")), None)
    if dv3 is not None and dv3["conditions"]["device"]["platform"] != "cpu":
        for extra_algo, budget in _ACCELERATOR_EXTRAS:
            run_extra(extra_algo, budget)
    if extras:
        result["extras"] = extras
    if failed:
        result["failed_workloads"] = failed
    print(json.dumps(result), flush=True)
    rc = _gate_against(result, args)
    return 1 if failed else rc


if __name__ == "__main__":
    sys.exit(main())
