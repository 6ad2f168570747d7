"""CLI verbs: run / evaluation / registration (role of sheeprl/cli.py:23-449).

``run`` composes the config from dotted CLI overrides, applies resume-merge and config
policing, resolves the algorithm through the registry, instantiates the Fabric runtime
from config and launches the registered entrypoint — the same flow as the reference
(cli.py:357-365 → run_algorithm cli.py:59-198), minus process spawning: JAX SPMD runs
one controller process per host.
"""

from __future__ import annotations

import importlib
import os
import sys
import warnings
from datetime import datetime
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

from sheeprl_tpu.config import Composer, compose, deep_merge, dotdict, instantiate
from sheeprl_tpu.utils.registry import algorithm_registry, evaluation_registry
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import print_config

# config keys that must not be taken from the old config on resume (reference cli.py:23-56)
# `resilience` is runtime-operational state like `metric`: the saved config may
# carry a supervisor/fault setup that must not silently override this launch's.
# NOTE `hydra` stays RESUMABLE on purpose: the saved hydra.run.dir places a
# resumed run in the original run's tree as the next version_N — the
# continuation semantics the resume tests pin (a gang restart is unaffected:
# it pins root_dir/run_name per attempt, so the old and new dirs coincide).
_NON_RESUMABLE_KEYS = (
    "checkpoint",
    "exp_name",
    "run_name",
    "root_dir",
    "metric",
    "resilience",
)


def resume_from_checkpoint(cfg: dotdict, overrides: Optional[Sequence[str]] = None) -> dotdict:
    """Force-merge the checkpoint's config over the current one, keeping the
    non-resumable keys, and hard-validate env/algo identity (reference cli.py:23-56).
    ``checkpoint.resume_from=latest`` resolves to the newest valid checkpoint under
    this experiment's log tree first (shared with the supervisor's discovery).

    ``overrides`` is this launch's raw CLI override list: explicit dotted values
    the user typed (``buffer.size=N``) are re-applied AFTER the merge, so they
    beat the checkpoint's saved config — on the first attempt and on every
    supervisor retry (which funnels through this same merge)."""
    import yaml

    if str(cfg.checkpoint.resume_from).strip().lower() == "latest":
        from sheeprl_tpu.resilience.discovery import resolve_latest

        cfg.checkpoint.resume_from = resolve_latest(cfg)
    ckpt_path = Path(cfg.checkpoint.resume_from)
    old_cfg_path = ckpt_path.parent.parent / "config.yaml"
    if not old_cfg_path.is_file():
        old_cfg_path = ckpt_path.parent / "config.yaml"
    if not old_cfg_path.is_file():
        raise ValueError(
            f"cannot resume from {ckpt_path}: no config.yaml found next to the checkpoint"
        )
    with open(old_cfg_path) as f:
        old_cfg = yaml.safe_load(f)
    if old_cfg["env"]["id"] != cfg.env.id:
        raise ValueError(
            f"This experiment is run with a different environment from the one of the "
            f"experiment you want to restart: got {cfg.env.id}, expected {old_cfg['env']['id']}"
        )
    if old_cfg["algo"]["name"] != cfg.algo.name:
        raise ValueError(
            f"This experiment is run with a different algorithm from the one of the "
            f"experiment you want to restart: got {cfg.algo.name}, expected {old_cfg['algo']['name']}"
        )
    non_resumable = _NON_RESUMABLE_KEYS
    explicit: dict = {}
    if overrides:
        from sheeprl_tpu.config import explicit_overrides

        explicit = explicit_overrides(overrides)
    # `hydra` is resumable BY DEFAULT: the saved hydra.run.dir places a resumed
    # run in the original run's tree as the next version_N (the continuation
    # semantics the resume tests pin). But when THIS launch names its own run
    # identity on the command line, its hydra layout wins — resuming another
    # run's checkpoint under an explicit run_name must not hijack the old tree.
    if any(
        k in ("exp_name", "run_name", "root_dir") or k.startswith("hydra.")
        for k in explicit
    ):
        non_resumable = non_resumable + ("hydra",)
    preserved = {k: cfg[k] for k in non_resumable if k in cfg}
    merged = dict(old_cfg)
    deep_merge(merged, preserved)
    merged["checkpoint"]["resume_from"] = str(ckpt_path)
    result = dotdict(merged)
    if explicit:
        from sheeprl_tpu.config import set_by_path

        for key, value in explicit.items():
            # never clobber the resolved resume path (the argv value may be the
            # literal "latest", or a base checkpoint a retry has moved past);
            # the rest of `checkpoint` is already preserved from this launch
            if key == "checkpoint.resume_from":
                continue
            try:
                set_by_path(result, key, value, create=True)
            except (KeyError, TypeError):
                continue  # an override targeting a group the old config lacks
    return result


def check_configs(cfg: dotdict) -> None:
    """Config policing (role of reference cli.py:270-344): algorithm existence,
    decoupled × strategy × devices combinations, optional-dependency downgrades,
    and basic value sanity — each with an actionable message."""
    entry = algorithm_registry.get(cfg.algo.name)
    if entry is None:
        available = ", ".join(sorted(algorithm_registry.keys()))
        raise ValueError(f"algorithm {cfg.algo.name!r} is not registered; available: {available}")
    decoupled = entry[0]["decoupled"]
    if decoupled and int(os.environ.get("SHEEPRL_NUM_ACTORS", "1")) < 1:
        raise ValueError("decoupled algorithms need at least one actor process")

    strategy = str(cfg.fabric.strategy)
    if strategy not in ("auto", "dp", "single_device"):
        raise ValueError(
            f"unknown fabric.strategy {strategy!r}; available: auto, dp, single_device "
            "(the reference's DDP/SingleDevice strategies map onto the mesh `dp` and "
            "`single_device` strategies here)"
        )
    devices = int(cfg.fabric.devices)
    if strategy == "single_device" and devices > 1:
        raise ValueError(
            f"single_device strategy requires fabric.devices=1, got {devices}; "
            "launch with 'fabric.strategy=dp' (or 'auto') to use the whole mesh"
        )
    if decoupled and strategy == "single_device":
        # reference parity: decoupled algorithms refuse non-DDP strategies
        # (reference cli.py:290-307) — the player/trainer split needs the mesh
        raise ValueError(
            f"{cfg.algo.name} is decoupled and is not supported by the single_device "
            "strategy; launch with 'fabric.strategy=dp' or 'fabric.strategy=auto'"
        )
    if decoupled and devices < 1:
        raise ValueError(f"decoupled algorithms need fabric.devices >= 1, got {devices}")

    # named-mesh sanity: canonicalize mesh_shape/axis_names (raises on shape/name
    # mismatches, duplicate names, a missing "data" axis, multiple wildcards)
    # before the run launches, and police the strategy interaction
    from sheeprl_tpu.parallel.fabric import normalize_mesh_spec

    mesh_shape, _mesh_axes = normalize_mesh_spec(
        cfg.fabric.get("mesh_shape"), cfg.fabric.get("axis_names")
    )
    if strategy == "single_device" and len(mesh_shape) > 1:
        raise ValueError(
            f"single_device strategy cannot drive a multi-axis mesh "
            f"(fabric.mesh_shape={mesh_shape}); launch with 'fabric.strategy=dp' or 'auto'"
        )
    if decoupled and len(mesh_shape) > 1:
        raise ValueError(
            f"{cfg.algo.name} is decoupled: its player/learner slices run 1-D data "
            f"meshes (a multi-axis fabric.mesh_shape={mesh_shape} is only supported "
            "by the coupled topologies — see howto/model_parallel.md)"
        )
    if "model" in _mesh_axes and len(mesh_shape) > 1:
        module = entry[0]["module"]
        if not any(fam in module for fam in ("dreamer", "p2e")):
            # the mesh layer is generic but only the Dreamer family shards its
            # parameters over `model` (howto/model_parallel.md) — elsewhere the
            # model-axis devices would just repeat replicated work
            warnings.warn(
                f"fabric.mesh_shape={mesh_shape} carries a 'model' axis but "
                f"{cfg.algo.name} does not shard parameters over it; those devices "
                "will do replicated work. The Dreamer family is the wired-up "
                "consumer — see howto/model_parallel.md."
            )

    # experience-backend sanity (sheeprl_tpu/data/service.py, howto/fleet.md):
    # fail before launch on a config that cannot form a service plane
    backend = str(cfg.buffer.get("backend", "local") if cfg.get("buffer") else "local")
    if backend not in ("local", "service", "device"):
        raise ValueError(
            f"unknown buffer.backend {backend!r}; available: local (in-process replay, "
            "the default), service (standalone experience data plane for the "
            "decoupled topologies — see howto/fleet.md) and device (on-mesh replay "
            "ring for the fused off-policy topology — see howto/device_replay.md)"
        )
    if backend == "device" and cfg.algo.name != "sac_anakin":
        raise ValueError(
            f"buffer.backend=device is wired for the fused off-policy topology "
            f"(sac_anakin), not {cfg.algo.name!r} — host loops would round-trip the "
            "ring every step, losing exactly what it buys (howto/device_replay.md)"
        )
    if backend == "service":
        if cfg.algo.name not in ("sac_decoupled", "dreamer_v3_decoupled"):
            raise ValueError(
                f"buffer.backend=service is wired for the decoupled actor/learner "
                f"topologies (sac_decoupled, dreamer_v3_decoupled), not {cfg.algo.name!r}"
            )
        service_cfg = cfg.buffer.get("service") or {}
        actors = int(service_cfg.get("actors") or 1)
        if actors < 1:
            raise ValueError(f"buffer.service.actors must be >= 1, got {actors}")
        from sheeprl_tpu.resilience.distributed import gang_processes

        gang_size = gang_processes(cfg)
        if gang_size and actors >= gang_size:
            raise ValueError(
                f"buffer.service.actors={actors} leaves no learner rank in a "
                f"{gang_size}-process gang (need actors <= gang.processes - 1)"
            )

    # optional-dependency downgrade (reference cli.py:333-340)
    if not cfg.model_manager.get("disabled", True):
        from sheeprl_tpu.utils.imports import _IS_MLFLOW_AVAILABLE

        if not _IS_MLFLOW_AVAILABLE:
            warnings.warn(
                "MLflow is not installed: model registration is disabled for this run. "
                "Install it with 'pip install mlflow' to use the model manager.",
                UserWarning,
            )
            cfg.model_manager.disabled = True

    # observability config sanity: resolve (and thereby validate) the profiler
    # mode — an invalid metric.profiler.mode must fail before the run launches
    from sheeprl_tpu.obs import resolve_profiler_config

    resolve_profiler_config(cfg.metric)

    # resilience config sanity (same fail-before-launch policy)
    from sheeprl_tpu.resilience import normalize_fault_cfg

    rcfg = cfg.get("resilience") or {}
    fault = normalize_fault_cfg(rcfg)  # raises on an unknown fault kind
    if fault is not None and fault["at"] < 0:
        raise ValueError("resilience.fault.at_policy_step must be >= 0")
    if fault is not None and fault["rank"] is not None and fault["rank"] < 0:
        raise ValueError("resilience.fault.rank must be >= 0 (a process index)")
    supervisor_cfg = rcfg.get("supervisor") or {}
    if int(supervisor_cfg.get("max_restarts", 3) or 0) < 0:
        raise ValueError("resilience.supervisor.max_restarts must be >= 0")
    watchdog_cfg = rcfg.get("watchdog") or {}
    if bool(watchdog_cfg.get("enabled", False)) and float(watchdog_cfg.get("timeout") or 0) <= 0:
        raise ValueError("resilience.watchdog.timeout must be > 0 when the watchdog is enabled")
    dist_cfg = rcfg.get("distributed") or {}
    gang_n = int((dist_cfg.get("gang") or {}).get("processes") or 0)
    if gang_n == 1 or gang_n < 0:
        raise ValueError(
            "resilience.distributed.gang.processes must be 0 (off) or >= 2 "
            "(a 1-process run is what the in-process resilience.supervisor is for)"
        )
    if gang_n >= 2 and str(cfg.fabric.get("accelerator", "auto")).lower() != "cpu":
        # a chip belongs to one process: N children that each resolve
        # accelerator=auto/tpu would all claim it, and all but one would fail
        # or hang. Chip assignment per child does not exist yet.
        raise ValueError(
            f"resilience.distributed.gang.processes={gang_n} with fabric.accelerator="
            f"{cfg.fabric.get('accelerator', 'auto')!r}: gangs are CPU-mesh only today "
            "(every child would claim the same chip) — pass fabric.accelerator=cpu"
        )
    if gang_n >= 2 and fault is not None and fault["rank"] is not None and fault["rank"] >= gang_n:
        raise ValueError(
            f"resilience.fault.rank={fault['rank']} targets no process of a "
            f"{gang_n}-process gang — the fault would never fire"
        )
    hb_cfg = dist_cfg.get("heartbeat") or {}
    hb_interval = float(hb_cfg.get("interval") or 2.0)
    hb_timeout = float(hb_cfg.get("timeout") or 60.0)
    if bool(hb_cfg.get("enabled", True)) and hb_timeout <= hb_interval:
        raise ValueError(
            "resilience.distributed.heartbeat.timeout must exceed heartbeat.interval "
            f"(got timeout={hb_timeout}, interval={hb_interval})"
        )

    # value sanity (reference cli.py:341-344)
    learning_starts = cfg.algo.get("learning_starts")
    if learning_starts is not None and int(learning_starts) < 0:
        raise ValueError("The `algo.learning_starts` parameter must be greater or equal to zero.")
    if int(cfg.env.action_repeat) < 1:
        cfg.env.action_repeat = 1


def _apply_hydra_cfg(cfg: dotdict) -> None:
    """Honor the hydra config group's run-dir layout (reference
    sheeprl/configs/hydra/default.yaml: hydra.run.dir places the run directory)."""
    from sheeprl_tpu.utils.logger import set_run_dir

    hydra_cfg = cfg.get("hydra") or {}
    set_run_dir((hydra_cfg.get("run") or {}).get("dir"))


def _apply_distribution_cfg(cfg: dotdict) -> None:
    """Global distribution argument-validation switch (reference cli.py:71 sets the
    torch-distributions default from configs/distribution/default.yaml)."""
    from sheeprl_tpu.utils.distribution import set_validate_args

    dist_cfg = cfg.get("distribution") or {}
    set_validate_args(bool(dist_cfg.get("validate_args", False)))


def _setup_xla_env(cfg: dotdict) -> None:
    """Apply the XLA/runtime knobs (replacing torch/cuDNN knobs, reference cli.py:186-196)."""
    import jax

    from sheeprl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # torch set_float32_matmul_precision names map 1:1 onto JAX's tri-state
    # (high → bf16_3x passes, highest → f32, default → bf16 on the MXU)
    prec = str(cfg.get("float32_matmul_precision", "high"))
    try:
        jax.config.update("jax_default_matmul_precision", prec)
    except Exception:
        warnings.warn(f"could not set matmul precision {prec!r}")
    if cfg.get("xla_deterministic_ops", False):
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_gpu_deterministic_ops=true"


def run_algorithm(cfg: dotdict) -> None:
    """Registry lookup → module import → fabric instantiation → launch
    (reference cli.py:59-198)."""
    entry = algorithm_registry[cfg.algo.name][0]
    module = importlib.import_module(entry["module"])
    main = getattr(module, entry["entrypoint"])

    # metric key filtering: keep only the algo's whitelisted metrics (reference cli.py:150-164)
    utils_mod = None
    try:
        utils_mod = importlib.import_module(f"{entry['module'].rsplit('.', 1)[0]}.utils")
    except ImportError:
        pass
    if utils_mod is not None and hasattr(utils_mod, "AGGREGATOR_KEYS") and cfg.metric.log_level > 0:
        keys = set(utils_mod.AGGREGATOR_KEYS)
        metrics = cfg.metric.aggregator.metrics
        # prefix matches keep per-stream suffixed metrics (e.g. the p2e exploration
        # critics' Loss/value_loss_exploration_<critic>)
        cfg.metric.aggregator.metrics = dotdict(
            {
                k: v
                for k, v in metrics.items()
                if k in keys or any(k.startswith(p + "_") for p in keys)
            }
        )
    if cfg.metric.log_level == 0 or cfg.metric.disable_timer:
        # telemetry needs the Time/* spans for its train-seconds/MFU accounting
        # and is documented as independent of log_level, so an enabled telemetry
        # keeps the timers alive (two perf_counter calls per span — noise even
        # for bench runs, which enable telemetry with logging off)
        timer.disabled = not bool((cfg.metric.get("telemetry") or {}).get("enabled", False))
    from sheeprl_tpu.utils.metric import MetricAggregator

    MetricAggregator.disabled = cfg.metric.log_level == 0
    MetricAggregator.warn_device_values = cfg.metric.log_level >= 1

    kwargs: Dict[str, Any] = {}
    if "finetuning" in cfg.algo.name and "p2e" in entry["module"]:
        # inherit env/config identity from the exploration run (reference
        # cli.py:116-147)
        import yaml

        ckpt_path = Path(cfg.checkpoint.exploration_ckpt_path)
        expl_cfg_path = ckpt_path.parent.parent / "config.yaml"
        if not expl_cfg_path.is_file():
            expl_cfg_path = ckpt_path.parent / "config.yaml"
        if not expl_cfg_path.is_file():
            raise ValueError(
                f"cannot finetune from {ckpt_path}: no config.yaml found next to the "
                "exploration checkpoint"
            )
        with open(expl_cfg_path) as f:
            exploration_cfg = dotdict(yaml.safe_load(f))
        if exploration_cfg.env.id != cfg.env.id:
            raise ValueError(
                "This experiment is run with a different environment from the one of "
                f"the exploration you want to finetune. Got '{cfg.env.id}', but the "
                f"environment used during exploration was {exploration_cfg.env.id}."
            )
        for k in (
            "frame_stack",
            "screen_size",
            "action_repeat",
            "grayscale",
            "clip_rewards",
            "frame_stack_dilation",
            "max_episode_steps",
            "reward_as_observation",
        ):
            cfg.env[k] = exploration_cfg.env[k]
        if cfg.buffer.get("load_from_exploration", False):
            cfg.fabric.devices = exploration_cfg.fabric.devices
        kwargs["exploration_cfg"] = exploration_cfg

    fabric = instantiate(
        cfg.fabric,
        checkpoint_backend=str(cfg.checkpoint.get("backend", "pickle")),
        checkpoint_async=bool(cfg.checkpoint.get("async_save", False)),
    )

    # Optional XLA trace capture (SURVEY §5.1's TPU equivalent of the reference's
    # profiling story). metric.profiler.mode=run wraps the launched entrypoint in
    # a jax.profiler trace whose dump lands under the run's log tree, viewable in
    # TensorBoard's profile plugin / Perfetto — meant for short diagnostic runs
    # (a full-length training run produces a very large trace; use mode=window,
    # handled by the in-loop RunTelemetry, for a bounded steady-state capture).
    # The trace starts INSIDE the launch, after fabric._setup has pinned the
    # platform: jax.profiler.start_trace initializes the backend, and doing that
    # before the pin would touch the accelerator even for accelerator=cpu runs.
    from sheeprl_tpu.obs import resolve_profiler_config

    profiler_cfg = resolve_profiler_config(cfg.metric)
    if profiler_cfg["mode"] == "run":
        from sheeprl_tpu.utils.logger import run_base_dir

        profiler_dir = profiler_cfg["dir"] or str(
            run_base_dir(cfg.root_dir, cfg.run_name) / "profiler"
        )
        inner_main = main

        def main(fabric_, cfg_, **kw):  # noqa: F811 — deliberate profiled wrapper
            import jax

            os.makedirs(profiler_dir, exist_ok=True)
            jax.profiler.start_trace(profiler_dir)
            try:
                return inner_main(fabric_, cfg_, **kw)
            finally:
                jax.profiler.stop_trace()

    try:
        fabric.launch(main, cfg, **kwargs)
    finally:
        # an exception that unwound past the loop skipped its telemetry.close():
        # flush the summary (clean_exit=False) so crashed/preempted attempts
        # still leave end-of-attempt state in telemetry.jsonl — the loops close
        # their own instance on the normal path, making this a no-op there
        from sheeprl_tpu.obs.telemetry import close_all_live_telemetry

        close_all_live_telemetry(clean_exit=False)
        if fabric.checkpoint_async:
            from sheeprl_tpu.utils.checkpoint import wait_for_checkpoint

            wait_for_checkpoint()


def run(args: Optional[Sequence[str]] = None) -> None:
    """Entry point: ``python -m sheeprl_tpu exp=ppo env=gym ...``.

    Resilience wiring (sheeprl_tpu/resilience, howto/fault_tolerance.md): the
    cooperative SIGTERM/SIGINT preemption handler is installed around the launch
    (``resilience.handler``, default on) — the loops poll it at iteration
    boundaries and write an emergency checkpoint before exiting, and a preempted
    run exits with the distinct :data:`PREEMPTED_EXIT_CODE`. With
    ``resilience.supervisor.enabled`` the launch runs under the bounded-restart
    supervisor, auto-resuming from the newest valid checkpoint on crash or
    preemption."""
    import copy

    import sheeprl_tpu  # ensure registries are populated

    from sheeprl_tpu.resilience import (
        PREEMPTED_EXIT_CODE,
        RANK_FAILED_EXIT_CODE,
        install_preemption_handler,
        preemption_requested,
        supervisor_enabled,
        uninstall_preemption_handler,
    )
    from sheeprl_tpu.resilience.distributed import RankFailureError, gang_processes

    overrides = list(args if args is not None else sys.argv[1:])
    cfg = compose(overrides)

    # gang children (SHEEPRL_COORDINATOR set) had jax.distributed brought up by
    # __main__._gang_child_bringup BEFORE any sheeprl_tpu import — it cannot be
    # done here, the registry imports above already ran jax computations

    # the argv-merged cfg BEFORE any resume merge: supervisor retries rebuild
    # from it so this launch's explicit overrides survive every attempt
    argv_cfg = dotdict(copy.deepcopy(cfg.as_dict()))
    if cfg.checkpoint.resume_from:
        cfg = resume_from_checkpoint(cfg, overrides=overrides)
    check_configs(cfg)
    _setup_xla_env(cfg)
    _apply_distribution_cfg(cfg)
    _apply_hydra_cfg(cfg)
    if cfg.metric.log_level > 0:
        print_config(cfg)

    handler_installed = False
    if bool((cfg.get("resilience") or {}).get("handler", True)):
        handler_installed = install_preemption_handler()
    try:
        if gang_processes(cfg) >= 2 and not os.environ.get("SHEEPRL_GANG_RANK"):
            # gang mode: this process never trains — it spawns and supervises
            # the N-rank gang (resilience/distributed.py), forwarding its own
            # SIGTERM to the children and restarting the whole gang on failure
            from sheeprl_tpu.resilience.distributed import supervise_gang

            outcome = supervise_gang(cfg, overrides)
        elif supervisor_enabled(cfg):
            from sheeprl_tpu.resilience.supervisor import supervise

            outcome = supervise(
                cfg,
                run_algorithm,
                lambda c: resume_from_checkpoint(c, overrides=overrides),
                argv_cfg=argv_cfg,
            )
        else:
            run_algorithm(cfg)
            outcome = "preempted" if preemption_requested() else "completed"
    except RankFailureError as err:
        # a PEER died and this rank tore itself down (directly, or escaping the
        # in-process supervisor's multi-process step-aside path): exit with the
        # distinct code so whatever supervises the gang never blames this rank
        print(f"[sheeprl-resilience] {err}", file=sys.stderr)
        raise SystemExit(RANK_FAILED_EXIT_CODE) from err
    finally:
        # a crash that unwound past the loop's finalize() leaves its watchdog
        # running (an abort-mode one would os._exit a later in-process run)
        from sheeprl_tpu.resilience.watchdog import stop_all_watchdogs

        stop_all_watchdogs()
        if handler_installed:
            uninstall_preemption_handler()
    if outcome == "preempted":
        raise SystemExit(PREEMPTED_EXIT_CODE)


def diagnose(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py diagnose <run_dir>`` — merge the run's telemetry
    stream(s) (per-process files of decoupled topologies, supervisor attempts)
    and print a rule-based bottleneck report, writing machine-readable
    ``diagnosis.json`` next to the streams. See ``howto/observability.md``
    ("Diagnosing a run") for the detector catalog."""
    from sheeprl_tpu.obs.diagnose import main as diagnose_main

    return diagnose_main(list(args if args is not None else sys.argv[1:]))


def slo(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py slo <run_dir|fleet_dir|live_dir>`` — replay the
    run's telemetry windows through its declared SLOs (``metric.telemetry.slo``
    + per-run ``slo.yaml``): per-objective burn rates, error budget remaining,
    and the alert lifecycle recomputed offline and cross-checked against the
    in-loop ``alert`` events; writes machine-readable ``slo.json`` next to the
    streams. ``--fail-on warning|critical`` gates on FIRING alerts. See
    ``howto/observability.md`` ("SLOs, error budgets, and alerts")."""
    from sheeprl_tpu.obs.slo import main as slo_main

    return slo_main(list(args if args is not None else sys.argv[1:]))


def profile(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py profile <run_dir>`` — parse the run's
    ``jax.profiler`` window capture(s) (``metric.profiler.mode=window``) into
    op-category attribution (comm/mxu/elementwise/copy/loop/host/idle shares of
    device time), achieved FLOP/s + roofline position per registered fused
    program, writing machine-readable ``profile.json`` next to the streams.
    ``--fail-on warning|critical`` gates on the comm_bound/copy_bound/host_gap
    detectors. See ``howto/observability.md`` ("Profiling a fused program")."""
    from sheeprl_tpu.obs.xprof import main as profile_main

    return profile_main(list(args if args is not None else sys.argv[1:]))


def fault_matrix(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py fault-matrix`` — run the resilience fault matrix on
    the CPU mesh: every ``resilience``-marked smoke (single-process preempt /
    crash / ckpt_kill / env_step recovery AND the rank-targeted distributed
    smokes — kill_rank, stale_heartbeat, sigterm-to-one-rank under the gang
    supervisor, which gate on ``diagnose --fail-on critical`` internally).
    Extra arguments pass through to pytest (e.g. ``-k gang`` to scope, ``-q``).
    Exit code is pytest's — non-zero means a recovery path regressed."""
    import subprocess

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [
        sys.executable,
        "-m",
        "pytest",
        os.path.join(repo_root, "tests", "test_resilience"),
        "-m",
        "resilience",
        "-q",
        "-p",
        "no:cacheprovider",
    ] + list(args if args is not None else sys.argv[1:])
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.call(cmd, env=env, cwd=repo_root)


def lint(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py lint [--aot] [--json] [--fail-on warning|critical]``
    — the JAX-aware static-analysis gate (howto/static_analysis.md): 7 AST
    rules codifying the repo's known JAX/TPU hazard classes (global
    ``jax.devices()`` views, unpinned Pallas dot precisions, host views feeding donated programs,
    host syncs inside jitted programs, unregistered telemetry events,
    training-loop hook completeness, config/code key drift), plus — with
    ``--aot`` — the fused-program contract sweep: every registered donated
    program is lowered for cpu+tpu off-chip and its donation/no-host-callback/
    collective contract asserted. Exceptions live in ``analysis/waivers.toml``,
    each with a reason; the gate holds at zero unwaived findings."""
    from sheeprl_tpu.analysis.engine import lint_main

    return lint_main(list(args if args is not None else sys.argv[1:]))


def fleet(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py fleet <spec.yaml>`` — schedule N member runs (seed/env
    sweeps) as one fleet: per-member bounded-restart supervision (resume strictly
    inside the member's dir), a SHARED persistent XLA compile cache (the first
    member compiles, the rest cold-start as cache hits), and fleet-level rollups
    — ``leaderboard.json`` ranked from the members' telemetry fingerprints +
    summaries, ``obs/compare`` findings across the sweep, ``--fail-on`` CI gate.
    See ``howto/fleet.md`` for the spec format and the leaderboard schema."""
    from sheeprl_tpu.fleet.runner import main as fleet_main

    return fleet_main(list(args if args is not None else sys.argv[1:]))


def trace(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py trace <run_dir|fleet_dir>`` — convert the run's
    merged telemetry stream(s) into a Perfetto/Chrome-trace JSON: one track per
    member/rank/role, phase spans per window, flow events linking the
    experience plane's ingest→sample and publish→refresh across process
    tracks. See ``howto/observability.md`` ("Tracing the dataflow")."""
    from sheeprl_tpu.obs.trace import main as trace_main

    return trace_main(list(args if args is not None else sys.argv[1:]))


def watch(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py watch <run_dir>`` — live terminal monitor over the
    run's telemetry stream(s) (follow mode: torn lines retried, late per-role
    streams and supervisor attempts picked up); exits with the run's status
    when its summary event lands. See ``howto/observability.md``
    ("Watching a live run")."""
    from sheeprl_tpu.obs.watch import main as watch_main

    return watch_main(list(args if args is not None else sys.argv[1:]))


def compare(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py compare <run_a> <run_b>`` — fingerprint-aware diff of
    two run dirs: per-window distributions (median/p90) of throughput, MFU and
    phases, compile/memory/restart totals, deltas flagged beyond the runs' own
    window spread, written to ``comparison.json``. See
    ``howto/observability.md`` ("Comparing runs / gating benchmarks")."""
    from sheeprl_tpu.obs.compare import main as compare_main

    return compare_main(list(args if args is not None else sys.argv[1:]))


def bench_diff(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py bench-diff <old.json> <new.json>`` — the BENCH_*.json
    regression gate (also available as ``bench.py --against``): workloads
    matched by metric name + fingerprint-compatible conditions, per-metric
    relative thresholds, ``--fail-on regression`` for CI."""
    from sheeprl_tpu.obs.compare import bench_diff_main

    return bench_diff_main(list(args if args is not None else sys.argv[1:]))


def serve(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py serve checkpoint_path=<ckpt> [serve.* overrides]`` —
    the policy serving tier (howto/serving.md): load any registered agent
    checkpoint (``checkpoint_path`` may be a file, a run dir, or a multi-rank
    checkpoint set — resolved through the supervisor's manifest-validated
    discovery), compile ONE donated fixed-shape step program, and serve
    concurrent sessions via continuous batching over a device-resident slot
    table. The robustness plane (howto/serving.md "Operating a server"): hot
    weight reload (``serve.reload.enabled``, zero recompiles), overload
    shedding (``serve.max_queue``) + per-request deadlines
    (``serve.deadline_ms``), SIGTERM → graceful drain (exit 75), ``/healthz``
    readiness on the metrics port, and ``serve.supervisor.*`` bounded-restart
    supervision. ``serve.prime=true`` compiles the serving programs into the
    persistent XLA cache and exits (cold-start priming, the ``sheeprl-compile``
    story for serving)."""
    from sheeprl_tpu.serve.main import serve_main

    return serve_main(list(args if args is not None else sys.argv[1:]))


def live(args: Optional[Sequence[str]] = None) -> int:
    """``python sheeprl.py live <spec.yaml> [key=value ...]`` — the closed-loop
    flywheel (howto/live.md): serving slots double as actors. One supervised
    in-process gang runs N :class:`PolicyServer` roles (booted from the spec's
    ``checkpoint_path``, explore slots injecting session-seeded noise), every
    finished session's trajectory rides the experience service into ONE
    ``buffer.backend=service`` learner, and each published weight version
    hot-reloads into every server between ticks — zero recompiles. SIGTERM
    drains the whole gang (exit 75); ``watch``/``diagnose``/``trace`` stitch
    the session→ingest→train→publish→reload flow across the live dir's
    per-role telemetry streams."""
    from sheeprl_tpu.live.runner import live_main

    return live_main(list(args if args is not None else sys.argv[1:]))


def check_configs_evaluation(cfg: dotdict) -> None:
    if cfg.float32_matmul_precision not in ("default", "high", "highest"):
        raise ValueError(
            f"float32_matmul_precision must be one of default/high/highest, got {cfg.float32_matmul_precision}"
        )
    if cfg.checkpoint_path is None:
        raise ValueError("checkpoint_path must be specified")


def eval_algorithm(cfg: dotdict) -> None:
    """Single-device evaluation dispatch (reference cli.py:201-267)."""
    from sheeprl_tpu.parallel.fabric import Fabric

    entry = evaluation_registry.get(cfg.algo.name)
    if entry is None:
        available = ", ".join(sorted(evaluation_registry.keys()))
        raise ValueError(
            f"no evaluation registered for algorithm {cfg.algo.name!r}; available: {available}"
        )
    entry = entry[0]
    module = importlib.import_module(entry["module"])
    evaluate_fn = getattr(module, entry["entrypoint"])
    fabric = Fabric(
        devices=1,
        accelerator=cfg.fabric.get("accelerator", "auto"),
        precision=cfg.fabric.get("precision", "32-true"),
        checkpoint_backend=str((cfg.get("checkpoint") or {}).get("backend", "pickle")),
    )
    # pin the platform BEFORE loading: the sharded (orbax) checkpoint reader touches
    # jax, and backend discovery must respect fabric.accelerator=cpu (otherwise a
    # cpu-pinned eval would still initialize — and possibly block on — the TPU)
    fabric._setup()
    state = None
    if cfg.checkpoint_path:
        from sheeprl_tpu.utils.checkpoint import load_checkpoint

        state = load_checkpoint(cfg.checkpoint_path)
    fabric.launch(evaluate_fn, cfg, state)


def evaluation(args: Optional[Sequence[str]] = None) -> None:
    """``sheeprl-eval checkpoint_path=... [overrides]`` (reference cli.py:368-404)."""
    import yaml

    import sheeprl_tpu  # noqa: F401 - populate registries

    overrides = list(args if args is not None else sys.argv[1:])
    kv = dict(o.split("=", 1) for o in overrides if "=" in o)
    ckpt_path = kv.get("checkpoint_path")
    if ckpt_path is None:
        raise ValueError("you must specify checkpoint_path=...")
    # a run dir / experiment tree / multi-rank checkpoint set resolves to its
    # newest manifest-valid checkpoint — the same discovery rules the crash
    # supervisor and the serving tier use (resilience/discovery.py)
    from sheeprl_tpu.resilience.discovery import resolve_checkpoint_path

    ckpt_path = Path(resolve_checkpoint_path(ckpt_path))
    cfg_path = ckpt_path.parent.parent / "config.yaml"
    if not cfg_path.is_file():
        cfg_path = ckpt_path.parent / "config.yaml"
    with open(cfg_path) as f:
        base = yaml.safe_load(f)
    base["env"]["num_envs"] = 1
    base["env"]["capture_video"] = yaml.safe_load(kv.get("env.capture_video", "true"))
    base.setdefault("fabric", {})
    base["fabric"]["devices"] = 1
    base["checkpoint_path"] = str(ckpt_path)
    base["seed"] = int(kv.get("seed", base.get("seed", 42)))
    if "fabric.accelerator" in kv:
        base["fabric"]["accelerator"] = kv["fabric.accelerator"]
    cfg = dotdict(base)
    check_configs_evaluation(cfg)
    _apply_distribution_cfg(cfg)
    eval_algorithm(cfg)


def one_train_phase_steps(cfg: dotdict) -> int:
    """Smallest ``total_steps`` that carries a run through its FIRST gradient
    phase (compiling every act + train program the full run would compile):
    one rollout for on-policy algorithms; learning_starts plus enough steps for
    the replay-ratio governor to grant a gradient step for off-policy ones.

    Step accounting is GLOBAL (``policy_steps_per_iter = num_envs * world_size``
    in every training loop), so the budget scales with ``fabric.devices`` — a
    priming run at devices=4 must still reach its first train phase."""
    algo = cfg.algo
    devices = cfg.fabric.get("devices", 1)
    try:
        world_size = int(devices)
    except (TypeError, ValueError):
        # "auto" (and any other non-integer spelling) means "all local devices"
        # exactly like -1 — resolving it to 1 would under-budget a multi-device
        # priming run, which then never reaches its first train phase
        world_size = 0
    if world_size <= 0:  # -1 = "all local devices" (dp-cpu/dp-tpu fabric configs)
        import jax

        # resolve the count the way the Fabric will: pin the platform FIRST for
        # cpu fabrics, so counting devices can never initialize (and on a TPU
        # box, claim) the accelerator backend for a run that won't use it
        if str(cfg.fabric.get("accelerator", "auto")) == "cpu":
            jax.config.update("jax_platforms", "cpu")
        world_size = jax.local_device_count()
    steps_per_iter = int(cfg.env.num_envs) * max(world_size, 1)
    if "learning_starts" in algo:
        ratio = float(algo.get("replay_ratio", 1.0) or 1.0)
        return int(algo.learning_starts) + (int(1.0 / ratio) + 2) * steps_per_iter
    if "rollout_steps" in algo:
        return int(algo.rollout_steps) * steps_per_iter
    raise ValueError(
        f"cannot derive a one-train-phase step budget for {algo.name!r} "
        "(no rollout_steps or learning_starts); pass algo.total_steps yourself and use `sheeprl`"
    )


def compile_warm(args: Optional[Sequence[str]] = None) -> None:
    """``sheeprl-compile exp=... [overrides]`` — prime the persistent XLA compile
    cache for an experiment WITHOUT doing a real training run.

    TPU-first rationale: a cold compile of the fused train programs is the
    largest start-up cost of a run (CHANGES.md, PR 21, has the Dreamer-V3 S
    figure measured on a v5e). Because compiled
    executables are keyed by (program, shapes) and every shape in a run is
    config-derived, running the exp for just long enough to reach its first
    train phase compiles the exact act + train programs the real run will use
    and lands them in the persistent cache (``sheeprl_tpu/utils/compile_cache.py``)
    — so the real job, a pod launch, or a benchmark run starts hot. No analogue
    exists in the reference (torch is eager); this is XLA-specific operational
    surface.

    The priming run disables logging/checkpointing/video/final-test and shrinks
    ``total_steps`` to one train phase:

    - on-policy (``algo.rollout_steps``): one rollout → one update,
    - off-policy / world-model (``algo.learning_starts`` + ``algo.replay_ratio``):
      learning_starts, then enough env steps for the replay-ratio governor to
      grant the first gradient step.

    Model/batch/sequence config is untouched — shapes must match the real run.
    Finetuning/offline entrypoints that need a checkpoint or dataset are not
    supported (prime their base exp instead).

    Serving: ``sheeprl-compile checkpoint_path=<ckpt> [serve.* overrides]``
    primes the SERVING tier instead — it AOT-compiles the batched slot-table
    step/attach programs for that checkpoint (exact slot count and obs shapes)
    into the same persistent cache, so ``sheeprl.py serve`` cold-starts as a
    cache hit. Equivalent to ``sheeprl.py serve ... serve.prime=true``."""
    import time

    import sheeprl_tpu  # noqa: F401 - populate registries

    overrides = list(args if args is not None else sys.argv[1:])
    if any(o.startswith("checkpoint_path=") for o in overrides):
        # serving-tier priming: the step program's shapes come from the
        # checkpoint + serve.* knobs, not from an exp config
        raise SystemExit(serve(overrides + ["serve.prime=true"]))
    cfg = compose(overrides)
    total = one_train_phase_steps(cfg)
    import tempfile

    scratch = tempfile.mkdtemp(prefix="sheeprl-compile-")
    prime_overrides = [
        f"algo.total_steps={total}",
        "algo.run_test=False",
        "metric.log_level=0",
        "metric.disable_timer=True",
        "checkpoint.save_last=False",
        f"checkpoint.every={max(total * 2, 1_000_000)}",
        # buffer capacity does not affect compiled program shapes, so the priming
        # buffer only needs to hold the priming steps — at real exp sizes (DV2:
        # 5M transitions) a memmap=False preallocation would OOM the host
        "buffer.memmap=False",
        f"buffer.size={max(total, 1)}",
        "env.capture_video=False",
        # artifacts (run dir, stray checkpoints) go to a throwaway dir — priming
        # must leave the user's logs/ tree untouched
        f"hydra.run.dir={scratch}",
    ]
    print(f"[sheeprl-compile] priming {cfg.algo.name} for {total} env steps: one full train phase")
    start = time.perf_counter()
    try:
        run(overrides + prime_overrides)
    finally:
        import shutil

        shutil.rmtree(scratch, ignore_errors=True)
    elapsed = time.perf_counter() - start
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    n_entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(
        f"[sheeprl-compile] done in {elapsed:.1f}s — persistent cache at "
        f"{cache_dir} now holds {n_entries} entries; the real run starts hot"
    )


def registration(args: Optional[Sequence[str]] = None) -> None:
    """Model-registry publication from a checkpoint (reference cli.py:407-449).
    Requires mlflow, which is optional."""
    from sheeprl_tpu.utils.imports import _IS_MLFLOW_AVAILABLE

    if not _IS_MLFLOW_AVAILABLE:
        raise ModuleNotFoundError(
            "mlflow is not installed; the model-manager CLI requires it. "
            "Install mlflow to register models."
        )
    from sheeprl_tpu.utils.mlflow import register_model_from_checkpoint

    overrides = list(args if args is not None else sys.argv[1:])
    kv = dict(o.split("=", 1) for o in overrides if "=" in o)
    register_model_from_checkpoint(kv)
