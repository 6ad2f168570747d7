"""``python sheeprl.py serve checkpoint_path=<ckpt> [serve.* overrides]``.

Composition mirrors ``sheeprl-eval`` (cli.evaluation): the config is read from
the checkpoint's own ``config.yaml``, a ``serve`` block of serving knobs is
merged over it (defaults below, then dotted ``serve.*`` CLI overrides), the
checkpoint is resolved through the crash supervisor's discovery rules
(``resolve_checkpoint_path`` — a run DIR or multi-rank set resolves to its
newest manifest-valid checkpoint), and the registered family extractor builds
the :class:`~sheeprl_tpu.serve.policy.ServePolicy` the server batches.

Serving knobs (``serve.*``):

- ``slots`` — concurrent device-resident sessions (the batch dimension of the
  ONE compiled step program);
- ``max_batch_wait_ms`` — continuous-batching coalescing window;
- ``greedy`` — deterministic (mode) actions vs sampled ones;
- ``sessions`` / ``max_session_steps`` — the built-in env-session driver: N
  concurrent client threads each play a real env episode with served actions
  (the in-process session API is the transport surface; this driver is its
  operational smoke);
- ``max_queue`` — bounded admission queue: sessions arriving past it are shed
  with ``ServerOverloaded`` (+ retry-after hint) instead of queueing forever
  (null = unbounded, the pre-robustness behavior);
- ``deadline_ms`` — per-request deadline: a pending observation older than
  this is dropped BEFORE the tick and the client gets ``DeadlineExceeded``;
- ``degraded_wait_factor`` — how much the coalescing window widens under
  sustained saturation (degraded mode);
- ``drain_grace_s`` — SIGTERM drain: stop admissions, let in-flight sessions
  finish for this long, then close with a clean summary and exit 75;
- ``reload.{enabled,poll_s,watch_dir}`` — hot weight reload: follow the
  watched directory's newest valid checkpoint (``serve/reload.py``) and swap
  params in atomically between ticks, zero recompiles;
- ``supervisor.{enabled,max_restarts,backoff,...}`` — bounded-restart
  supervision of the serve loop itself (the training supervisor's
  ``run_restart_policy``), with session-loss accounting per restart;
- ``telemetry.enabled`` / ``telemetry.every`` — the serving telemetry stream
  (``watch``/``diagnose`` compatible, see howto/serving.md); with
  ``metric.telemetry.http_port`` set, ``/metrics`` (Prometheus) and
  ``/healthz`` (readiness: 200 serving / 503 draining-or-loading) ride it;
- ``prime=true`` — compile the step/attach programs into the persistent XLA
  compile cache and exit WITHOUT serving: the ``sheeprl-compile`` story for the
  serving tier (cold-start becomes a cache hit).

Exit codes: ``0`` every session completed, ``1`` a session failed or the
server crashed (restart budget exhausted when supervised), ``2`` nothing to
drive, ``75`` (EX_TEMPFAIL, the resilience plane's preempted code) SIGTERM →
drained cleanly — external supervisors reschedule, exactly as for training.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, Optional, Sequence

__all__ = ["SERVE_DEFAULTS", "build_serve_cfg", "serve_main"]

SERVE_DEFAULTS: Dict[str, Any] = {
    "slots": 4,
    "max_batch_wait_ms": 2.0,
    "greedy": True,
    "sessions": 2,
    "max_session_steps": 1000,
    "request_timeout": 120.0,
    "log_dir": None,  # default: logs/serve/<algo>_<timestamp>
    "prime": False,
    # robustness plane (howto/serving.md, "Operating a server")
    "max_queue": None,  # null = unbounded admission (no shedding)
    "deadline_ms": None,  # null = no per-request deadline
    # per-slot exploration split (the live flywheel, howto/live.md): the lowest
    # round(fraction*slots) slot indices get session-seeded Gaussian action
    # noise; all other slots serve greedy, byte-identical actions
    "explore": {"fraction": 0.0, "noise": 0.3},
    "degraded_wait_factor": 4.0,
    "drain_grace_s": 10.0,
    "reload": {"enabled": False, "poll_s": 2.0, "watch_dir": None},
    "supervisor": {
        "enabled": False,
        "max_restarts": 3,
        "backoff": 1.0,
        "backoff_cap": 60.0,
    },
    "telemetry": {"enabled": True, "every": 256},
}


def build_serve_cfg(overrides: Sequence[str]):
    """Compose the serving config: checkpoint's config.yaml + serve defaults +
    dotted CLI overrides. Returns the dotdict cfg (with ``checkpoint_path``
    resolved and ``serve`` populated)."""
    import copy

    import yaml

    from sheeprl_tpu.config import dotdict, set_by_path
    from sheeprl_tpu.resilience.discovery import resolve_checkpoint_path

    kv = dict(o.split("=", 1) for o in overrides if "=" in o)
    ckpt_arg = kv.get("checkpoint_path")
    if ckpt_arg is None:
        raise ValueError(
            "you must specify checkpoint_path=... (a checkpoint file, a run dir, "
            "or a multi-rank checkpoint dir — discovery resolves the newest valid set)"
        )
    from pathlib import Path

    ckpt_path = Path(resolve_checkpoint_path(ckpt_arg))
    cfg_path = ckpt_path.parent.parent / "config.yaml"
    if not cfg_path.is_file():
        cfg_path = ckpt_path.parent / "config.yaml"
    if not cfg_path.is_file():
        raise ValueError(
            f"cannot serve {ckpt_path}: no config.yaml found next to the checkpoint"
        )
    with open(cfg_path) as f:
        base = yaml.safe_load(f)
    # serving is single-controller, one env worth of obs per session
    base["env"]["num_envs"] = 1
    base["env"]["capture_video"] = False
    base.setdefault("fabric", {})
    base["fabric"]["devices"] = 1
    base["checkpoint_path"] = str(ckpt_path)
    base["serve"] = copy.deepcopy(SERVE_DEFAULTS)
    cfg = dotdict(base)
    for key, raw in kv.items():
        if key == "checkpoint_path":
            continue
        try:
            value = yaml.safe_load(raw)
        except yaml.YAMLError:
            value = raw
        try:
            set_by_path(cfg, key, value, create=True)
        except (KeyError, TypeError):
            continue
    cfg.seed = int(kv.get("seed", base.get("seed", 42)))
    # hot reload follows the checkpoint SOURCE the operator pointed at: a run
    # dir keeps producing newer checkpoints under it, an exact file's parent
    # is the closest thing to one
    if cfg.serve.reload.get("watch_dir") is None:
        cfg.serve.reload.watch_dir = (
            str(ckpt_arg) if os.path.isdir(str(ckpt_arg)) else str(ckpt_path.parent)
        )
    return cfg


def _default_log_dir(cfg) -> str:
    stamp = time.strftime("%Y-%m-%d_%H-%M-%S")
    return os.path.join("logs", "serve", f"{cfg.algo.name}_{stamp}")


def _prime(server, policy) -> Dict[str, int]:
    """AOT-compile the serving step/attach programs (landing them in the
    persistent XLA compile cache) without serving a single request."""
    import numpy as np

    from sheeprl_tpu.utils.mfu import abstractify

    table = server.table
    step, attach = table.aot_programs()
    obs = {k: spec.zeros(table.num_slots) for k, spec in policy.obs_spec.items()}
    mask = np.zeros((table.num_slots,), np.bool_)
    keys = table._slot_keys([0] * table.num_slots)
    compiled = 0
    for fn, args in (
        (step, (policy.params, table.states, obs, mask)),
        (attach, (policy.params, table.states, keys, mask)),
    ):
        fn.lower(*abstractify(args)).compile()
        compiled += 1
    return {"programs": compiled, "slots": table.num_slots}


class _ServeAttempt:
    """One serving attempt: server + telemetry + reloader + the drain watcher.
    The supervisor path runs several of these against one telemetry stream
    (per-attempt identity), the plain path exactly one."""

    def __init__(self, cfg: Any, fabric: Any, log_dir: str, attempt: int = 0) -> None:
        from sheeprl_tpu.resilience.faults import build_fault_plan
        from sheeprl_tpu.serve.policy import resolve_serve_policy
        from sheeprl_tpu.serve.server import PolicyServer
        from sheeprl_tpu.serve.telemetry import ServingTelemetry
        from sheeprl_tpu.utils.checkpoint import load_checkpoint

        self.cfg = cfg
        self.fabric = fabric
        self.log_dir = log_dir
        serve_cfg = cfg.serve

        state = load_checkpoint(cfg.checkpoint_path)
        self.policy = resolve_serve_policy(fabric, cfg, state)

        tcfg = serve_cfg.get("telemetry") or {}
        metric_tcfg = ((cfg.get("metric") or {}).get("telemetry")) or {}
        self.telemetry = ServingTelemetry(
            fabric,
            cfg,
            log_dir,
            enabled=bool(tcfg.get("enabled", True)),
            every=int(tcfg.get("every", 256)),
            http_port=metric_tcfg.get("http_port"),
            http_host=str(metric_tcfg.get("http_host") or "127.0.0.1"),
            attempt=attempt,
            serve_info={
                "slots": int(serve_cfg.slots),
                "max_batch_wait_ms": float(serve_cfg.max_batch_wait_ms),
                "greedy": bool(serve_cfg.greedy),
                "checkpoint_path": str(cfg.checkpoint_path),
                **self.policy.meta,
            },
        )
        self.server = PolicyServer(
            self.policy,
            slots=int(serve_cfg.slots),
            max_batch_wait_ms=float(serve_cfg.max_batch_wait_ms),
            base_seed=int(cfg.seed),
            telemetry=self.telemetry,
            request_timeout=float(serve_cfg.request_timeout),
            max_queue=serve_cfg.get("max_queue"),
            deadline_ms=serve_cfg.get("deadline_ms"),
            degraded_wait_factor=float(serve_cfg.get("degraded_wait_factor") or 4.0),
            fault_plan=build_fault_plan(cfg.get("resilience")),
            explore_fraction=float((serve_cfg.get("explore") or {}).get("fraction") or 0.0),
            explore_noise=float((serve_cfg.get("explore") or {}).get("noise") or 0.3),
        )
        self.reloader = None
        reload_cfg = serve_cfg.get("reload") or {}
        if bool(reload_cfg.get("enabled")):
            from sheeprl_tpu.serve.reload import CheckpointReloadSource, WeightReloader

            source = CheckpointReloadSource(
                str(reload_cfg.get("watch_dir") or os.path.dirname(cfg.checkpoint_path)),
                fabric,
                cfg,
                current_path=str(cfg.checkpoint_path),
            )
            # no explicit device: staged params stay uncommitted like the boot
            # params, so a swap never changes the step/attach jit signature
            self.reloader = WeightReloader(
                self.server,
                source,
                telemetry=self.telemetry,
                poll_s=float(reload_cfg.get("poll_s") or 2.0),
            )
        self.drained = False
        self._stop_watch = threading.Event()
        self._watcher: Optional[threading.Thread] = None

    # -- drain / health watcher ----------------------------------------------------

    def _set_health(self, ready: bool, status: str) -> None:
        endpoint = getattr(self.telemetry, "metrics_endpoint", None)
        if endpoint is not None:
            endpoint.set_health(
                {
                    "ready": ready,
                    "status": status,
                    "draining": self.server.draining,
                    "degraded": self.server.degraded,
                    "weight_version": self.server.weight_version,
                    "sessions_active": self.server.active_sessions,
                    "queue_depth": self.server.queue_depth,
                }
            )

    def _watch(self) -> None:
        from sheeprl_tpu.resilience import signals

        grace = float(self.cfg.serve.get("drain_grace_s") or 10.0)
        while not self._stop_watch.wait(0.2):
            if signals.preemption_requested() and not self.drained:
                # cooperative SIGTERM → graceful drain: stop admissions, let
                # in-flight sessions finish inside the grace window, close
                # with a CLEAN summary (this is a wind-down, not a crash)
                self.drained = True
                self._set_health(False, "draining")
                print(
                    f"[sheeprl-serve] preemption requested: draining (grace "
                    f"{grace:.0f}s) — admissions stopped, in-flight sessions finishing",
                    file=sys.stderr,
                    flush=True,
                )
                self.server.drain(grace, clean_exit=True)
                return
            self._set_health(True, "ok")

    # -- lifecycle -----------------------------------------------------------------

    def run(self) -> Dict[str, Any]:
        """Serve the configured env sessions to completion (or drain). Returns
        ``{results, preempted, error, sessions_lost}``."""
        from sheeprl_tpu.resilience import signals
        from sheeprl_tpu.serve.drivers import run_env_sessions

        serve_cfg = self.cfg.serve
        sessions = int(serve_cfg.sessions)
        self.server.start()
        if self.reloader is not None:
            self.reloader.start()
        self._set_health(True, "ok")
        self._watcher = threading.Thread(
            target=self._watch, name="sheeprl-serve-watch", daemon=True
        )
        self._watcher.start()
        try:
            results = run_env_sessions(
                self.server,
                self.cfg,
                sessions=sessions,
                max_session_steps=int(serve_cfg.max_session_steps),
                log_dir=self.log_dir,
            )
        finally:
            if self.reloader is not None:
                self.reloader.stop()
            self._stop_watch.set()
            preempted = signals.preemption_requested()
            if preempted and self._watcher is not None:
                # let the watcher finish the drain it owns (grace-bounded)
                self._watcher.join(
                    timeout=float(serve_cfg.get("drain_grace_s") or 10.0) + 30.0
                )
            self._set_health(False, "stopped")
            self.server.close(clean_exit=self.server._error is None)
        lost = [r for r in results if r.get("error")]
        return {
            "results": results,
            "preempted": preempted,
            "error": self.server._error,
            # a drained session ended by the server, not by its episode: those
            # are wind-down casualties, not lost state; LOST sessions are the
            # crash path's — the supervisor's restart event carries the count
            "sessions_lost": len(lost),
        }


def serve_main(args: Optional[Sequence[str]] = None) -> int:
    """The ``serve`` verb implementation (called by ``sheeprl_tpu.cli.serve``)."""
    import jax

    import sheeprl_tpu  # noqa: F401 — populate the serve registry

    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.resilience import signals
    from sheeprl_tpu.resilience.restart_policy import RestartPolicy, run_restart_policy
    from sheeprl_tpu.serve.policy import resolve_serve_policy
    from sheeprl_tpu.serve.server import PolicyServer
    from sheeprl_tpu.serve.telemetry import ServingTelemetry
    from sheeprl_tpu.utils.checkpoint import load_checkpoint
    from sheeprl_tpu.utils.compile_cache import enable_compile_cache

    overrides = list(args if args is not None else sys.argv[1:])
    cfg = build_serve_cfg(overrides)
    serve_cfg = cfg.serve

    # the persistent compile cache is the serving cold-start story: a primed
    # (serve.prime=true) or previously-served policy compiles as a cache hit
    enable_compile_cache()

    fabric = Fabric(
        devices=1,
        accelerator=cfg.fabric.get("accelerator", "auto"),
        precision=cfg.fabric.get("precision", "32-true"),
        checkpoint_backend=str((cfg.get("checkpoint") or {}).get("backend", "pickle")),
    )
    # pin the platform BEFORE loading (same rationale as eval_algorithm)
    fabric._setup()

    if bool(serve_cfg.get("prime")):
        state = load_checkpoint(cfg.checkpoint_path)
        policy = resolve_serve_policy(fabric, cfg, state)
        server = PolicyServer(
            policy,
            slots=int(serve_cfg.slots),
            max_batch_wait_ms=float(serve_cfg.max_batch_wait_ms),
            base_seed=int(cfg.seed),
        )
        t0 = time.perf_counter()
        stats = _prime(server, policy)
        cache_dir = jax.config.jax_compilation_cache_dir
        print(
            f"[sheeprl-serve] primed {stats['programs']} serving program(s) for "
            f"{cfg.algo.name} ({stats['slots']} slots) in {time.perf_counter() - t0:.1f}s"
            f" — persistent cache at {cache_dir}"
        )
        return 0

    sessions = int(serve_cfg.sessions)
    if sessions < 1:
        print(
            "[sheeprl-serve] serve.sessions=0: nothing to drive. The in-process "
            "session API (PolicyServer.open_session) is the transport surface; "
            "set serve.sessions=N to run N concurrent env sessions to completion.",
            file=sys.stderr,
        )
        return 2

    log_dir = serve_cfg.get("log_dir") or _default_log_dir(cfg)
    os.makedirs(log_dir, exist_ok=True)

    # cooperative SIGTERM handling — lifecycle parity with training: the
    # handler records, the drain watcher acts (main-thread only; a serve
    # driven from a worker thread still drains via request_preemption)
    handler_installed = signals.install_preemption_handler()

    reload_cfg = serve_cfg.get("reload") or {}
    print(
        f"[sheeprl-serve] serving {cfg.algo.name} from {cfg.checkpoint_path} — "
        f"{serve_cfg.slots} slots, {sessions} env session(s), telemetry at {log_dir}"
        + (
            f", hot reload following {reload_cfg.get('watch_dir')}"
            if bool(reload_cfg.get("enabled"))
            else ""
        )
    )

    sup_cfg = serve_cfg.get("supervisor") or {}
    try:
        if not bool(sup_cfg.get("enabled")):
            info = _ServeAttempt(cfg, fabric, log_dir, attempt=0).run()
            return _verdict(info)

        # bounded-restart supervision of the serve loop itself: the training
        # supervisor's policy loop, with session-loss accounting per restart
        policy_obj = RestartPolicy.from_cfg(sup_cfg)
        # a preempted (SIGTERM-drained) serve EXITS 75 for the external
        # supervisor — restarting it in-process would undo the drain
        policy_obj.restart_on_preempt = False
        from sheeprl_tpu.obs.jsonl import JsonlEventSink

        sink = JsonlEventSink(os.path.join(log_dir, "telemetry.jsonl"))
        state: Dict[str, Any] = {"info": None, "lost_total": 0}

        def emit(event: str, **fields: Any) -> None:
            fields.setdefault("attempt", policy_obj.attempt)
            sink.emit(event, **fields)

        def run_attempt(attempt: int):
            try:
                info = _ServeAttempt(cfg, fabric, log_dir, attempt=attempt).run()
            except Exception as err:  # SystemExit/KeyboardInterrupt propagate
                # a boot-time crash (checkpoint read, telemetry port bind)
                # never reached the tick loop: no sessions existed, but the
                # restart budget must govern it like any crashed attempt
                info = {
                    "results": [],
                    "preempted": False,
                    "error": err,
                    "sessions_lost": 0,
                }
            state["info"] = info
            if info["preempted"]:
                return "preempt", info
            if info["error"] is not None:
                state["lost_total"] += int(info["sessions_lost"])
                return "crash", info
            return "completed", info

        def restart_fields(attempt, outcome, info):
            return {
                "error": repr(info.get("error"))[:500] if info.get("error") else None,
                "sessions_lost": int(info.get("sessions_lost") or 0),
                "sessions_lost_total": int(state["lost_total"]),
            }

        def giveup_fields(info):
            return {
                "error": repr(info.get("error")) if info.get("error") else None,
                "sessions_lost_total": int(state["lost_total"]),
            }

        def on_giveup(outcome, info):
            if info.get("error") is not None:
                raise info["error"]
            return "preempted"

        try:
            run_restart_policy(
                policy_obj,
                run_attempt,
                emit,
                restart_fields=restart_fields,
                giveup_fields=giveup_fields,
                on_giveup=on_giveup,
            )
        finally:
            sink.close()
        return _verdict(state["info"])
    finally:
        if handler_installed:
            signals.uninstall_preemption_handler()


def _verdict(info: Optional[Dict[str, Any]]) -> int:
    """Map one attempt's outcome onto the serve exit codes."""
    from sheeprl_tpu.resilience.signals import PREEMPTED_EXIT_CODE

    if info is None:
        return 1
    for r in info["results"]:
        print(
            f"[sheeprl-serve] session seed={r.get('seed')}: {r.get('steps', 0)} steps, "
            f"reward {r.get('reward', 0.0):.2f}"
            + (f" — ERROR {r['error']}" if r.get("error") else "")
        )
    if info["preempted"]:
        print(
            "[sheeprl-serve] drained after preemption request — clean exit "
            f"(code {PREEMPTED_EXIT_CODE})"
        )
        return PREEMPTED_EXIT_CODE
    if info["error"] is not None:
        print(f"[sheeprl-serve] server crashed: {info['error']!r}", file=sys.stderr)
        return 1
    return 1 if any(r.get("error") for r in info["results"]) else 0
