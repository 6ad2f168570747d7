"""``python sheeprl.py live <spec> [key=value ...]`` — the closed-loop flywheel.

One supervised in-process gang that closes the production RL loop:

- **serve**: ``spec.servers`` :class:`~sheeprl_tpu.serve.server.PolicyServer`
  roles boot from ``checkpoint_path`` and drive real env sessions (the serve
  driver's traffic pattern). Serving slots double as actors: each finished
  session's trajectory is assembled OFF the tick loop
  (``serve/trajectory.py``) and shipped through an
  :class:`~sheeprl_tpu.data.service.ExperienceWriter` — slot ``rank k`` is
  actor rank ``k`` of the experience plane. Explore slots
  (``serve.explore.fraction``) inject session-seeded action noise; the
  remaining "real traffic" slots stay greedy and byte-exact.
- **learn**: ONE experience-service learner (the ``buffer.backend=service``
  learner of ``sac_decoupled``, verbatim) ingests those trajectories, trains
  continuously at ``algo.replay_ratio`` and publishes actor weights every
  ``buffer.service.publish_every`` rounds on the version-keyed weight plane.
- **reload**: every server's :class:`~sheeprl_tpu.serve.reload.WeightReloader`
  follows the plane via ``SubscriberReloadSource`` — new versions hot-swap
  between ticks, zero recompiles (same avals ⇒ same compiled step program).
  ``buffer.service.poll_weights=false`` freezes serving weights (and makes
  ``diagnose``'s weight_staleness detector fire, by design).

The roles share one process: the coordination plane is an in-process
:class:`~sheeprl_tpu.data.service.LocalKV`
(:func:`~sheeprl_tpu.data.service.install_local_service_plane`), the learner
runs on a worker thread with its own Fabric, and the whole gang is supervised
by the training supervisor's ``run_restart_policy`` — a crashed attempt
restarts the WHOLE flywheel (fresh plane, fresh roles) within the restart
budget. SIGTERM drains every server inside ``drain_grace_s``, lets the learner
take its emergency checkpoint, and exits ``75`` — lifecycle parity with
training and serving.

Telemetry: serve role 0 writes ``telemetry.jsonl``, role ``k>0``
``telemetry.serve{k}.jsonl``, the learner ``telemetry.learner.jsonl``, and the
gang supervisor ``telemetry.live.jsonl`` (``live`` lifecycle events +
restart/giveup) — all in the live dir, so ``watch``/``diagnose``/``trace``
stitch the session→ingest→train→publish→reload flow across role tracks.

Exit codes: ``0`` every session completed and the learner exited cleanly,
``1`` a role crashed (restart budget exhausted when supervised), ``2`` nothing
to drive, ``75`` SIGTERM → drained cleanly.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

__all__ = ["live_main"]

# a learner that outlives the serve roles' shutdown by this much is hung
_LEARNER_JOIN_S = 600.0


def _default_live_dir(spec: Dict[str, Any]) -> str:
    stamp = time.strftime("%Y-%m-%d_%H-%M-%S")
    return os.path.join("logs", "live", f"{spec['name']}_{stamp}")


def _learner_cfg(cfg: Any, spec: Dict[str, Any], live_dir: str) -> Any:
    """Derive the learner's config from the serving config: same checkpoint
    config (so avals — and therefore the compiled serving program — match the
    published weights), retargeted at the service backend and the live dir's
    role stream."""
    import copy

    import yaml

    from sheeprl_tpu.config import dotdict, set_by_path

    lcfg = dotdict(copy.deepcopy(dict(cfg)))
    set_by_path(lcfg, "buffer.backend", "service", create=True)
    set_by_path(lcfg, "buffer.service.actors", int(spec["servers"]), create=True)
    # the learner starts FRESH from cfg.seed (same init as training would) and
    # immediately publishes v1 — a spec learner override of
    # checkpoint.resume_from warm-starts it from a checkpoint instead
    set_by_path(lcfg, "checkpoint.resume_from", None, create=True)
    set_by_path(lcfg, "metric.telemetry.enabled", True, create=True)
    set_by_path(lcfg, "metric.telemetry.jsonl", True, create=True)
    set_by_path(
        lcfg,
        "metric.telemetry.jsonl_path",
        os.path.join(live_dir, "telemetry.jsonl"),
        create=True,
    )
    for item in spec["learner"]:
        if "=" not in item:
            raise ValueError(f"live spec learner override {item!r} must be key=value")
        key, raw = item.split("=", 1)
        try:
            value = yaml.safe_load(raw)
        except yaml.YAMLError:
            value = raw
        set_by_path(lcfg, key, value, create=True)
    return lcfg


class _ActorGraftSource:
    """The experience plane publishes the ACTOR subtree only (the decoupled
    learner's actors never need critic/temperature params), while a serve
    policy holds the family's FULL serving tree. Graft each polled subtree
    into the server's current params so the reloader's aval gate compares
    like with like; payloads that already match the full tree pass through."""

    name = "subscriber"

    def __init__(self, inner: Any, server: Any) -> None:
        self._inner = inner
        self._server = server

    def peek_available(self) -> Any:
        return self._inner.peek_available()

    def poll(self) -> Any:
        out = self._inner.poll()
        if out is None:
            return None
        tree, version, meta = out
        current = self._server.policy.params
        if (
            isinstance(current, dict)
            and "actor" in current
            and not (isinstance(tree, dict) and set(tree) == set(current))
        ):
            merged = dict(current)
            merged["actor"] = tree
            tree = merged
        return tree, version, meta


class _LiveRole:
    """One serving role of the gang: server + its trajectory ingest, weight
    subscription/reloader, dataflow lineage and per-role telemetry stream."""

    def __init__(
        self,
        rank: int,
        cfg: Any,
        fabric: Any,
        state: Any,
        live_dir: str,
        spec: Dict[str, Any],
        *,
        kv: Any,
        ns: str,
        opts: Dict[str, Any],
        attempt: int,
    ) -> None:
        from sheeprl_tpu.config import dotdict
        from sheeprl_tpu.data.service import ActorDataflow, ExperienceWriter, WeightSubscriber
        from sheeprl_tpu.resilience.faults import build_fault_plan
        from sheeprl_tpu.serve.policy import resolve_serve_policy
        from sheeprl_tpu.serve.reload import SubscriberReloadSource, WeightReloader
        from sheeprl_tpu.serve.server import PolicyServer
        from sheeprl_tpu.serve.telemetry import ServingTelemetry
        from sheeprl_tpu.serve.trajectory import TrajectoryIngest

        self.rank = int(rank)
        # each role drives sessions from its own seed plane (session seed =
        # cfg.seed + client index inside run_env_sessions)
        self.cfg = dotdict(dict(cfg))
        self.cfg["seed"] = int(cfg.seed) + self.rank * 10000
        serve_cfg = cfg.serve
        tcfg = serve_cfg.get("telemetry") or {}

        policy = resolve_serve_policy(fabric, cfg, state)
        stream = "telemetry.jsonl" if self.rank == 0 else f"telemetry.serve{self.rank}.jsonl"
        self.telemetry = ServingTelemetry(
            fabric,
            cfg,
            live_dir,
            enabled=bool(tcfg.get("enabled", True)),
            every=int(tcfg.get("every", 256)),
            attempt=attempt,
            rank=self.rank,
            jsonl_path=os.path.join(live_dir, stream),
            serve_info={
                "role": "serve",
                "rank": self.rank,
                "slots": int(serve_cfg.slots),
                "max_batch_wait_ms": float(serve_cfg.max_batch_wait_ms),
                "greedy": bool(serve_cfg.greedy),
                "checkpoint_path": str(cfg.checkpoint_path),
                **policy.meta,
            },
        )
        self.server = PolicyServer(
            policy,
            slots=int(serve_cfg.slots),
            max_batch_wait_ms=float(serve_cfg.max_batch_wait_ms),
            base_seed=int(self.cfg.seed),
            telemetry=self.telemetry,
            request_timeout=float(serve_cfg.request_timeout),
            max_queue=serve_cfg.get("max_queue"),
            deadline_ms=serve_cfg.get("deadline_ms"),
            degraded_wait_factor=float(serve_cfg.get("degraded_wait_factor") or 4.0),
            fault_plan=build_fault_plan(cfg.get("resilience")),
            explore_fraction=float((serve_cfg.get("explore") or {}).get("fraction") or 0.0),
            explore_noise=float((serve_cfg.get("explore") or {}).get("noise") or 0.3),
        )
        self.writer = ExperienceWriter(
            kv,
            ns,
            self.rank,
            max_inflight=opts["max_inflight"],
            flush_every=opts["flush_every"],
            poll_s=opts["poll_s"],
            timeout_s=opts["timeout_s"],
            abort_check=opts["abort_check"],
        )
        self.ingest = TrajectoryIngest(
            self.writer,
            mlp_keys=cfg.algo.mlp_keys.encoder,
            max_queue=int(spec["ingest"]["max_queue"]),
            sample_next_obs=bool(cfg.buffer.sample_next_obs),
            telemetry=self.telemetry,
            weight_version_of=lambda: self.server.weight_version,
        )
        self.server.trajectories = self.ingest
        self.subscriber = WeightSubscriber(
            kv, ns, poll_s=opts["poll_s"], timeout_s=opts["timeout_s"], abort_check=opts["abort_check"]
        )
        self.telemetry.attach_dataflow(ActorDataflow(self.writer, self.subscriber))
        self.reloader = None
        if bool(opts.get("poll_weights", True)):
            self.reloader = WeightReloader(
                self.server,
                _ActorGraftSource(SubscriberReloadSource(self.subscriber), self.server),
                telemetry=self.telemetry,
                poll_s=float(spec["reload_poll_s"]),
            )
        self.results: List[Dict[str, Any]] = []
        self.error: Optional[BaseException] = None

    def start(self) -> None:
        self.server.start()
        if self.reloader is not None:
            self.reloader.start()

    def drive(self, spec: Dict[str, Any], live_dir: str) -> None:
        """Run the role's session waves (the driver thread's body)."""
        from sheeprl_tpu.config import dotdict
        from sheeprl_tpu.resilience import signals
        from sheeprl_tpu.serve.drivers import run_env_sessions

        pause = float(spec["wave_pause_s"])
        try:
            for wave in range(int(spec["session_rounds"])):
                if wave and pause > 0:
                    # pace the waves (wave_pause_s) so a short-session workload
                    # still overlaps the learner's train→publish cadence —
                    # preemption cuts the pause short
                    deadline = time.monotonic() + pause
                    while time.monotonic() < deadline:
                        if signals.preemption_requested() or self.server._error is not None:
                            return
                        time.sleep(min(0.05, pause))
                if signals.preemption_requested() or self.server._error is not None:
                    return
                wave_cfg = dotdict(dict(self.cfg))
                wave_cfg["seed"] = int(self.cfg.seed) + wave * 100
                self.results.extend(
                    run_env_sessions(
                        self.server,
                        wave_cfg,
                        sessions=int(spec["sessions"]),
                        max_session_steps=int(spec["max_session_steps"]),
                        log_dir=live_dir,
                    )
                )
        except Exception as exc:
            self.error = exc

    def shutdown(self, *, preempted: bool) -> Dict[str, Any]:
        """Ordered role teardown: reloader → ingest (drain + ship) → final
        ingest accounting → writer EOS → server close. Returns the role's
        accounting for the gang's ``live`` shutdown event."""
        if self.reloader is not None:
            self.reloader.stop()
        self.ingest.close()
        snapshot = self.ingest.telemetry_snapshot()
        self.telemetry.emit_event(
            "ingest", role="actor", rank=self.rank, **snapshot, **self.writer.telemetry_snapshot()
        )
        try:
            self.writer.close(preempted=preempted)
        except Exception:
            pass  # a dead learner must not block the serve teardown
        self.server.close(clean_exit=self.server._error is None)
        return {
            "rank": self.rank,
            "sessions": len(self.results),
            "session_errors": sum(1 for r in self.results if r.get("error")),
            "reloads": int(self.server.reloads),
            "weight_version": int(self.server.weight_version),
            **snapshot,
        }


class _LiveAttempt:
    """One attempt of the whole gang: a fresh in-process service plane, a fresh
    learner thread and fresh serve roles; the supervisor runs several of these
    against the same live dir (per-attempt stream identity)."""

    def __init__(
        self, cfg: Any, lcfg: Any, fabric: Any, live_dir: str, spec: Dict[str, Any], attempt: int
    ) -> None:
        self.cfg = cfg
        self.lcfg = lcfg
        self.fabric = fabric
        self.live_dir = live_dir
        self.spec = spec
        self.attempt = int(attempt)

    def run(self, emit_live) -> Dict[str, Any]:
        from sheeprl_tpu.config import instantiate, set_by_path
        from sheeprl_tpu.data.service import (
            clear_local_service_plane,
            install_local_service_plane,
            service_options,
        )
        from sheeprl_tpu.resilience import signals
        from sheeprl_tpu.utils.checkpoint import load_checkpoint

        spec = self.spec
        servers = int(spec["servers"])
        kv, ns = install_local_service_plane()
        set_by_path(self.lcfg, "metric.telemetry.attempt", self.attempt, create=True)
        opts = service_options(self.lcfg)
        layout = {
            "nprocs": servers + 1,
            "actors": servers,
            "learners": 1,
            "actor_ranks": tuple(range(servers)),
            "learner_ranks": (servers,),
            "leader": servers,
        }

        roles: List[_LiveRole] = []
        learner_error: List[BaseException] = []
        lthread: Optional[threading.Thread] = None
        watcher: Optional[threading.Thread] = None
        stop_watch = threading.Event()
        drained = threading.Event()
        preempted = False
        try:
            # the learner's Fabric comes from the checkpoint config
            # (instantiate resolves its CheckpointCallback — the learner's
            # checkpoint path runs through fabric.call("on_checkpoint_player"))
            lfabric = instantiate(
                self.lcfg.fabric,
                checkpoint_backend=str(self.lcfg.checkpoint.get("backend", "pickle")),
                checkpoint_async=bool(self.lcfg.checkpoint.get("async_save", False)),
            )
            lfabric.local_mesh = True
            lfabric._setup()

            def _learn() -> None:
                from sheeprl_tpu.algos.sac.sac_decoupled import _service_learner

                try:
                    _service_learner(lfabric, self.lcfg, layout)
                except BaseException as exc:  # noqa: BLE001 — the gang must see it
                    learner_error.append(exc)

            lthread = threading.Thread(target=_learn, name="sheeprl-live-learner", daemon=True)
            lthread.start()

            state = load_checkpoint(self.cfg.checkpoint_path)
            for rank in range(servers):
                roles.append(
                    _LiveRole(
                        rank,
                        self.cfg,
                        self.fabric,
                        state,
                        self.live_dir,
                        spec,
                        kv=kv,
                        ns=ns,
                        opts=opts,
                        attempt=self.attempt,
                    )
                )
            del state
            for role in roles:
                role.start()
            emit_live(
                "live",
                status="start",
                servers=servers,
                sessions=int(spec["sessions"]),
                session_rounds=int(spec["session_rounds"]),
                slots=int(self.cfg.serve.slots),
                explore_slots=int(roles[0].server.explore_slots) if roles else 0,
                checkpoint_path=str(self.cfg.checkpoint_path),
                namespace=ns,
            )

            grace = float(spec["drain_grace_s"])

            def _watch() -> None:
                while not stop_watch.wait(0.2):
                    if signals.preemption_requested() and not drained.is_set():
                        drained.set()
                        print(
                            f"[sheeprl-live] preemption requested: draining {len(roles)} "
                            f"server(s) (grace {grace:.0f}s) — admissions stopped, "
                            "in-flight sessions finishing",
                            file=sys.stderr,
                            flush=True,
                        )
                        drains = [
                            threading.Thread(
                                target=role.server.drain,
                                args=(grace,),
                                kwargs={"clean_exit": True},
                                daemon=True,
                            )
                            for role in roles
                        ]
                        for t in drains:
                            t.start()
                        for t in drains:
                            t.join(timeout=grace + 30.0)
                        return

            watcher = threading.Thread(target=_watch, name="sheeprl-live-watch", daemon=True)
            watcher.start()

            drivers = [
                threading.Thread(
                    target=role.drive,
                    args=(spec, self.live_dir),
                    name=f"sheeprl-live-drive{role.rank}",
                    daemon=True,
                )
                for role in roles
            ]
            for t in drivers:
                t.start()
            for t in drivers:
                t.join()
        finally:
            stop_watch.set()
            preempted = signals.preemption_requested()
            if preempted and watcher is not None:
                # the watcher owns the drain — let it finish (grace-bounded)
                watcher.join(timeout=float(spec["drain_grace_s"]) + 60.0)
            role_info = []
            for role in roles:
                try:
                    role_info.append(role.shutdown(preempted=preempted))
                except Exception as exc:
                    if not isinstance(role.error, BaseException):
                        role.error = exc
            if lthread is not None:
                lthread.join(timeout=_LEARNER_JOIN_S)
                if lthread.is_alive():
                    learner_error.append(
                        TimeoutError(
                            f"learner did not exit within {_LEARNER_JOIN_S:.0f}s of serve shutdown"
                        )
                    )
            clear_local_service_plane()

        error: Optional[BaseException] = None
        for role in roles:
            if role.server._error is not None:
                error = role.server._error
                break
            if role.error is not None:
                error = role.error
                break
        if error is None and learner_error:
            error = learner_error[0]
        results = [r for role in roles for r in role.results]
        info = {
            "results": results,
            "preempted": preempted,
            "error": error,
            "sessions_lost": sum(1 for r in results if r.get("error")),
            "reloads": sum(int(r.get("reloads") or 0) for r in role_info),
            "roles": role_info,
        }
        emit_live(
            "live",
            status="shutdown",
            preempted=bool(preempted),
            error=repr(error)[:500] if error is not None else None,
            sessions=len(results),
            sessions_lost=int(info["sessions_lost"]),
            reloads=int(info["reloads"]),
            trajectories_ingested=sum(
                int(r.get("trajectories_ingested") or 0) for r in role_info
            ),
            trajectories_dropped=sum(
                int(r.get("trajectories_dropped") or 0) for r in role_info
            ),
            trajectory_rows=sum(int(r.get("trajectory_rows") or 0) for r in role_info),
        )
        return info


def live_main(args: Optional[Sequence[str]] = None) -> int:
    """The ``live`` verb implementation (called by ``sheeprl_tpu.cli.live``)."""
    import sheeprl_tpu  # noqa: F401 — populate the serve registry

    from sheeprl_tpu.live.spec import load_live_spec, serve_overrides, write_marker
    from sheeprl_tpu.obs.jsonl import JsonlEventSink
    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.resilience import signals
    from sheeprl_tpu.resilience.restart_policy import RestartPolicy, run_restart_policy
    from sheeprl_tpu.serve.main import build_serve_cfg
    from sheeprl_tpu.utils.compile_cache import enable_compile_cache
    from sheeprl_tpu.utils.logger import set_run_dir

    argv = list(args if args is not None else sys.argv[1:])
    if not argv:
        print("usage: sheeprl.py live <spec.yaml> [key=value ...]", file=sys.stderr)
        return 2
    spec = load_live_spec(argv[0], argv[1:])
    cfg = build_serve_cfg(serve_overrides(spec))
    if not str(cfg.algo.name).startswith("sac"):
        print(
            f"[sheeprl-live] checkpoint algo {cfg.algo.name!r} has no service learner: "
            "the live flywheel currently trains SAC-family policies "
            "(the learner is sac_decoupled's buffer.backend=service learner)",
            file=sys.stderr,
        )
        return 2
    if spec["servers"] < 1 or spec["sessions"] < 1:
        print(
            "[sheeprl-live] nothing to drive: the spec needs servers >= 1 and "
            "sessions >= 1 (each server drives its sessions through its own slots)",
            file=sys.stderr,
        )
        return 2

    live_dir = spec["log_dir"] or _default_live_dir(spec)
    os.makedirs(live_dir, exist_ok=True)
    # every role's artifacts land under the live dir: the learner's
    # run_base_dir (checkpoints, memmap buffer) resolves to <live_dir>/learner
    set_run_dir(live_dir)
    streams = {"serve0": "telemetry.jsonl", "learner": "telemetry.learner.jsonl", "live": "telemetry.live.jsonl"}
    for k in range(1, spec["servers"]):
        streams[f"serve{k}"] = f"telemetry.serve{k}.jsonl"
    write_marker(live_dir, spec, streams)

    lcfg = _learner_cfg(cfg, spec, live_dir)

    enable_compile_cache()
    fabric = Fabric(
        devices=1,
        accelerator=cfg.fabric.get("accelerator", "auto"),
        precision=cfg.fabric.get("precision", "32-true"),
        checkpoint_backend=str((cfg.get("checkpoint") or {}).get("backend", "pickle")),
    )
    fabric._setup()

    # cooperative SIGTERM: the handler records (main thread), the drain watcher
    # and the learner's resilience poll act — one signal winds the WHOLE gang down
    handler_installed = signals.install_preemption_handler()

    print(
        f"[sheeprl-live] flywheel {spec['name']}: {spec['servers']} server(s) x "
        f"{cfg.serve.slots} slots from {cfg.checkpoint_path}, "
        f"{spec['sessions']} session(s)/server x {spec['session_rounds']} wave(s), "
        f"explore fraction {(cfg.serve.get('explore') or {}).get('fraction', 0.0)}, "
        f"telemetry at {live_dir}"
    )

    sink = JsonlEventSink(os.path.join(live_dir, "telemetry.live.jsonl"))
    sup_cfg = spec["supervisor"]
    state: Dict[str, Any] = {"info": None, "lost_total": 0}
    policy_obj = RestartPolicy.from_cfg(sup_cfg)
    # a preempted (SIGTERM-drained) gang EXITS 75 for the external supervisor —
    # restarting it in-process would undo the drain
    policy_obj.restart_on_preempt = False

    def emit(event: str, **fields: Any) -> None:
        fields.setdefault("attempt", policy_obj.attempt)
        sink.emit(event, **fields)

    def run_attempt(attempt: int):
        try:
            info = _LiveAttempt(cfg, lcfg, fabric, live_dir, spec, attempt).run(emit)
        except Exception as err:  # SystemExit/KeyboardInterrupt propagate
            info = {"results": [], "preempted": False, "error": err, "sessions_lost": 0}
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
        return "gave_up"

    try:
        if not bool(sup_cfg.get("enabled")):
            outcome, info = run_attempt(0)
        else:
            run_restart_policy(
                policy_obj,
                run_attempt,
                emit,
                restart_fields=restart_fields,
                giveup_fields=giveup_fields,
                on_giveup=on_giveup,
            )
        return _verdict(state["info"])
    finally:
        sink.close()
        set_run_dir(None)
        if handler_installed:
            signals.uninstall_preemption_handler()


def _verdict(info: Optional[Dict[str, Any]]) -> int:
    """Map the final attempt's outcome onto the live exit codes."""
    from sheeprl_tpu.resilience.signals import PREEMPTED_EXIT_CODE

    if info is None:
        return 1
    for r in info.get("roles") or []:
        print(
            f"[sheeprl-live] serve{r['rank']}: {r['sessions']} session(s) "
            f"({r['session_errors']} failed), {r['trajectories_ingested']} "
            f"trajectorie(s) ingested ({r['trajectories_dropped']} shed), "
            f"{r['reloads']} hot reload(s) to weight v{r['weight_version']}"
        )
    if info["preempted"]:
        print(
            "[sheeprl-live] gang drained after preemption request — clean exit "
            f"(code {PREEMPTED_EXIT_CODE})"
        )
        return PREEMPTED_EXIT_CODE
    if info["error"] is not None:
        print(f"[sheeprl-live] gang crashed: {info['error']!r}", file=sys.stderr)
        return 1
    return 1 if any(r.get("error") for r in info["results"]) else 0
