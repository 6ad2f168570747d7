"""``python sheeprl.py fleet <spec>`` — schedule N member runs as one fleet.

Generalizes the PR 8 restart-policy supervisors: every member runs under its own
:class:`~sheeprl_tpu.resilience.restart_policy.RestartPolicy` (crash → resume
from the newest valid checkpoint INSIDE the member's dir — never a sibling's),
attempts are ``python -m sheeprl_tpu`` children with the member's overrides and
a pinned ``hydra.run.dir``, and the whole sweep shares the ONE persistent XLA
compile cache of the checkout (``utils/compile_cache.py``): the first member (run alone when ``stagger_first``) compiles,
every later member cold-starts as pure cache hits — measured, not assumed, via
the telemetry compile gauges (``compile.cold`` in ``leaderboard.json``).

Fleet layout::

    <fleet dir>/
      fleet.json               # the marker discovery/watch/diagnose key on
      telemetry.fleet.jsonl    # the runner's own event stream (spawn/exit/restart)
      members/<name>/          # one pinned hydra.run.dir per member
        telemetry.jsonl        #   one stream across that member's attempts
        attempt<K>.log         #   per-attempt child stdout/stderr
        version_N/...          #   the run's ordinary artifacts + checkpoints
      leaderboard.json         # ranked rollup + gate verdict (obs/compare findings)

A SIGTERM to the runner forwards to every live member child (their cooperative
preemption handler takes the emergency checkpoint) and stops scheduling new
members; fleet members default to ``restart_on_preempt: false`` — a reclaim is
the parent's signal to wind down, not to relaunch.
"""

from __future__ import annotations

import json
import os
import signal as _signal
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from sheeprl_tpu.fleet import spec as fleet_spec
from sheeprl_tpu.fleet.rollup import build_leaderboard, format_leaderboard
from sheeprl_tpu.utils.compile_cache import cache_dir

__all__ = ["run_fleet", "main"]


def _member_dir(fleet_dir: str, name: str) -> str:
    return os.path.join(fleet_dir, "members", name)


def _build_member_env(spec: Dict[str, Any]) -> Dict[str, str]:
    env = dict(os.environ)
    # the package must be importable from any cwd the member inherits
    import sheeprl_tpu

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(sheeprl_tpu.__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    if spec.get("compile_cache", True):
        # members inherit the ONE persistent cache every entry point uses
        # (utils/compile_cache.py: JAX_COMPILATION_CACHE_DIR or the in-checkout
        # default — never a per-fleet directory, whose time-stamped path would
        # start every sweep cold). A 0s persistence threshold makes even
        # sub-second CPU programs land in it, so later members cold-start as
        # pure cache hits.
        env.setdefault("SHEEPRL_JAX_CACHE_MIN_COMPILE_SECS", "0")
    for key, value in (spec.get("env") or {}).items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return env


def run_fleet(
    spec_path: str,
    *,
    fleet_dir: Optional[str] = None,
    fail_on: Optional[str] = None,
    max_parallel: Optional[int] = None,
) -> int:
    from sheeprl_tpu.obs.jsonl import JsonlEventSink
    from sheeprl_tpu.resilience import signals
    from sheeprl_tpu.resilience.discovery import find_latest_checkpoint
    from sheeprl_tpu.resilience.restart_policy import RestartPolicy, run_restart_policy

    spec = fleet_spec.load_spec(spec_path)
    if fleet_dir is None:
        stamp = time.strftime("%Y-%m-%d_%H-%M-%S")
        fleet_dir = os.path.join("logs", "fleets", f"{spec['name']}_{stamp}")
    fleet_dir = os.path.abspath(fleet_dir)
    os.makedirs(fleet_dir, exist_ok=True)
    fleet_spec.write_marker(fleet_dir, spec)
    member_env = _build_member_env(spec)
    parallel = max(int(max_parallel or spec["max_parallel"]), 1)

    sink = JsonlEventSink(os.path.join(fleet_dir, "telemetry.fleet.jsonl"))
    sink_lock = threading.Lock()
    live_children: Dict[str, subprocess.Popen] = {}
    live_lock = threading.Lock()

    # opt-in live metrics endpoint: `metric.telemetry.http_port=N` in the spec's
    # base overrides makes the RUNNER scrapeable (member counts/outcomes). The
    # override is NOT forwarded to the members — N co-scheduled children racing
    # one port would be noise; scrape the fleet at its runner.
    http_cfg: Dict[str, Any] = {}
    member_base: List[str] = []
    for arg in spec["base"]:
        if arg.startswith("metric.telemetry.http_port="):
            http_cfg["http_port"] = arg.split("=", 1)[1]
        elif arg.startswith("metric.telemetry.http_host="):
            http_cfg["http_host"] = arg.split("=", 1)[1]
        else:
            member_base.append(arg)
    endpoint = None
    if http_cfg.get("http_port") not in (None, "", "null"):
        from sheeprl_tpu.obs.metrics_http import build_endpoint

        endpoint = build_endpoint(http_cfg, labels={"fleet": str(spec["name"])})
    board_lock = threading.Lock()
    # members_* gauges count TERMINAL member outcomes only — the same classes
    # leaderboard.json records — while attempts/restarts count per-attempt
    # events (a restarted member is one member, several attempts)
    board = {
        "Fleet/attempts": 0,
        "Fleet/restarts": 0,
        "Fleet/members_finished": 0,
        "Fleet/members_completed": 0,
        "Fleet/members_preempted": 0,
        "Fleet/members_crashed": 0,
    }

    def _publish_board() -> None:
        if endpoint is None:
            return
        with board_lock:
            gauges = dict(board)
        with live_lock:
            gauges["Fleet/members_running"] = float(len(live_children))
        gauges["Fleet/members_total"] = float(len(spec["members"]))
        endpoint.update(gauges)

    def _board_event(event: str, fields: Dict[str, Any]) -> None:
        if endpoint is None:
            return
        with board_lock:
            if event == "member" and fields.get("status") == "spawn":
                board["Fleet/attempts"] += 1
            elif event == "restart":
                board["Fleet/restarts"] += 1
        _publish_board()

    def _board_result(outcome: str) -> None:
        if endpoint is None:
            return
        with board_lock:
            board["Fleet/members_finished"] += 1
            key = {"completed": "completed", "preempted": "preempted"}.get(
                str(outcome), "crashed"
            )
            board[f"Fleet/members_{key}"] += 1
        _publish_board()

    def emit(event: str, **fields: Any) -> None:
        with sink_lock:
            try:
                sink.emit(event, **fields)
            except OSError:
                pass
        _board_event(event, fields)

    emit(
        "fleet",
        status="start",
        name=spec["name"],
        members=[m["name"] for m in spec["members"]],
        max_parallel=parallel,
        compile_cache=cache_dir(member_env) if spec["compile_cache"] else None,
    )

    # code-health fingerprint for the whole sweep: one `lint --json` at startup
    # into the fleet dir (static rules only — the AOT sweep is a test/CI gate,
    # not a per-fleet cost), so leaderboard rollups record exactly which rule
    # catalog the fleet's code passed and what was waived (howto/static_analysis.md)
    try:
        from sheeprl_tpu.analysis.engine import lint_summary, run_lint

        lint_report = run_lint()
        with open(os.path.join(fleet_dir, "lint.json"), "w") as fh:
            json.dump(lint_report, fh, indent=2)
            fh.write("\n")
        emit("fleet", status="lint", **lint_summary(lint_report))
    except Exception as exc:  # noqa: BLE001 — lint must never take the fleet down
        emit("fleet", status="lint", error=repr(exc)[:300])

    handler_installed = signals.install_preemption_handler()

    def forward_preempt() -> None:
        with live_lock:
            children = list(live_children.values())
        for child in children:
            if child.poll() is None:
                try:
                    child.send_signal(_signal.SIGTERM)
                except OSError:
                    pass

    def run_member(member: Dict[str, Any]) -> Dict[str, Any]:
        name = member["name"]
        member_dir = _member_dir(fleet_dir, name)
        os.makedirs(member_dir, exist_ok=True)
        base_args = list(member_base) + list(member["overrides"]) + [
            f"hydra.run.dir={member_dir}",
            "metric.telemetry.enabled=true",
            f"metric.telemetry.jsonl_path={os.path.join(member_dir, 'telemetry.jsonl')}",
            # the FLEET owns the restart policy; an in-process supervisor on top
            # would double-restart and double-count attempts
            "resilience.supervisor.enabled=false",
        ]
        # EVERYTHING below (including the policy/timeout parsing — a malformed
        # spec value must not kill a scheduler thread) runs under the broad
        # except at the bottom: a broken member yields outcome="crashed" and a
        # member error event, never a dead worker with no leaderboard entry
        policy = None
        try:
            # fleet members default to NOT relaunching on preemption: a reclaim
            # that reached the runner is a wind-down, the runner stops scheduling
            restarts = {"restart_on_preempt": False, **spec["restarts"]}
            policy = RestartPolicy.from_cfg(restarts)
            # optional per-attempt wall budget (restarts.attempt_timeout secs): a
            # wedged member (e.g. an env worker pinning a crashed child alive)
            # gets SIGTERM, then SIGKILL after the cooperative-checkpoint grace —
            # the fleet must never block forever on one immortal member
            attempt_timeout = float(restarts.get("attempt_timeout") or 0.0)
            kill_grace = float(restarts.get("kill_grace") or 30.0)

            def emit_member(event: str, **fields: Any) -> None:
                fields.setdefault("member", name)
                fields.setdefault("attempt", policy.attempt)
                emit(event, **fields)

            def run_attempt(attempt: int):
                attempt_args = list(base_args)
                if attempt > 0:
                    # resume STRICTLY inside this member's dir — a sweep sibling's
                    # newer checkpoint must never hijack a retry (regression-pinned
                    # in tests/test_resilience/test_fleet_discovery.py)
                    attempt_args = [
                        a for a in attempt_args if not a.startswith("checkpoint.resume_from=")
                    ]
                    attempt_args.append("resilience.fault.kind=null")
                    resume = find_latest_checkpoint(member_dir)
                    if resume is not None:
                        attempt_args.append(f"checkpoint.resume_from={resume}")
                    attempt_args.append(f"metric.telemetry.attempt={attempt}")
                log_path = os.path.join(member_dir, f"attempt{attempt}.log")
                emit_member("member", status="spawn", args_tail=attempt_args[-4:])
                with open(log_path, "ab") as log_fh:
                    child = subprocess.Popen(
                        [sys.executable, "-m", "sheeprl_tpu"] + attempt_args,
                        env=member_env,
                        stdout=log_fh,
                        stderr=subprocess.STDOUT,
                        cwd=fleet_dir,
                    )
                with live_lock:
                    live_children[name] = child
                started = time.monotonic()
                terminated_at: Optional[float] = None
                try:
                    while child.poll() is None:
                        if signals.preemption_requested():
                            forward_preempt()
                        waited = time.monotonic() - started
                        if attempt_timeout and waited > attempt_timeout:
                            if terminated_at is None:
                                terminated_at = time.monotonic()
                                emit_member("member", status="timeout", seconds=round(waited, 1))
                                try:
                                    child.send_signal(_signal.SIGTERM)
                                except OSError:
                                    pass
                            elif time.monotonic() - terminated_at > kill_grace:
                                try:
                                    child.kill()
                                except OSError:
                                    pass
                        time.sleep(0.2)
                finally:
                    with live_lock:
                        live_children.pop(name, None)
                rc = int(child.returncode)
                outcome = (
                    "completed"
                    if rc == 0
                    else "preempt"
                    if rc == signals.PREEMPTED_EXIT_CODE
                    else "crash"
                )
                emit_member("member", status="exit", rc=rc, outcome=outcome, log=log_path)
                return outcome, {"rc": rc, "log": log_path}

            def restart_fields(attempt, outcome, info):
                resume = find_latest_checkpoint(member_dir)
                return {"member": name, "resume_from": str(resume) if resume else None}

            def on_giveup(outcome, info):
                return "preempted" if outcome == "preempt" else "crashed"

            outcome = run_restart_policy(
                policy,
                run_attempt,
                emit_member,
                restart_fields=restart_fields,
                giveup_fields=lambda info: {"member": name, "rc": info.get("rc")},
                on_giveup=on_giveup,
            )
        except Exception as exc:  # a broken member must not take the fleet down
            emit("member", status="error", member=name,
                 attempt=getattr(policy, "attempt", 0), error=repr(exc)[:300])
            outcome = "crashed"
        restarts_made = getattr(policy, "attempt", 0)
        _board_result(outcome)
        return {
            "name": name,
            "dir": member_dir,
            "outcome": outcome,
            # total attempts MADE (restarts + the first), preserved through the
            # rollup even when a member died before emitting any telemetry
            "attempts": restarts_made + 1,
            "restarts": restarts_made,
        }

    members = list(spec["members"])
    results: List[Dict[str, Any]] = []
    try:
        start_at = 0
        if spec["stagger_first"] and members:
            # the cache-warming stagger: member 0 runs ALONE so its compiles land
            # in the shared cache before any sibling starts
            results.append(run_member(members[0]))
            start_at = 1
        pending = members[start_at:]
        if pending and not signals.preemption_requested():
            if parallel <= 1:
                for member in pending:
                    if signals.preemption_requested():
                        results.append(
                            {"name": member["name"], "dir": _member_dir(fleet_dir, member["name"]),
                             "outcome": "skipped", "attempts": 0}
                        )
                        continue
                    results.append(run_member(member))
            else:
                slots = threading.Semaphore(parallel)
                out_lock = threading.Lock()
                slot_results: Dict[str, Dict[str, Any]] = {}

                def worker(member: Dict[str, Any]) -> None:
                    with slots:
                        if signals.preemption_requested():
                            result = {
                                "name": member["name"],
                                "dir": _member_dir(fleet_dir, member["name"]),
                                "outcome": "skipped",
                                "attempts": 0,
                            }
                        else:
                            result = run_member(member)
                    with out_lock:
                        slot_results[member["name"]] = result

                threads = [
                    threading.Thread(target=worker, args=(m,), daemon=True) for m in pending
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                # run_member never raises, but a worker lost to something truly
                # unexpected must still leave a leaderboard entry, not a KeyError
                results.extend(
                    slot_results.get(
                        m["name"],
                        {"name": m["name"], "dir": _member_dir(fleet_dir, m["name"]),
                         "outcome": "crashed", "attempts": 0},
                    )
                    for m in pending
                )
        elif pending:
            results.extend(
                {"name": m["name"], "dir": _member_dir(fleet_dir, m["name"]),
                 "outcome": "skipped", "attempts": 0}
                for m in pending
            )
    finally:
        forward_preempt()  # never orphan children on a forced unwind
        if handler_installed:
            signals.uninstall_preemption_handler()

    leaderboard = build_leaderboard(fleet_dir, spec, results, fail_on=fail_on)
    emit(
        "fleet",
        status="done",
        outcomes={r["name"]: r["outcome"] for r in results},
        gate=leaderboard["gate"],
        leaderboard=os.path.join(fleet_dir, "leaderboard.json"),
    )
    sink.close()
    if endpoint is not None:
        endpoint.close()
    print(format_leaderboard(leaderboard))
    print(f"\nfleet dir: {fleet_dir}\nleaderboard: {os.path.join(fleet_dir, 'leaderboard.json')}")
    return 1 if leaderboard["gate"]["failed"] else 0


def main(argv: Optional[List[str]] = None) -> int:
    """``python sheeprl.py fleet <spec.yaml>`` entry point."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="sheeprl.py fleet",
        description="Schedule a fleet of member runs (seed/env sweeps) with per-member "
        "restart policies, a shared persistent XLA compile cache, and fleet-level "
        "rollups (leaderboard.json, cross-member compare). See howto/fleet.md.",
    )
    parser.add_argument("spec", help="fleet spec file (YAML/JSON)")
    parser.add_argument("--dir", dest="fleet_dir", default=None, help="fleet directory (default: logs/fleets/<name>_<timestamp>)")
    parser.add_argument(
        "--fail-on",
        choices=("warning", "critical"),
        default=None,
        help="gate: exit 1 when any member's diagnosis/compare findings reach this "
        "severity (member crashes always fail the gate); overrides the spec's compare.fail_on",
    )
    parser.add_argument(
        "--max-parallel", type=int, default=None, help="override the spec's member slots"
    )
    args = parser.parse_args(list(argv) if argv is not None else sys.argv[1:])
    try:
        return run_fleet(
            args.spec,
            fleet_dir=args.fleet_dir,
            fail_on=args.fail_on,
            max_parallel=args.max_parallel,
        )
    except (FileNotFoundError, ValueError) as exc:
        print(f"fleet: {exc}", file=sys.stderr)
        return 2
