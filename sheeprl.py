"""Root launcher for no-install source checkouts (role of reference sheeprl.py):
``python sheeprl.py exp=ppo env=gym env.id=CartPole-v1``.

Also hosts the offline/observability tooling (howto/observability.md):

- ``python sheeprl.py diagnose <run_dir>`` — merge a run's telemetry.jsonl
  stream(s) and print a rule-based bottleneck report;
- ``python sheeprl.py profile <run_dir>`` — op-level attribution of the run's
  ``jax.profiler`` window capture(s): comm/mxu/copy/idle shares of device
  time, achieved FLOP/s + roofline position per registered fused program
  (``profile.json``, ``--fail-on`` gate);
- ``python sheeprl.py watch <run_dir>`` — live terminal monitor that follows
  the stream(s) of a running (or about-to-start) run and exits with its status;
- ``python sheeprl.py compare <run_a> <run_b>`` — fingerprint-aware cross-run
  diff with noise-aware regression findings (``comparison.json``);
- ``python sheeprl.py trace <run_dir|fleet_dir>`` — convert the merged
  telemetry streams into a Perfetto/Chrome-trace JSON (one track per
  member/rank/role, phase spans, cross-process dataflow flow events);
- ``python sheeprl.py bench-diff <old.json> <new.json>`` — the BENCH_*.json
  regression gate (``--fail-on regression`` for CI);
- ``python sheeprl.py slo <run_dir|fleet_dir|live_dir>`` — replay the run's
  windows through its declared SLOs: per-objective burn rates and error-budget
  remaining, recorded/recomputed alert states (``slo.json``, ``--fail-on
  warning|critical``);
- ``python sheeprl.py fault-matrix`` — the resilience fault matrix on the CPU
  mesh (single-process + rank-targeted distributed fault smokes; see
  ``howto/fault_tolerance.md``);
- ``python sheeprl.py serve checkpoint_path=<ckpt>`` — the policy serving
  tier: continuous-batching inference over a device-resident session-slot
  table (``howto/serving.md``);
- ``python sheeprl.py fleet <spec.yaml>`` — schedule a fleet of member runs
  (seed/env sweeps) with per-member restart supervision, a shared persistent
  XLA compile cache, and leaderboard/compare rollups (``howto/fleet.md``);
- ``python sheeprl.py lint [--aot]`` — the JAX-aware static-analysis +
  AOT program-contract gate (``howto/static_analysis.md``).
"""

import os
import sys


def _lint_pin() -> None:
    """``lint`` is an offline gate: pin the CPU platform (it must never claim a
    chip another process may hold) and force the 8-device virtual
    host mesh BEFORE jax initializes, so the ``--aot`` sweep can lower the
    data-parallel mesh programs. Must run before the sheeprl_tpu import below,
    which executes jax computations."""
    if len(sys.argv) > 1 and sys.argv[1] == "lint":
        # FORCE the pins — not setdefault: a user's exported JAX_PLATFORMS=tpu
        # would otherwise initialize the accelerator the verb promises never
        # to touch, and an exported
        # --xla_force_host_platform_device_count=1 would silently skip the
        # 8-device anakin contract while the gate reports green. Pre-existing
        # unrelated XLA_FLAGS (e.g. --xla_dump_to) are preserved; any existing
        # device-count flag is REPLACED with 8.
        import re as _re

        flags = _re.sub(
            r"--xla_force_host_platform_device_count=\d+", "", os.environ.get("XLA_FLAGS", "")
        ).strip()
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ["JAX_PLATFORMS"] = "cpu"


_lint_pin()


def _gang_parent_pin() -> None:
    """Duplicated from sheeprl_tpu/__main__.py on purpose: it must run BEFORE
    the sheeprl_tpu import below (which executes jax computations), and
    importing anything from the package would trigger exactly that. The gang
    SUPERVISOR never trains, so pin it to the CPU backend."""
    if os.environ.get("SHEEPRL_GANG_RANK") or os.environ.get("SHEEPRL_GANG_PLATFORM"):
        return
    for arg in sys.argv[1:]:
        if arg.startswith("resilience.distributed.gang.processes="):
            value = arg.split("=", 1)[1].strip()
            if value.isdigit() and int(value) >= 2:
                import jax

                jax.config.update("jax_platforms", "cpu")
            return


_gang_parent_pin()

from sheeprl_tpu.cli import (  # noqa: E402
    bench_diff,
    compare,
    diagnose,
    fault_matrix,
    fleet,
    lint,
    live,
    profile,
    run,
    serve,
    slo,
    trace,
    watch,
)

_SUBCOMMANDS = {
    "diagnose": diagnose,
    "profile": profile,
    "watch": watch,
    "compare": compare,
    "bench-diff": bench_diff,
    "fault-matrix": fault_matrix,
    "serve": serve,
    "slo": slo,
    "fleet": fleet,
    "live": live,
    "trace": trace,
    "lint": lint,
}

if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] in _SUBCOMMANDS:
        raise SystemExit(_SUBCOMMANDS[sys.argv[1]](sys.argv[2:]))
    run()
