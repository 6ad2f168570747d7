"""Fleet-runner CPU smokes: one REAL 2-member seed sweep (tiny PPO members)
through ``run_fleet``, asserted from its artifacts — the acceptance shape:

- both members complete, ``leaderboard.json`` written and ranked;
- the SHARED compile cache makes the second member's COLD compile count 0
  (``compile.cold``), measured from the telemetry compile gauges;
- the fleet dir diagnoses as one unit (``diagnose --fail-on critical`` green)
  and watches as one unit (fleet watch exits with the gate verdict);
- a crashing member restarts under its own policy and resumes from ITS OWN
  checkpoint (member-scoped discovery).

Marked ``fleet`` (tier-1: these are the fast CPU smokes; the gang-scale
experience-service smokes live in tests/test_resilience with ``slow``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from sheeprl_tpu.fleet.runner import run_fleet

pytestmark = pytest.mark.fleet

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_SPEC = """
name: smoke
base:
  - exp=ppo
  - env=dummy
  - env.id=discrete_dummy
  - env.num_envs=2
  - env.sync_env=True
  - env.capture_video=False
  - fabric.accelerator=cpu
  - algo.rollout_steps=16
  - algo.total_steps=64
  - algo.update_epochs=1
  - "algo.cnn_keys.encoder=[]"
  - "algo.mlp_keys.encoder=[state]"
  - algo.run_test=False
  - metric.log_level=0
  - checkpoint.save_last=True
  # the RUNNER binds the metrics endpoint (ephemeral port) and must NOT
  # forward the override to the members (N children racing one port)
  - metric.telemetry.http_port=0
sweep:
  seed: [42, 43]
restarts: {max_restarts: 1, backoff: 0.05, attempt_timeout: 120, kill_grace: 10}
env:
  JAX_PLATFORMS: cpu
  XLA_FLAGS: null
"""


@pytest.fixture(scope="module")
def fleet_run(tmp_path_factory):
    workdir = tmp_path_factory.mktemp("fleet")
    spec_path = workdir / "spec.yaml"
    spec_path.write_text(_SPEC)
    fleet_dir = str(workdir / "fleetdir")
    rc = run_fleet(str(spec_path), fleet_dir=fleet_dir, fail_on="critical")
    leaderboard = json.load(open(os.path.join(fleet_dir, "leaderboard.json")))
    return {"rc": rc, "dir": fleet_dir, "leaderboard": leaderboard}


@pytest.mark.timeout(420)
def test_fleet_completes_and_gate_green(fleet_run):
    assert fleet_run["rc"] == 0
    lb = fleet_run["leaderboard"]
    assert lb["gate"]["failed"] is False
    assert {m["name"] for m in lb["members"]} == {"seed-42", "seed-43"}
    assert all(m["outcome"] == "completed" for m in lb["members"])
    # ranked: every member has a rank and the rank metric populated
    assert [m["rank"] for m in lb["members"]] == [1, 2]
    assert all(isinstance((m["summary"] or {}).get("sps"), (int, float)) for m in lb["members"])
    # code-health fingerprint: the runner's startup `lint --json` pass landed in
    # the fleet dir and the rollup surfaced its summary (howto/static_analysis.md)
    assert os.path.isfile(os.path.join(fleet_run["dir"], "lint.json"))
    assert lb["lint"]["findings"] == 0 and len(lb["lint"]["rules_run"]) >= 7


def test_shared_cache_second_member_cold_compiles_zero(fleet_run):
    lb = fleet_run["leaderboard"]
    by_name = {m["name"]: m for m in lb["members"]}
    first, second = by_name["seed-42"], by_name["seed-43"]
    # the stagger ran seed-42 alone (cold only if the checkout's one cache was)...
    assert first["compile"]["count"] > 0
    # ...and seed-43 cold-started as PURE cache hits — the acceptance number
    assert second["compile"]["cold"] == 0, second["compile"]
    assert second["compile"]["cache_hits"] == second["compile"]["count"]
    # members inherit the one cache: no per-fleet (time-stamped) directory
    assert not os.path.exists(os.path.join(fleet_run["dir"], "xla_cache"))


def test_fleet_dir_diagnoses_as_one_unit(fleet_run):
    from sheeprl_tpu.cli import diagnose

    rc = diagnose([fleet_run["dir"], "--fail-on", "critical", "--quiet"])
    assert rc == 0
    aggregate = json.load(open(os.path.join(fleet_run["dir"], "diagnosis.json")))
    assert set(aggregate["members"]) == {"seed-42", "seed-43"}
    # every member also kept its own diagnosis.json
    for name in ("seed-42", "seed-43"):
        assert os.path.isfile(os.path.join(fleet_run["dir"], "members", name, "diagnosis.json"))


def test_fleet_dir_watches_as_one_unit(fleet_run):
    import io

    from sheeprl_tpu.obs.watch import watch_run

    out = io.StringIO()
    rc = watch_run(fleet_run["dir"], interval=0.05, grace=0.1, timeout=30, plain=True, out=out)
    assert rc == 0, out.getvalue()
    text = out.getvalue()
    assert "2 member(s)" in text and "gate green" in text


def test_member_telemetry_fingerprints_differ_by_seed(fleet_run):
    lb = fleet_run["leaderboard"]
    hashes = {m["fingerprint"]["config_hash"] for m in lb["members"]}
    assert len(hashes) == 2  # seed is part of the config identity
    # the cross-member compare ran against the baseline and left its artifact
    by_name = {m["name"]: m for m in lb["members"]}
    compare = by_name["seed-43"]["compare"]
    assert compare is not None and os.path.isfile(compare["json_path"])


def test_malformed_restart_knob_fails_the_member_not_the_fleet(tmp_path):
    # a spec value that breaks per-member setup (float("60s")) must yield a
    # crashed LEADERBOARD ENTRY + member error event — in parallel mode too,
    # where an unhandled worker exception used to kill the thread silently and
    # crash the fleet with no leaderboard at all
    spec_path = tmp_path / "spec.yaml"
    spec_path.write_text(
        """
name: broken
base: [exp=ppo]
sweep: {seed: [1, 2]}
max_parallel: 2
stagger_first: false
restarts: {attempt_timeout: 60s}
"""
    )
    fleet_dir = str(tmp_path / "fleetdir")
    rc = run_fleet(str(spec_path), fleet_dir=fleet_dir)
    lb = json.load(open(os.path.join(fleet_dir, "leaderboard.json")))
    assert rc == 1  # crashed members fail the gate
    assert all(m["outcome"] == "crashed" for m in lb["members"])
    events = [
        json.loads(line)
        for line in open(os.path.join(fleet_dir, "telemetry.fleet.jsonl"))
    ]
    assert any(e["event"] == "member" and e.get("status") == "error" for e in events)
    assert any(e["event"] == "fleet" and e.get("status") == "done" for e in events)


@pytest.mark.timeout(420)
def test_crashing_member_restarts_and_resumes_member_scoped(tmp_path):
    spec_path = tmp_path / "spec.yaml"
    spec_path.write_text(
        _SPEC.replace("seed: [42, 43]", "seed: [7]")
        + "members:\n"
        + "  - name: crasher\n"
        + "    overrides: [seed=8, resilience.fault.kind=crash, "
        # a cadence checkpoint (step 32) lands BEFORE the crash (fires at the
        # step-64 iteration), so the retry has member-local state to resume
        + "resilience.fault.at_policy_step=48, checkpoint.every=16]\n"
    )
    fleet_dir = str(tmp_path / "fleetdir")
    rc = run_fleet(str(spec_path), fleet_dir=fleet_dir, fail_on=None)
    lb = json.load(open(os.path.join(fleet_dir, "leaderboard.json")))
    by_name = {m["name"]: m for m in lb["members"]}
    assert rc == 0, lb["gate"]
    assert by_name["crasher"]["outcome"] == "completed"
    # attempt 2 happened and its resume stayed INSIDE the member dir
    events = [
        json.loads(line)
        for line in open(os.path.join(fleet_dir, "telemetry.fleet.jsonl"))
    ]
    restarts = [e for e in events if e["event"] == "restart" and e.get("member") == "crasher"]
    assert len(restarts) == 1
    resume = restarts[0].get("resume_from")
    assert resume and os.path.join("members", "crasher") in resume
