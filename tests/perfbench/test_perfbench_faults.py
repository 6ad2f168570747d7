"""`correct` has to come out false when the timed path is broken underneath, and the
command has to refuse to run without the chip."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.harness import bench, faults


@pytest.mark.timeout(600)
@pytest.mark.parametrize("fault,numbers", [
    ("state_unchanged", ("wm_grad_gap", "wm_update_gap", "actor_update_gap", "critic_update_gap")),
    ("half_batch", ("wm_loss_gap", "wm_grad_gap")),
])
def test_a_planted_fault_reads_not_correct(fault, numbers, tiny):
    with faults.planted(fault):
        result = bench.run_cell("dv3_XL_crafter.train_4env", 11, 0.3, False, platform="cpu",
                                extra_overrides=tiny)
    sound = bench.run_cell("dv3_XL_crafter.train_4env", 11, 0.3, False, platform="cpu",
                           extra_overrides=tiny)
    assert sound["correct"] is True
    for name in numbers:  # the fault reads far above a sound run of the same seed
        assert result["compared"][name]["value"] > 100 * max(sound["compared"][name]["value"], 1e-6)
    if fault == "state_unchanged":  # by the measure's construction
        assert result["compared"]["wm_update_gap"]["value"] == pytest.approx(1.0)
        assert result["correct"] is False and result["failed"] >= 2


@pytest.mark.timeout(600)
def test_the_control_a_lower_precision_reads_above_a_sound_run(tiny):
    # on the chip the control is one bf16 pass per float32 matmul; the CPU has no such
    # pass, so the test size's control is the program's own bf16 compute path
    sound = bench.run_cell("dv3_XL_crafter.train_4env", 12, 0.3, False, platform="cpu", extra_overrides=tiny)
    control = bench.run_cell("dv3_XL_crafter.train_4env", 12, 0.3, False, platform="cpu",
                             extra_overrides=[*tiny, "fabric.precision=bf16-mixed"])
    assert control["compared"]["wm_loss_gap"]["value"] > 100 * sound["compared"]["wm_loss_gap"]["value"]
    assert control["compared"]["wm_grad_gap"]["value"] > 10 * sound["compared"]["wm_grad_gap"]["value"]


def test_the_command_exits_non_zero_without_the_chip(repo_root):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "dv3_XL_crafter.train_4env", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=repo_root, env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result
    assert "needs 1 tpu chip" in proc.stderr


def test_the_last_line_is_the_result_object_and_nothing_more(monkeypatch, capsys):
    stub = {"correct": True, "attempted": 6, "failed": 0,
            "metrics": {"env_steps_per_s": {"value": 3.2, "unit": "steps/s"}, "setup_s": {"value": 50.0, "unit": "s"}},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1},
            "compared": {"wm_loss_gap": {"value": 1e-6, "limit": 1e-3}}}
    monkeypatch.setattr(bench, "run_cell", lambda *a, **k: stub)
    assert bench.main(["--workload", "w", "--seed", str(2**31 + 9), "--seconds", "51", "--trace", "0"]) == 0
    last = capsys.readouterr().out.splitlines()[-1]
    parsed = json.loads(last)
    assert parsed == stub and list(parsed)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(parsed)
