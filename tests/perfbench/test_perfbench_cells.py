"""Every cell's whole run at tiny widths, with the CPU accepted from inside the test
(as tests/test_chip_smoke.py does for the smoke): set-up, the first three steps
followed by the reference, warm-up, a window of whole cycles, the comparison."""

import pytest

from perfbench.harness import bench

CELLS = ["dv3_XL_crafter.train_4env", "dv3_L_doapp128.train", "dv3_XL_crafter.train"]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


@pytest.mark.timeout(600)
@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_agrees_with_the_reference_at_tiny_widths(workload, tiny):
    result = bench.run_cell(workload, 2**31 + 77, 0.5, False, platform="cpu", extra_overrides=tiny)
    assert list(result) == KEYS  # the result line's keys, `compared` last
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 6
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"}
    assert result["metrics"]["env_steps_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["unit"] == "s"
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    compared = result["compared"]
    # float32 on both sides here: the two implementations agree to rounding
    assert compared["wm_loss_gap"]["value"] < 1e-5
    for group in ("wm", "actor", "critic"):
        assert compared[f"{group}_grad_gap"]["value"] < 1e-3 and compared[f"{group}_update_gap"]["value"] < 1e-3
    assert compared["act_view_gap"] == {"value": 0.0, "limit": 0.0}


@pytest.mark.timeout(600)
def test_traced_run_reports_the_per_layer_metrics_it_can_read(tiny):
    result = bench.run_cell("dv3_XL_crafter.train_4env", 5, 0.5, True, platform="cpu", extra_overrides=tiny)
    assert result["correct"] is True
    metrics = result["metrics"]
    # spans and counters are read on any platform; there is no TPU capture here, and a
    # reader that finds nothing to read returns nothing (never 0 for a share of a peak)
    assert {"compile_s", "compiles_in_window", "host_env_act_share", "train_call_ms"} <= set(metrics)
    assert "train_step_mfu" not in metrics and "device_idle_share" not in metrics
    assert metrics["compiles_in_window"]["value"] == 0
    assert "env_steps_per_s" not in metrics
