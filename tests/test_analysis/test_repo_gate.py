"""The zero-findings gate on the repo itself: the lint catalog must hold at
zero unwaived findings on the current tree (exceptions live in
``analysis/waivers.toml``, each with a reason). This is tier-1's standing
TPU-hazard audit — a PR that reintroduces a ``jax.devices()`` global view,
an unpinned Pallas dot, an
unregistered telemetry event, a hookless training loop or a config/code key
drift fails HERE, before any chip sees it."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from sheeprl_tpu.analysis.engine import lint_summary, repo_root, run_lint

pytestmark = pytest.mark.lint

REPO_ROOT = str(repo_root())


def test_repo_lint_has_zero_unwaived_findings():
    report = run_lint()
    assert report["findings"] == [], (
        "unwaived lint findings on the tree — fix them or add a reasoned waiver "
        "to sheeprl_tpu/analysis/waivers.toml:\n"
        + "\n".join(
            f"  [{f['severity']}] {f['rule']}: {f['file']}:{f['line']} — {f['summary']}"
            for f in report["findings"]
        )
    )
    # all 7 rules actually ran (a rule that silently skipped would hollow the gate)
    assert len(report["rules_run"]) >= 7


def test_lint_summary_shape():
    report = run_lint()
    summary = lint_summary(report)
    assert summary["findings"] == 0
    assert isinstance(summary["waived"], int)
    assert "jax-devices-global-view" in summary["rules_run"]


def test_cli_gate_exits_zero_and_json_is_machine_readable():
    proc = subprocess.run(
        [sys.executable, "sheeprl.py", "lint", "--fail-on", "warning", "--json"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=240,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["findings"] == [] and report["counts"]["critical"] == 0


def test_cli_fail_on_gates_a_seeded_finding(tmp_path, monkeypatch):
    # drop a hazard into a COPY of the package layout and point the engine at it
    pkg = tmp_path / "sheeprl_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text("import jax\nd = jax.devices()[0]\n")
    report = run_lint(root=str(tmp_path), use_waivers=False)
    assert any(f["rule"] == "jax-devices-global-view" for f in report["findings"])


@pytest.mark.slow
def test_cli_full_aot_gate_exits_zero():
    """The acceptance command verbatim: ``python sheeprl.py lint --aot
    --fail-on warning`` exits 0 (static rules + the whole program-contract
    sweep). Slow tier: the sweep itself runs in tier-1 as the parametrized
    test_aot_contracts pass; this pins the operational entry point."""
    proc = subprocess.run(
        [sys.executable, "sheeprl.py", "lint", "--aot", "--fail-on", "warning"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=1800,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
