"""The AOT program-contract sweep as ONE parametrized tier-1 test: every
program registered via ``@register_fused_program`` (the ~12 donated
``jax.jit`` train/serve programs plus the lowering-sensitive ops dispatches)
is built through its loop's OWN factory, lowered for its declared platforms
(cpu+tpu off-chip), and its contract asserted — donation survives lowering
(and XLA's optimization pipeline where the spec compiles), no host-transfer
markers, custom calls restricted to the declared allowlist, expected
collectives present on the mesh programs.

This subsumes the three hand-written AOT tests (anakin, serve slots,
test_tpu_lowering.py): those files now assert registration/negatives only, and
``python sheeprl.py lint --aot`` runs this identical sweep operationally."""

from __future__ import annotations

import pytest

from sheeprl_tpu.analysis.programs import (
    FUSED_PROGRAMS,
    check_program_contract,
    ensure_registry,
)

pytestmark = pytest.mark.lint

ensure_registry()

# the adoption floor: a refactor that quietly drops a family's registration
# must fail loudly here, not shrink the sweep
EXPECTED_PROGRAMS = {
    "sac.train_phase",
    "sac_ae.train_phase",
    "droq.train_phase",
    "dreamer_v1.train_step",
    "dreamer_v2.train_step",
    "dreamer_v3.train_step",
    "p2e_dv1.train_step",
    "p2e_dv2.train_step",
    "p2e_dv3.train_step",
    "ppo.anakin_step",
    "serve.slot_step",
    "serve.slot_attach",
    "ops.gru_pallas_step",
    "ops.gru_platform_dispatch",
    "ops.gru_step_grad",
}


def test_registry_covers_every_expected_program():
    assert EXPECTED_PROGRAMS <= set(FUSED_PROGRAMS), (
        "fused-program registry lost entries: "
        f"{sorted(EXPECTED_PROGRAMS - set(FUSED_PROGRAMS))}"
    )


def test_every_donated_program_sweeps_both_platforms():
    # acceptance: the sweep covers every registered donated program on BOTH
    # cpu and tpu lowering platforms (ops dispatch entries may be tpu-only —
    # their cpu negative is pinned in test_tpu_lowering.py)
    for name, spec in FUSED_PROGRAMS.items():
        if spec.contract.donated:
            assert set(spec.contract.platforms) == {"cpu", "tpu"}, name


@pytest.mark.timeout(420)
@pytest.mark.parametrize("name", sorted(FUSED_PROGRAMS))
def test_program_contract(name):
    findings = check_program_contract(FUSED_PROGRAMS[name])
    hard = [f for f in findings if f["severity"] != "info"]
    assert hard == [], "\n".join(f"{f['summary']} -> {f['suggestion']}" for f in hard)
    # on the 8-device tier-1 harness nothing should be skipped either
    skipped = [f for f in findings if f["severity"] == "info"]
    assert skipped == [], skipped[0]["summary"] if skipped else None
