"""A program's trace depends on what its builder was handed, never on what the process
built before: no lower layer keeps a switch that a `Fabric` set up earlier has flipped."""

from __future__ import annotations

import re

import jax
import pytest

from sheeprl_tpu.analysis.programs import FUSED_PROGRAMS, ensure_registry
from sheeprl_tpu.parallel.fabric import Fabric

ensure_registry()


def _jaxpr_text(name: str) -> str:
    """The jaxpr of the family's train program (its losses and their gradients), built on
    a one-device fabric by the loop's own factory; addresses in printed callables go."""
    fn, args = FUSED_PROGRAMS[name].builder()
    return re.sub(r"0x[0-9a-f]+", "0x", str(fn.trace(*args).jaxpr))


@pytest.mark.parametrize("name", ["dreamer_v3.train_step", "dreamer_v2.train_step", "sac_ae.train_phase"])
def test_trace_is_the_same_after_a_multi_device_fabric_was_set_up(name):
    before = _jaxpr_text(name)
    Fabric(devices=len(jax.devices("cpu")), accelerator="cpu")._setup()
    same = _jaxpr_text(name) == before  # not compared inside the assert: a diff of two jaxprs is thousands of lines
    assert same, f"{name} traces another program once a multi-device Fabric has been set up in the process"


@pytest.mark.parametrize("family", ["dreamer_v3", "dreamer_v2"])
@pytest.mark.parametrize("devices", [1, 2])
def test_the_agent_takes_the_fused_gru_step_from_the_fabric_it_is_handed(family, devices):
    import importlib

    from sheeprl_tpu.analysis.programs import tiny_dreamer_cfg, tiny_obs_space

    fabric = Fabric(devices=devices, accelerator="cpu")
    fabric._setup()
    build_agent = importlib.import_module(f"sheeprl_tpu.algos.{family}.agent").build_agent
    agent, _ = build_agent(fabric, (4,), False, tiny_dreamer_cfg(family), tiny_obs_space(), jax.random.PRNGKey(0))
    assert agent.recurrent_model.fused_step is (devices == 1)
