"""The driver contract (__graft_entry__) must stay green: entry() compiles
single-chip and dryrun_multichip() runs the FULL Dreamer-V3 train phase on a
virtual multi-device mesh with params replicated and the batch data-sharded.
Protecting it in-suite means a regression is caught before the driver's gate."""

from __future__ import annotations

import jax
import pytest


@pytest.mark.timeout(280)
def test_entry_compiles_and_runs():
    import __graft_entry__ as graft

    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    actions, h, z = out
    assert actions.shape[0] == h.shape[0] == z.shape[0]
    assert jax.numpy.isfinite(h).all()


@pytest.mark.timeout(280)
def test_dryrun_multichip_two_devices():
    """The conftest provides 8 virtual CPU devices; the dryrun's own asserts cover
    replication and loss finiteness."""
    import __graft_entry__ as graft

    graft.dryrun_multichip(2)
