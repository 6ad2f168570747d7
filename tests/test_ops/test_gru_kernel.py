"""Numerical parity of the fused Pallas LayerNorm-GRU step (interpret mode on CPU)
against the pure-XLA reference and against the LayerNormGRUCell module."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.ops.gru import (
    fused_ln_gru_step,
    ln_gru_step_reference,
    pallas_gru_applicable,
)


def _random_case(key, B, X, H, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    inp = jax.random.normal(ks[0], (B, X + H), dtype)
    hx = jax.random.normal(ks[1], (B, H), dtype)
    w = jax.random.normal(ks[2], (X + H, 3 * H), dtype) * 0.3
    b = jax.random.normal(ks[3], (3 * H,), dtype) * 0.1
    scale = 1.0 + 0.1 * jax.random.normal(ks[4], (3 * H,), dtype)
    bias = 0.1 * jax.random.normal(ks[5], (3 * H,), dtype)
    return inp, hx, w, b, scale, bias


@pytest.mark.parametrize("B,X,H", [(4, 6, 8), (16, 32, 64), (33, 8, 16)])
def test_kernel_matches_reference(B, X, H):
    args = _random_case(jax.random.PRNGKey(0), B, X, H)
    ref = ln_gru_step_reference(*args)
    out = fused_ln_gru_step(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_kernel_matches_reference_with_batch_grid():
    """Batch larger than one block exercises the grid tiling."""
    args = _random_case(jax.random.PRNGKey(1), 300, 16, 32)
    ref = ln_gru_step_reference(*args)
    out = fused_ln_gru_step(*args, block_b=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_module_uses_same_math():
    """LayerNormGRUCell (XLA path on CPU) must equal the reference step exactly —
    the Pallas path is parity-tested against the same function above."""
    from sheeprl_tpu.models.models import LayerNormGRUCell

    B, X, H = 5, 7, 12
    cell = LayerNormGRUCell(hidden_size=H)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, X))
    hx = jax.random.normal(jax.random.PRNGKey(3), (B, H))
    params = cell.init(jax.random.PRNGKey(4), hx, x)["params"]
    out = cell.apply({"params": params}, hx, x)
    inp = jnp.concatenate([x, hx], axis=-1)
    ref = ln_gru_step_reference(
        inp, hx, params["kernel"], params["bias"], params["ln_scale"], params["ln_bias"]
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_kernel_gradient_matches_reference():
    """The custom VJP (XLA backward behind the Pallas forward) must produce the
    same gradients as differentiating the reference directly."""
    args = _random_case(jax.random.PRNGKey(5), 8, 6, 16)

    def loss_fused(*a):
        return jnp.sum(fused_ln_gru_step(*a, interpret=True) ** 2)

    def loss_ref(*a):
        return jnp.sum(ln_gru_step_reference(*a) ** 2)

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4, 5))(*args)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4, 5))(*args)
    for gf, gr in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=1e-5, atol=1e-5)


def test_applicability_bounds():
    assert pallas_gru_applicable(512, 256)  # XS
    assert pallas_gru_applicable(1024, 512)  # S-scale (K = mlp+h = 1024) fits
    assert not pallas_gru_applicable(1664, 1024)  # M (20 MiB block): XLA
    assert not pallas_gru_applicable(12288, 4096)  # XL falls back to XLA
    # lower bound: the benchmark exp's toy cell (H=8, K=16) never reaches Mosaic
    assert not pallas_gru_applicable(16, 8)
    assert not pallas_gru_applicable(1000, 512)  # K off the lane grid


@pytest.mark.slow
def test_gradients_flow_through_module():
    from sheeprl_tpu.models.models import LayerNormGRUCell

    cell = LayerNormGRUCell(hidden_size=8)
    x = jnp.ones((3, 4))
    hx = jnp.zeros((3, 8))
    params = cell.init(jax.random.PRNGKey(0), hx, x)["params"]

    def loss(p):
        return jnp.sum(cell.apply({"params": p}, hx, x) ** 2)

    grads = jax.grad(loss)(params)
    assert float(jnp.abs(grads["kernel"]).sum()) > 0
    assert float(jnp.abs(grads["ln_scale"]).sum()) > 0
