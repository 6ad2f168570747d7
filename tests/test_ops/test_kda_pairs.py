"""The decayed-pairs kernels of Kimi delta attention (`sheeprl_tpu/ops/kda_pairs.py`) in Pallas'
interpreter against the XLA form they replace on the chip (`models/kimi_linear.py::
_decayed_pairs`, twice): both matrices, and the gradients through the kernel's `custom_vjp` (its
backward kernel) against XLA's autodiff of the XLA form in float64, in ``q``, ``k`` and ``G``, over ragged
tails, sub-chunks of 8, 16 and 32, decays near 0 and near 1 and decays that take a chunk's state to
nothing; the shapes the kernels refuse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import kimi_linear
from sheeprl_tpu.ops import kda_pairs

D = kda_pairs.LANES
# float32 sums over 128 channels and over sub-chunks taken in another order, over the largest entry
TOLERANCE = 2e-6


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _chunked(t, chunk, decay, seed, dead_channels=0):
    """``q``, ``k``, ``G`` ``[n, B, H, chunk, dk]`` as `chunk_kda` makes them from the layer's
    ``[B, T, H, dk]`` (a ragged tail padded with ``k`` 0 and ``g`` 0), with a step's ``exp(g)`` in
    ``decay`` (log-uniform), or 1e-30 in the first ``dead_channels`` key channels."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (2, t, 2, D)
    q = kimi_linear.l2_norm(jax.random.normal(keys[0], shape)) / np.sqrt(D)
    k = kimi_linear.l2_norm(jax.random.normal(keys[1], shape))
    g = jax.random.uniform(keys[2], shape, minval=np.log(decay[0]), maxval=np.log(decay[1]))
    g = g.at[..., :dead_channels].set(np.log(1e-30))
    pad, n = (-t) % chunk, -(-t // chunk)

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(2, n, chunk, 2, D)
        return jnp.moveaxis(jnp.swapaxes(x, 2, 3), 1, 0)

    q, k, g = map(chunks, (q, k, g))
    return q, k, jnp.cumsum(g, axis=-2)


def _xla(q, k, since, sub):
    return kimi_linear._decayed_pairs(k, k, since, sub), kimi_linear._decayed_pairs(q, k, since, sub)


def _kernel(q, k, since, sub):
    return kda_pairs.decayed_pairs(q, k, since, sub, True)


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


@pytest.mark.parametrize("t, chunk, sub, decay, dead", [
    (20, 16, 8, (0.2, 0.999), 0), (64, 32, 8, (0.2, 0.999), 0), (70, 64, 16, (1e-4, 0.9999), 0),
    (64, 64, 32, (0.2, 0.999), 0), (16, 16, 16, (0.2, 0.999), 0), (40, 16, 8, (0.9999, 1.0), 0),
    (40, 16, 8, (1e-6, 1e-3), 0), (32, 32, 8, (0.2, 0.999), 3)],
    ids=["ragged", "subchunks_of_8", "subchunks_of_16_near_0_beside_near_1", "subchunks_of_32", "one_subchunk",
         "near_1", "near_0", "decay_1e-30"])
def test_the_kernels_are_the_xla_form_in_values_and_gradients(t, chunk, sub, decay, dead):
    q, k, since = _chunked(t, chunk, decay, seed=t + chunk + sub, dead_channels=dead)
    assert kda_pairs.supports(k.shape, sub)
    want, got = jax.jit(_xla, static_argnums=3)(q, k, since, sub), jax.jit(_kernel, static_argnums=3)(q, k, since, sub)
    for a, b in zip(got, want):
        assert np.all(np.isfinite(a)) and _gap(a, b) <= TOLERANCE
        assert np.all(np.asarray(a)[..., np.triu_indices(chunk, 1)[0], np.triu_indices(chunk, 1)[1]] == 0)
    cotangents = jax.random.normal(jax.random.PRNGKey(sub), (2, *want[0].shape))

    def loss(pairs):
        return lambda *x: sum(jnp.sum(p * c) for p, c in zip(pairs(*x, sub), cotangents))

    got = jax.jit(jax.grad(loss(_kernel), argnums=(0, 1, 2)))(q, k, since)
    # XLA's autodiff in float64: in float32 its dG keeps the rounding of the diagonal pairs, which
    # it adds to both sides of a difference, 1.4e-4 of the largest dG where decays are near 0
    with jax.enable_x64(True):
        want = jax.jit(jax.grad(loss(_xla), argnums=(0, 1, 2)))(*(x.astype(jnp.float64) for x in (q, k, since)))
    for a, b in zip(got, want):  # dq, dk, dG
        assert np.all(np.isfinite(a)) and _gap(a, b) <= TOLERANCE


def test_the_backward_reads_no_cotangent_above_the_diagonal():
    """Above the diagonal both matrices are 0 whatever the inputs: a cotangent there changes no
    gradient (the rule's ``inside`` gets a full one from the products that read it)."""
    q, k, since = _chunked(32, 32, (0.2, 0.999), seed=1)
    cotangents = jax.random.normal(jax.random.PRNGKey(2), (2, *k.shape[:-1], 32))
    upper = jnp.triu(jnp.ones((32, 32), bool), 1)

    def grads(cts):
        return jax.grad(lambda *x: sum(jnp.sum(p * c) for p, c in zip(_kernel(*x, 8), cts)), argnums=(0, 1, 2))(q, k, since)

    for a, b in zip(grads(cotangents), grads(jnp.where(upper, 0.0, cotangents))):
        np.testing.assert_array_equal(a, b)


def test_the_kernels_refuse_shapes_they_cannot_tile():
    assert kda_pairs.supports((8, 16, 32, 64, D), 16) and kda_pairs.supports((4, 16, 2 * D), 8)
    assert not kda_pairs.supports((4, 16, 64), 8)  # dk no whole lane tile
    assert not kda_pairs.supports((4, 16, D), 4)  # a sub-chunk no whole sublane tile
    assert not kda_pairs.supports((4, 40, D), 16)  # the chunk no whole number of sub-chunks
    x = jnp.zeros((4, 16, 64))
    with pytest.raises(ValueError, match="lanes"):
        kda_pairs.decayed_pairs(x, x, x, 8, True)
