"""The delta-rule decode kernel (`sheeprl_tpu/ops/delta_rule_decode.py`) in Pallas' interpreter
against the XLA form it replaces on the chip (`models/qwen3_next.py::delta_rule_step` off the
TPU): a chain of steps with the state carried, at decays over the init's whole range and write
strengths over (0, 1), equal to float32 rounding, with the decay a head's scalar (Qwen3-Next's)
and a key channel's (Kimi delta attention's, `models/kimi_linear.py::kda_step`); the shapes it
refuses; the state written in the buffer it came in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import qwen3_next
from sheeprl_tpu.ops import delta_rule_decode as drd

D = drd.LANES
STEPS = 16
# the largest gap to the XLA form over the largest entry, after STEPS steps: float32 rounding of
# sums over 128 terms taken in another order
TOLERANCE = 2e-6


def _chain(batch, heads, seed, per_channel=False):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = qwen3_next.l2_norm(jax.random.normal(keys[0], (STEPS, batch, heads, D))) / np.sqrt(D)
    k = qwen3_next.l2_norm(jax.random.normal(keys[1], (STEPS, batch, heads, D)))
    v = jax.random.normal(keys[2], (STEPS, batch, heads, D))
    decays = (STEPS, batch, heads, D) if per_channel else (STEPS, batch, heads)
    g = jnp.log(jax.random.uniform(keys[3], decays, minval=0.2, maxval=0.999))  # a step's decay
    beta = jax.random.uniform(keys[4], (STEPS, batch, heads), minval=1e-3, maxval=1 - 1e-3)
    state = 0.5 * jax.random.normal(keys[5], (batch, heads, D, D))
    return state, (q, k, v, g, beta)


def _run(step, state, xs):
    def body(state, x):
        out, state = step(state, *x)
        return state, out

    return jax.jit(lambda s, xs: jax.lax.scan(body, s, xs))(state, xs)


@pytest.mark.parametrize("per_channel", [False, True], ids=["decay_a_head", "decay_a_key_channel"])
@pytest.mark.parametrize("batch", [2, 8])
@pytest.mark.parametrize("heads", [4, 32])
def test_the_kernel_is_the_xla_form_over_a_chain_of_steps(batch, heads, per_channel):
    state, xs = _chain(batch, heads, seed=batch + heads, per_channel=per_channel)
    assert jax.default_backend() != "tpu" and not qwen3_next.decode_kernel_taken(state.shape)
    want_state, want_out = _run(qwen3_next.delta_rule_step, state, xs)
    got_state, got_out = _run(lambda *a: drd.delta_rule_decode(*a, interpret=True), state, xs)
    decay = np.exp(np.asarray(xs[3]))
    assert decay.min() < 0.21 and decay.max() > 0.99  # the init's whole range of decays
    for got, want in ((got_state, want_state), (got_out, want_out)):
        assert np.all(np.isfinite(got))
        assert float(jnp.max(jnp.abs(got - want))) <= TOLERANCE * float(jnp.max(jnp.abs(want)))


def test_a_decay_alike_over_the_key_channels_is_the_decay_a_head():
    """The per-channel path given one value a head across its channels is the per-head path,
    whose code and result are what they were before the per-channel decay came."""
    state, (q, k, v, g, beta) = _chain(2, 4, seed=3)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    head_state, head_out = _run(lambda *a: drd.delta_rule_decode(*a, interpret=True), state, (q, k, v, g, beta))
    chan_state, chan_out = _run(lambda *a: drd.delta_rule_decode(*a, interpret=True), state, (q, k, v, wide, beta))
    for got, want in ((chan_state, head_state), (chan_out, head_out)):
        assert float(jnp.max(jnp.abs(got - want))) <= TOLERANCE * float(jnp.max(jnp.abs(want)))


def test_the_per_channel_decay_scales_the_states_rows():
    """One step from a state of ones with ``k`` = 0 (nothing written): the new state is the old
    one's row ``c`` times ``exp(g_c)``, the decay a key channel, and each column alike."""
    state, (q, k, v, g, beta) = _chain(2, 4, seed=4, per_channel=True)
    ones = jnp.ones_like(state)
    _, new = drd.delta_rule_decode(ones, q[0], jnp.zeros_like(k[0]), v[0], g[0], beta[0], interpret=True)
    np.testing.assert_allclose(new, jnp.broadcast_to(jnp.exp(g[0])[..., None], new.shape), rtol=1e-6)


def test_the_kernel_refuses_a_state_it_cannot_tile():
    assert drd.supports((64, 32, D, D)) and drd.supports((2, 4, 2 * D, D))
    assert not drd.supports((2, 4, 64, D)) and not drd.supports((2, 4, D, 96)) and not drd.supports((4, D, D))
    state, (q, k, v, g, beta) = _chain(2, 4, seed=0)
    with pytest.raises(ValueError, match="lanes"):
        drd.delta_rule_decode(state[..., :96], q[0], k[0], v[0, ..., :96], g[0], beta[0], interpret=True)


def test_a_step_with_the_state_donated_writes_it_in_place():
    state, xs = _chain(2, 4, seed=1)
    step = jax.jit(lambda s, *x: drd.delta_rule_decode(s, *x, interpret=True), donate_argnums=0)
    at = state.unsafe_buffer_pointer()
    out, new = step(state, *(x[0] for x in xs))
    assert state.is_deleted() and new.unsafe_buffer_pointer() == at
    assert out.shape == (2, 4, D) and np.all(np.isfinite(new))
