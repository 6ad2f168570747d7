"""The `lfm2_moe` trunk (`sheeprl_tpu/models/lfm2.py`) against the plain reference the
benchmark owns (`perfbench/reference/lfm2_moe.py`), at small widths on the CPU: each
block, full against step by step through its state, the expert layer and its four
shares, loss and gradients."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import lfm2, lm_layers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_reference():
    path = os.path.join(ROOT, "perfbench", "reference", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()
T = 12


def sizes(experts_held=(2, 4), layer_types=("conv", "full_attention", "conv"), num_dense_layers=1):
    """(the program's spec, the reference's `model` block) of one small model."""
    spec = lfm2.LFM2Spec(
        vocab_size=50, hidden_size=32, intermediate_size=48, moe_intermediate_size=24, num_attention_heads=4,
        num_key_value_heads=2, layer_types=tuple(layer_types), num_dense_layers=num_dense_layers, num_experts=8,
        num_experts_per_tok=2, experts_held=tuple(experts_held), max_seq_len=T)
    m = dict(
        vocab_size=50, hidden_size=32, intermediate_size=48, moe_intermediate_size=24, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, layer_types=list(layer_types), num_dense_layers=num_dense_layers,
        num_experts_routed=8, num_experts_per_tok=2, experts_held=list(experts_held), conv_L_cache=3, norm_eps=1e-5,
        rope_theta=1e6, vf_coef=1.0)
    return spec, m


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "perfbench", "reference", "lfm2_moe.py")) as fh:
        source = fh.read()
    assert "import sheeprl_tpu" not in source and "from sheeprl_tpu" not in source and "pallas" not in source


def test_weights_have_the_references_layout_and_values():
    spec, m = sizes()
    mine, theirs = lfm2.init_params(spec, jax.random.PRNGKey(5)), ref.init_params(m, 5)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
        close(a, b, 0)
    assert lfm2.parameter_count(spec) == sum(x.size for x in jax.tree_util.tree_leaves(theirs))


@pytest.mark.parametrize("block", ["short_conv", "attention", "dense_ffn", "expert_layer"])
def test_each_block_agrees_with_the_reference(block):
    spec, m = sizes()
    params = ref.init_params(m, 1)
    u = jax.random.normal(jax.random.PRNGKey(2), (3, T, spec.hidden_size))  # 36 tokens: the dense form
    if block == "short_conv":
        close(lfm2.short_conv(params["layer_0"]["op"], u), ref.short_conv(params["layer_0"]["op"], u))
    elif block == "attention":
        close(lfm2.attention(params["layer_1"]["op"], u, spec), ref.attention(params["layer_1"]["op"], u, m))
    elif block == "dense_ffn":
        p = params["layer_0"]["ffn"]
        close(lfm2.swiglu(p, u), ref.swiglu(p["w1"], p["w3"], p["w2"], u))
    else:
        p = params["layer_1"]["ffn"]
        y, ids, counters = lfm2.expert_layer(p, u.reshape(-1, spec.hidden_size), spec)
        expected, info = ref.expert_layer(p, u, m)
        close(y.reshape(u.shape), expected)
        assert np.array_equal(np.sort(ids, -1), np.sort(np.asarray(info["own"]).reshape(ids.shape), -1))
        held = (np.asarray(ids) >= 2) & (np.asarray(ids) < 6)
        assert counters["pairs_held"] == held.sum() and counters["pairs_dropped"] == 0
        assert counters["max_load"] >= 1.0


@pytest.mark.parametrize("tokens", [16, 128, 129, 400])
def test_the_expert_layers_two_forms_agree_with_the_reference(tokens, monkeypatch):
    """Few tokens go through every held expert, many through one sort and grouped products
    (`lfm2.DENSE_TOKENS`): both are the reference's layer, with every pair computed."""
    spec, m = sizes()
    p = ref.init_params(m, 21)["layer_1"]["ffn"]
    u = jax.random.normal(jax.random.PRNGKey(22), (tokens, spec.hidden_size))
    y, ids, counters = jax.jit(lambda p, u: lfm2.expert_layer(p, u, spec))(p, u)
    close(y, ref.expert_layer(p, u[None], m)[0][0])
    assert counters["pairs_dropped"] == 0 and counters["pairs_held"] == ((ids >= 2) & (ids < 6)).sum()
    grads = jax.grad(lambda p, u: jnp.sum(jnp.sin(lfm2.expert_layer(p, u, spec)[0])), argnums=(0, 1))(p, u)
    expected = jax.grad(lambda p, u: jnp.sum(jnp.sin(ref.expert_layer(p, u[None], m)[0])), argnums=(0, 1))(p, u)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(expected)):
        close(a, b, 1e-4)


def test_short_conv_full_agrees_with_step_by_step_through_its_state():
    spec, m = sizes()
    p = ref.init_params(m, 1)["layer_0"]["op"]
    u = jax.random.normal(jax.random.PRNGKey(3), (2, T, spec.hidden_size))
    state, steps = jnp.zeros((2, spec.conv_L_cache, spec.hidden_size)), []
    for t in range(T):
        y, state = lfm2.short_conv_step(p, state, u[:, t])
        steps.append(y)
    close(jnp.stack(steps, axis=1), lfm2.short_conv(p, u))


def test_attention_full_agrees_with_step_by_step_through_its_cache():
    spec, m = sizes()
    p = ref.init_params(m, 1)["layer_1"]["op"]
    u = jax.random.normal(jax.random.PRNGKey(4), (2, T, spec.hidden_size))
    shape = (2, T, spec.num_key_value_heads, spec.head_dim)
    cache, steps = (jnp.zeros(shape), jnp.zeros(shape)), []
    for t in range(T):
        y, cache = lfm2.attention_step(p, cache, u[:, t], jnp.int32(t), spec)
        steps.append(y)
    close(jnp.stack(steps, axis=1), lfm2.attention(p, u, spec))


def test_prefill_then_decode_logits_agree_with_the_references_full_forward():
    spec, m = sizes()
    params = ref.init_params(m, 7)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (3, T), 0, spec.vocab_size)
    logits, values, own, _ = ref.forward(params, m, tokens)
    carry, step = lfm2.init_carry(spec, 3), jax.jit(lambda p, c, t: lfm2.step(p, spec, c, t))
    for t in range(T):  # every token through the two kinds of state, prompt and response alike
        step_logits, step_values, carry, ids, counters = step(params, carry, tokens[:, t])
        close(step_logits, logits[:, t])
        close(step_values, values[:, t])
        assert np.array_equal(np.sort(ids, -1), np.sort(np.asarray(own[:, t]), -1))
    full_logits, full_values, full_ids, _ = lfm2.forward(params, spec, tokens)
    close(full_logits, logits)
    close(full_values, values)
    assert int(carry["t"]) == T and full_ids.shape == (3, T, spec.num_moe_layers, spec.num_experts_per_tok)


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5, 6-7 of 8 (the four chips of the deployment, in small): what
    each share computes for its own experts adds up to the whole layer of the uncut
    reference, which holds all 8."""
    spec_all, m_all = sizes(experts_held=(0, 8))
    whole = ref.init_params(m_all, 11)["layer_1"]["ffn"]
    u = jax.random.normal(jax.random.PRNGKey(12), (2 * T, spec_all.hidden_size))
    expected, _ = ref.expert_layer(whole, u[None], m_all)
    total, pairs = 0.0, 0.0
    for first in (0, 2, 4, 6):
        spec, m = sizes(experts_held=(first, 2))
        share = {**whole, **{k: whole[k][first:first + 2] for k in ("w1", "w3", "w2")}}
        y, _, counters = lfm2.expert_layer(share, u, spec)
        close(y, ref.expert_layer(share, u[None], m)[0][0])
        total, pairs = total + y, pairs + counters["pairs_held"]
    close(total, expected[0])
    assert pairs == 2 * T * spec_all.num_experts_per_tok  # every (token, expert) pair lands on exactly one share


def test_loss_and_gradients_agree_with_jax_grad_of_the_reference():
    spec, m = sizes()
    params = ref.init_params(m, 13)
    keys = jax.random.split(jax.random.PRNGKey(14), 5)
    batch = {
        "tokens": jax.random.randint(keys[0], (4, T), 0, spec.vocab_size),
        "actions": jax.random.randint(keys[1], (4, T), 0, spec.vocab_size),
        "logprobs": -3.0 + 0.1 * jax.random.normal(keys[2], (4, T)),
        "advantages": jax.random.normal(keys[3], (4, T)),
        "returns": jax.random.normal(keys[4], (4, T)),
        "mask": (jnp.arange(T)[None] >= jnp.array([3, 5, 4, 6])[:, None]).astype(jnp.float32),
    }

    def program_loss(p):
        from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss

        logits, values, _, _ = lfm2.forward(p, spec, batch["tokens"])
        logp_all = jax.nn.log_softmax(logits)
        logp = jnp.take_along_axis(logp_all, batch["actions"][..., None], -1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, -1)
        mean = lambda x: jnp.sum(x * batch["mask"]) / batch["mask"].sum()  # noqa: E731
        return (mean(policy_loss(logp, batch["logprobs"], batch["advantages"], 0.2, "none"))
                + mean(value_loss(values, values, batch["returns"], 0.2, False, "none"))
                + 0.01 * mean(entropy_loss(entropy, "none")))

    loss, grads = jax.value_and_grad(program_loss)(params)
    ids = lfm2.forward(params, spec, batch["tokens"])[2]
    step = jax.jit(functools.partial(ref.block_grad, m))
    ref_grads, parts, own, _ = ref.minibatch_grad(step, params, batch, ids, 0.2, 0.01, block=2)
    close(loss, parts[0] + parts[1] + 0.01 * parts[2])
    assert np.array_equal(np.sort(ids, -1), np.sort(own, -1))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6 + 1e-4 * float(jnp.abs(b).max()),
                                   err_msg=jax.tree_util.keystr(path))
    assert not np.any(np.asarray(grads["layer_1"]["ffn"]["bias"]))  # the expert bias is a buffer


@pytest.mark.parametrize("backend, sizes_mkn, path", [("cpu", (256, 128, 128), "ragged_dot"),
                                                     ("tpu", (256, 128, 128), "kernel"),
                                                     ("tpu", (256, 128, 100), "ragged_dot, said")])
def test_grouped_products_choose_their_path_from_the_default_backend(backend, sizes_mkn, path, monkeypatch, recwarn):
    """The Pallas kernel where the TPU is the default backend and it can tile the sizes; a
    TPU run that falls to `ragged_dot` (a dense product per group there) warns, once."""
    m, k, n = sizes_mkn
    took = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(lm_layers, "_gmm_tpu", lambda rows, w, sizes, passes: took.append("kernel") or jax.lax.ragged_dot(rows, w, sizes))
    lfm2._warn_dense_groups.cache_clear()
    rows, weights = jnp.ones((m, k)), jnp.ones((2, k, n))
    group_sizes, valid = jnp.array([100, 60], jnp.int32), jnp.arange(m) < 160
    for _ in range(2):
        out = lfm2.grouped_matmul(rows, weights, group_sizes, valid)
    assert out.shape == (m, n) and float(out[159, 0]) == k and float(out[160, 0]) == 0.0
    said = [w for w in recwarn.list if "ragged_dot" in str(w.message)]
    assert (took == ["kernel"] * 2) == (path == "kernel") and len(said) == (1 if path.endswith("said") else 0)


def test_a_spec_refuses_a_share_outside_the_routed_experts():
    with pytest.raises(ValueError, match="experts_held"):
        sizes(experts_held=(6, 4))
