"""The `deepseek_v3` trunk (`sheeprl_tpu/models/deepseek_v3.py`) against the plain reference the
benchmark owns (`perfbench/reference/deepseek_v3.py`), at small widths on the CPU: each block,
the absorbed step form token by token through the latent cache against the expanded
whole-sequence form, prefill then decode against the reference's full forward, the cache's
shape, the eight shares of an expert layer, the routed scale and the bias, the bounded dispatch
at every imbalance, loss and gradients."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import deepseek_v3, lm_layers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT, "perfbench", "reference", "deepseek_v3.py")


def _load_reference():
    spec = importlib.util.spec_from_file_location("deepseek_v3_reference", REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()
T = 20
SCALE = 2.446


def sizes(experts_held=(4, 4), num_experts=16, max_seq_len=T, scale=SCALE, shared=2):
    """(the program's spec, the reference's `model` block) of one small model: a dense layer, two expert layers."""
    both = dict(
        vocab_size=50, hidden_size=32, intermediate_size=48, moe_intermediate_size=24, num_attention_heads=4,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6, kv_lora_rank=16, num_hidden_layers=3, first_k_dense_replace=1,
        num_experts_per_tok=4, n_shared_experts=shared, routed_scaling_factor=scale, norm_eps=1e-5, rope_theta=5e4)
    spec = deepseek_v3.DeepseekV3Spec(**both, num_experts=num_experts, experts_held=tuple(experts_held), max_seq_len=max_seq_len)
    m = dict(**both, num_experts_routed=num_experts, experts_held=list(experts_held), vf_coef=1.0)
    return spec, m


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)


def test_the_reference_imports_nothing_of_the_program_and_keeps_to_the_expanded_form():
    with open(REFERENCE) as fh:
        source = fh.read()
    assert "import sheeprl_tpu" not in source and "from sheeprl_tpu" not in source and "pallas" not in source
    assert "dynamic_update_slice" not in source and "cache" not in source.split('"""', 2)[2]  # no cache, no step form
    assert 'precision' not in source.split('"""', 2)[2]  # callers set `highest`; the file pins nothing lower


def test_weights_have_the_references_layout_and_values():
    spec, m = sizes()
    mine, theirs = deepseek_v3.init_params(spec, jax.random.PRNGKey(5)), ref.init_params(m, 5)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
        close(a, b, 0)
    assert deepseek_v3.parameter_count(spec) == sum(x.size for x in jax.tree_util.tree_leaves(theirs))
    assert "ffn" in mine["layer_0"] and "router" not in mine["layer_0"]["ffn"]  # the leading layer is dense
    assert mine["layer_1"]["ffn"]["shared"]["w1"].shape == (32, 2 * 24) and "shared_gate" not in mine["layer_1"]["ffn"]
    assert np.all(np.asarray(mine["layer_1"]["op"]["kv_norm"]) == 1.0) and mine["layer_1"]["op"]["kv_norm"].shape == (16,)


@pytest.mark.parametrize("block", ["mla", "expert_layer", "dense_layer"])
def test_each_block_agrees_with_the_reference(block):
    spec, m = sizes()
    params = ref.init_params(m, 1)
    u = jax.random.normal(jax.random.PRNGKey(2), (3, T, spec.hidden_size))  # 60 tokens: the dense form
    if block == "mla":
        p = dict(params["layer_1"]["op"], kv_norm=1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(3), (16,)))
        close(deepseek_v3.mla(p, u, spec), ref.latent_attention(p, u, m))
    elif block == "expert_layer":
        p = params["layer_1"]["ffn"]
        y, ids, counters = deepseek_v3.expert_layer(p, u.reshape(-1, spec.hidden_size), spec)
        expected, info = ref.expert_layer(p, u, m)
        close(y.reshape(u.shape), expected)
        assert np.array_equal(np.sort(ids, -1), np.sort(np.asarray(info["own"]).reshape(ids.shape), -1))
        held = (np.asarray(ids) >= 4) & (np.asarray(ids) < 8)
        assert counters["pairs_held"] == held.sum() and counters["pairs_dropped"] == 0
    else:
        p = params["layer_0"]["ffn"]
        y, ids, counters = deepseek_v3._ffn(p, u, "dense", spec)
        close(y, ref.swiglu(p["w1"], p["w3"], p["w2"], u))
        assert ids is None and counters is None


@pytest.mark.parametrize("cache_len, block, kernel", [(T, 128, False), (T + 12, 128, False), (T + 12, 8, False), (T + 10, 7, False),
                                                    (128, 128, True)],
                         ids=["fills_the_cache", "does_not_fill_it", "four_blocks_of_eight", "block_of_six", "the_kernel"])
def test_the_absorbed_step_form_through_the_latent_cache_is_the_expanded_form(cache_len, block, kernel, monkeypatch):
    """Token by token through the latent cache, never making a key or a value of a cached
    position, against the whole-sequence form that makes them all: values to rounding; with the
    rows past the position holding NaN, which a step must never read as what they hold; and a
    block at a time (the running softmax over as many blocks as the position asks for), or
    through the latent-cache kernel (`ops/latent_decode.py`, here in Pallas' interpreter)."""
    monkeypatch.setattr(deepseek_v3, "CACHE_BLOCK", block)
    if kernel:
        monkeypatch.setattr(deepseek_v3, "decode_kernel_passes", lambda shape: lm_layers.matmul_passes())
    assert deepseek_v3.cache_block(cache_len) == {(T, 128): 20, (T + 12, 128): 32, (T + 12, 8): 8, (T + 10, 7): 6,
                                                  (128, 128): 128}[(cache_len, block)]
    spec, m = sizes(max_seq_len=cache_len)
    p = dict(ref.init_params(m, 1)["layer_1"]["op"], kv_norm=1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(3), (16,)))
    u = jax.random.normal(jax.random.PRNGKey(4), (2, T, spec.hidden_size))
    cache, steps = deepseek_v3.init_carry(spec, 2)["layer_1"], []
    assert cache.shape == (2, cache_len, 16 + 4) and not np.any(np.asarray(cache))
    cache = jnp.full_like(cache, jnp.nan)  # whatever the buffer held before: a step reads written rows alone
    step = jax.jit(lambda cache, u, t: deepseek_v3.mla_step(p, cache, u, t, spec))
    for t in range(T):
        y, cache = step(cache, u[:, t], jnp.int32(t))
        steps.append(y)
    close(jnp.stack(steps, axis=1), deepseek_v3.mla(p, u, spec), 1e-5)
    assert np.all(np.isnan(np.asarray(cache[:, T:])))  # the rows past the last position were never written
    # a row is the NORMED latent and the ROTATED key, as the expanded form makes them
    _, _, c, k_pe = deepseek_v3._latent_inputs(p, u, jnp.arange(T), spec)
    close(cache[:, :T, :16], c, 1e-6)
    close(cache[:, :T, 16:], k_pe, 1e-6)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla_form", "the_kernel"])
def test_the_step_counts_the_share_of_layers_whose_decode_took_the_kernel(kernel, monkeypatch):
    """`mla/decode_kernel_share`, fixed when the step is traced: 0 where every layer takes the
    XLA form (off the chip), 1 where every layer's cache takes the kernel."""
    if kernel:
        monkeypatch.setattr(deepseek_v3, "decode_kernel_passes", lambda shape: lm_layers.matmul_passes())
    spec, _ = sizes(max_seq_len=128)
    params = deepseek_v3.init_params(spec, jax.random.PRNGKey(0))
    step = jax.jit(lambda p, c, t: deepseek_v3.step(p, spec, c, t))
    *_, counters = step(params, deepseek_v3.init_carry(spec, 2), jnp.zeros((2,), jnp.int32))
    assert counters["mla/decode_kernel_share"] == float(kernel)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, at any depth."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list)) else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_carry_is_576_floats_a_token_a_layer_and_a_step_makes_no_key_or_value_of_a_cached_position():
    """At the published head sizes (16 heads, 128 + 64, 128, rank 512) and a short cache: the
    carry holds `[B, S, 576]` a layer and nothing per head, and no array of a decode step has
    both the cache's positions and the heads' key or value channels."""
    seq, batch, heads = 24, 2, 16
    spec = deepseek_v3.DeepseekV3Spec(
        vocab_size=50, hidden_size=64, intermediate_size=48, moe_intermediate_size=24, num_attention_heads=heads,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512, num_hidden_layers=2, first_k_dense_replace=1,
        num_experts=16, num_experts_per_tok=4, experts_held=(0, 8), n_shared_experts=2, routed_scaling_factor=SCALE, max_seq_len=seq)
    carry = deepseek_v3.init_carry(spec, batch)
    assert {name: leaf.shape for name, leaf in carry.items()} == {"t": (), "layer_0": (batch, seq, 576), "layer_1": (batch, seq, 576)}
    assert spec.latent_width == 576 and spec.cache_bytes_per_sequence == 2 * seq * 576 * 4
    params = jax.eval_shape(lambda: deepseek_v3.init_params(spec, jax.random.PRNGKey(0)))
    jaxpr = jax.make_jaxpr(lambda p, c, t: deepseek_v3.step(p, spec, c, t))(params, carry, jnp.zeros((batch,), jnp.int32))
    per_head = {128, 192, 256, 128 + 64 + 128}  # a head's key, query, key and value together
    for eqn in _equations(jaxpr.jaxpr):
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if seq in shape and heads in shape:  # over the cache's positions and the heads: scores and weights alone
                assert not per_head & set(shape), (eqn.primitive.name, shape)
            assert not (seq in shape and heads * 128 in shape or seq in shape and heads * 256 in shape), (eqn.primitive.name, shape)


def test_prefill_then_decode_logits_agree_with_the_references_full_forward():
    spec, m = sizes()
    params = ref.init_params(m, 7)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (3, T), 0, spec.vocab_size)
    logits, values, own, _ = ref.forward(params, m, tokens)
    carry, step = deepseek_v3.init_carry(spec, 3), jax.jit(lambda p, c, t: deepseek_v3.step(p, spec, c, t))
    for t in range(T):  # every token through the latent caches, prompt and response alike
        step_logits, step_values, carry, ids, counters = step(params, carry, tokens[:, t])
        close(step_logits, logits[:, t])
        close(step_values, values[:, t])
        assert np.array_equal(np.sort(ids, -1), np.sort(np.asarray(own[:, t]), -1))
        assert counters["pairs_dropped"] == 0
    assert spec.cache_bytes_per_sequence == sum(carry[f"layer_{i}"].nbytes for i in range(3)) // 3
    full_logits, full_values, full_ids, _ = jax.jit(lambda p, t: deepseek_v3.forward(p, spec, t))(params, tokens)
    close(full_logits, logits)
    close(full_values, values)
    assert int(carry["t"]) == T and full_ids.shape == (3, T, 2, spec.num_experts_per_tok)  # two expert layers


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Experts 4i to 4i + 3 of 32 (the eight chips of the deployment, in small): what each share
    computes for its own experts, with the shared experts (which every chip computes alike)
    counted once, adds up to the whole layer of the uncut reference, which holds all 32."""
    spec_all, m_all = sizes(experts_held=(0, 32), num_experts=32)
    whole = ref.init_params(m_all, 11)["layer_1"]["ffn"]
    u = jax.random.normal(jax.random.PRNGKey(12), (7 * T, spec_all.hidden_size))  # 140 tokens: the grouped form
    expected, _ = ref.expert_layer(whole, u[None], m_all)
    shared = lm_layers.swiglu(whole["shared"], u)
    total, pairs = 0.0, 0.0
    for first in range(0, 32, 4):
        spec, m = sizes(experts_held=(first, 4), num_experts=32)
        share = {**whole, **{k: whole[k][first:first + 4] for k in ("w1", "w3", "w2")}}
        y, _, counters = deepseek_v3.expert_layer(share, u, spec)
        close(y, ref.expert_layer(share, u[None], m)[0][0])
        total, pairs = total + (y - shared), pairs + counters["pairs_held"]
    close(total + shared, expected[0], 5e-5)
    assert pairs == 7 * T * spec_all.num_experts_per_tok  # every (token, expert) pair lands on exactly one share


def test_the_scale_and_the_bias_reach_the_weights_and_the_choice_as_the_equations_say():
    spec, m = sizes()
    p = ref.init_params(m, 3)["layer_1"]["ffn"]
    p["bias"] = p["bias"].at[5].set(3.0).at[2].set(-3.0)  # expert 5 is always chosen, expert 2 never
    u = jax.random.normal(jax.random.PRNGKey(4), (40, spec.hidden_size))
    ids, w = deepseek_v3.route(p, u, spec)
    s = jax.nn.sigmoid(u @ p["router"])
    assert np.all(np.any(np.asarray(ids) == 5, axis=-1)) and not np.any(np.asarray(ids) == 2)
    assert np.array_equal(np.sort(ids, -1), np.sort(jax.lax.top_k(s + p["bias"], 4)[1], -1))
    chosen = jnp.take_along_axis(s, ids, axis=-1)  # the weights are the scores WITHOUT the bias, over their sum, times the scale
    close(w, SCALE * chosen / chosen.sum(-1, keepdims=True), 1e-6)
    close(w.sum(-1), jnp.full((40,), SCALE), 1e-5)
    unscaled, _ = sizes(scale=1.0)
    close(deepseek_v3.route(p, u, unscaled)[1] * SCALE, w, 1e-6)
    # the bias gets no gradient, the router does
    grads = jax.grad(lambda p: jnp.sum(jnp.sin(deepseek_v3.expert_layer(p, u, spec)[0])))(p)
    assert not np.any(np.asarray(grads["bias"])) and np.any(np.asarray(grads["router"]))
    # the shared experts are one ungated SwiGLU: without them the layer is the routed part alone
    bare, _ = sizes(shared=0)
    routed = deepseek_v3.expert_layer({k: v for k, v in p.items() if k != "shared"}, u, bare)[0]
    close(deepseek_v3.expert_layer(p, u, spec)[0] - routed, lm_layers.swiglu(p["shared"], u), 1e-5)


def test_a_spec_that_states_no_scale_and_no_gate_gets_the_layer_it_had():
    """The two properties this trunk added to the shared layer leave the other trunks' layer as it
    was: LFM2's and Qwen3-Next's specs state neither, which reads as a scale of 1 (no multiply in
    the router's program) and a gated shared expert."""
    from sheeprl_tpu.models import lfm2, qwen3_next

    for other in (lfm2.LFM2Spec, qwen3_next.Qwen3NextSpec):
        assert not hasattr(other, "routed_scaling_factor") and not hasattr(other, "shared_expert_gate")
    spec, m = sizes()
    unscaled, _ = sizes(scale=1.0)
    p = ref.init_params(m, 3)["layer_1"]["ffn"]
    u = jnp.ones((8, spec.hidden_size))
    count = lambda spec: str(jax.make_jaxpr(lambda p, u: lm_layers.route(p, u, spec))(p, u)).count(" mul ")  # noqa: E731
    assert count(spec) == count(unscaled) + 1


def _routed(kind: str, tokens: int, spec):
    """A router matrix that sends every token to held experts (`all`), none (`none`), every
    token to the same held experts (`one_group`), or wherever the seed says (`seeded`)."""
    h, e = spec.hidden_size, spec.num_experts
    e0, held = spec.experts_held
    router = 0.02 * jax.random.normal(jax.random.PRNGKey(31), (h, e))
    u = jax.random.normal(jax.random.PRNGKey(32), (tokens, h))
    lift = jnp.zeros((e,))
    if kind == "all":
        lift = lift.at[e0:e0 + held].set(50.0)
    elif kind == "none":
        lift = lift.at[e0:e0 + held].set(-50.0)
    elif kind == "one_group":
        lift = lift.at[e0].set(80.0).at[e0 + 1:e0 + spec.num_experts_per_tok].set(50.0)
    u = u.at[:, 0].set(1.0)  # a constant channel carries the lift, so that it reaches every token alike
    return router.at[0].set(lift), u


@pytest.mark.parametrize("kind", ["all", "none", "one_group", "seeded"])
def test_no_pair_is_dropped_at_any_imbalance(kind):
    """The bounded dispatch: buffers of `dispatch_rows` rows (here 2 x 160 x 4 x 4/16 = 384 of
    the 640 pairs), and every pair on a held expert computed whatever the routing, in further
    rounds where more land than a buffer holds: the reference's layer, values and gradients.
    A sigmoid's score saturates, so the lift is in the scores and the bias both."""
    tokens = 160
    spec, m = sizes()
    p = ref.init_params(m, 21)["layer_1"]["ffn"]
    p["router"], u = _routed(kind, tokens, spec)
    p["bias"] = 0.1 * p["router"][0]  # the choice is by `s + b`: the lifted experts lead by more than a sigmoid can
    bound = lm_layers.dispatch_rows(spec, tokens)
    assert bound == 384 and bound < tokens * spec.num_experts_per_tok
    y, ids, counters = jax.jit(lambda p, u: deepseek_v3.expert_layer(p, u, spec))(p, u)
    held = ((ids >= 4) & (ids < 8)).sum()
    assert held == {"all": 640, "none": 0, "one_group": 640}.get(kind, held)
    assert counters["pairs_dropped"] == 0 and counters["pairs_held"] == held
    rounds = max(1, -(-int(held) // bound))
    assert counters["dispatch_fill"] == pytest.approx(int(held) / (rounds * bound))
    close(y, ref.expert_layer(p, u[None], m)[0][0], 5e-5)
    grads = jax.jit(jax.grad(lambda p, u: jnp.sum(jnp.sin(deepseek_v3.expert_layer(p, u, spec)[0])), argnums=(0, 1)))(p, u)
    expected = jax.jit(jax.grad(lambda p, u: jnp.sum(jnp.sin(ref.expert_layer(p, u[None], m)[0])), argnums=(0, 1)))(p, u)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(expected)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4 * max(1.0, float(jnp.abs(b).max())),
                                   err_msg=jax.tree_util.keystr(path))


def test_loss_and_gradients_agree_with_jax_grad_of_the_reference():
    spec, m = sizes()
    params = ref.init_params(m, 9)
    keys = jax.random.split(jax.random.PRNGKey(10), 4)
    batch = {"tokens": jax.random.randint(keys[0], (8, T), 0, spec.vocab_size),  # 160 tokens: the grouped form
             "actions": jax.random.randint(keys[1], (8, T), 0, spec.vocab_size),
             "logprobs": -3.0 + 0.1 * jax.random.normal(keys[2], (8, T)), "advantages": jax.random.normal(keys[3], (8, T)),
             "returns": jnp.ones((8, T)), "mask": (jnp.arange(T) >= 5).astype(jnp.float32) * jnp.ones((8, 1))}

    def program_loss(p):
        logits, values, ids, _ = deepseek_v3.forward(p, spec, batch["tokens"])
        logp_all = jax.nn.log_softmax(logits, axis=-1)
        logp = jnp.take_along_axis(logp_all, batch["actions"][..., None], axis=-1)[..., 0]
        ratio = jnp.exp(logp - batch["logprobs"])
        adv, mask = batch["advantages"], batch["mask"]
        pg = jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 0.8, 1.2))
        return (jnp.sum(pg * mask) + jnp.sum(jnp.square(values - batch["returns"]) * mask)) / mask.sum()

    def reference_loss(p):
        terms, _ = ref.loss_terms(p, m, batch, None, 0.2)
        return (terms[0] + terms[1]) / batch["mask"].sum()

    mine, theirs = jax.jit(jax.value_and_grad(program_loss))(params), jax.jit(jax.value_and_grad(reference_loss))(params)
    close(mine[0], theirs[0], 1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(mine[1])[0], jax.tree_util.tree_leaves(theirs[1])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * max(1.0, float(jnp.abs(b).max()) * 10), err_msg=jax.tree_util.keystr(path))
    assert np.any(np.asarray(mine[1]["layer_1"]["op"]["kv_norm"])) and np.any(np.asarray(mine[1]["layer_2"]["ffn"]["shared"]["w2"]))
    assert not np.any(np.asarray(mine[1]["layer_1"]["ffn"]["bias"]))
