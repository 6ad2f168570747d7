"""The `qwen3_next` trunk (`sheeprl_tpu/models/qwen3_next.py`) against the plain reference
the benchmark owns (`perfbench/reference/qwen3_next.py`), at small widths on the CPU: each
block, the chunked delta rule against the recurrence (values and gradients), full against
step by step through the three kinds of state, the expert layer's sixteen shares and its
bounded dispatch at every imbalance, loss and gradients."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import lm_layers, qwen3_next

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT, "perfbench", "reference", "qwen3_next.py")


def _load_reference():
    spec = importlib.util.spec_from_file_location("qwen3_next_reference", REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()
T = 20  # no multiple of the chunk of 8: the last chunk is padded
LAYERS = ("linear_attention", "full_attention")


def sizes(experts_held=(4, 4), layer_types=LAYERS, num_experts=16):
    """(the program's spec, the reference's `model` block) of one small model."""
    shared = dict(
        vocab_size=50, hidden_size=32, moe_intermediate_size=24, shared_expert_intermediate_size=16,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel_dim=4, num_experts_per_tok=4,
        norm_eps=1e-6, rope_theta=1e7)
    spec = qwen3_next.Qwen3NextSpec(
        **shared, layer_types=tuple(layer_types), num_experts=num_experts, experts_held=tuple(experts_held),
        partial_rotary_factor=0.25, max_seq_len=T, chunk_size=8)
    m = dict(**shared, layer_types=list(layer_types), num_experts_routed=num_experts, experts_held=list(experts_held),
             rotary_dim=4, vf_coef=1.0)
    return spec, m


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)


def test_the_reference_imports_nothing_of_the_program_and_keeps_to_the_recurrence():
    with open(REFERENCE) as fh:
        source = fh.read()
    assert "import sheeprl_tpu" not in source and "from sheeprl_tpu" not in source and "pallas" not in source
    assert "solve_triangular" not in source and "lax.scan(jax.checkpoint(one_token)" in source  # a token at a time, no chunks


def test_weights_have_the_references_layout_and_values():
    spec, m = sizes()
    mine, theirs = qwen3_next.init_params(spec, jax.random.PRNGKey(5)), ref.init_params(m, 5)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
        close(a, b, 0)
    assert qwen3_next.parameter_count(spec) == sum(x.size for x in jax.tree_util.tree_leaves(theirs))
    decay = np.exp(-np.exp(np.asarray(mine["layer_0"]["op"]["A_log"])) * np.log1p(np.exp(np.asarray(mine["layer_0"]["op"]["dt_bias"]))))
    assert 0.15 < decay.min() and decay.max() < 1.0  # a step keeps a fifth to nearly all of the state


@pytest.mark.parametrize("block", ["linear_attention", "attention", "expert_layer"])
def test_each_block_agrees_with_the_reference(block):
    spec, m = sizes()
    params = ref.init_params(m, 1)
    u = jax.random.normal(jax.random.PRNGKey(2), (3, T, spec.hidden_size))  # 60 tokens: the dense form
    if block == "linear_attention":
        close(qwen3_next.linear_attention(params["layer_0"]["op"], u, spec), ref.linear_attention(params["layer_0"]["op"], u, m))
    elif block == "attention":
        close(qwen3_next.attention(params["layer_1"]["op"], u, spec), ref.attention(params["layer_1"]["op"], u, m))
    else:
        p = params["layer_1"]["ffn"]
        y, ids, counters = qwen3_next.expert_layer(p, u.reshape(-1, spec.hidden_size), spec)
        expected, info = ref.expert_layer(p, u, m)
        close(y.reshape(u.shape), expected)
        assert np.array_equal(np.sort(ids, -1), np.sort(np.asarray(info["own"]).reshape(ids.shape), -1))
        held = (np.asarray(ids) >= 4) & (np.asarray(ids) < 8)
        assert counters["pairs_held"] == held.sum() and counters["pairs_dropped"] == 0


def _delta_inputs(key, t=T, heads=3, dk=8, dv=6):
    keys = jax.random.split(key, 5)
    q = qwen3_next.l2_norm(jax.random.normal(keys[0], (2, t, heads, dk))) / np.sqrt(dk)
    k = qwen3_next.l2_norm(jax.random.normal(keys[1], (2, t, heads, dk)))
    v = jax.random.normal(keys[2], (2, t, heads, dv))
    g = -jax.random.uniform(keys[3], (2, t, heads), minval=0.001, maxval=1.6)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, t, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("t, chunk", [(T, 8), (16, 8), (5, 8), (T, 64), (76, 8)],
                         ids=["ragged", "whole", "short", "one_chunk", "more_chunks_than_a_trip"])
def test_the_chunked_delta_rule_is_the_recurrence_in_values_and_gradients(t, chunk):
    inputs = _delta_inputs(jax.random.PRNGKey(3), t=t)
    cotangent = jax.random.normal(jax.random.PRNGKey(4), (2, t, 3, 6))
    chunked = lambda *x: jnp.sum(qwen3_next.chunk_delta_rule(*x, chunk) * cotangent)  # noqa: E731
    recurrent = lambda *x: jnp.sum(ref.delta_rule(*x) * cotangent)  # noqa: E731
    close(qwen3_next.chunk_delta_rule(*inputs, chunk), ref.delta_rule(*inputs))
    mine = jax.jit(jax.grad(chunked, argnums=(0, 1, 2, 3, 4)))(*inputs)
    theirs = jax.jit(jax.grad(recurrent, argnums=(0, 1, 2, 3, 4)))(*inputs)
    for a, b in zip(mine, theirs):
        assert np.all(np.isfinite(a))
        close(a, b, 1e-4)


def test_the_chunked_rule_stays_finite_where_a_chunk_forgets_everything():
    """Decays that take a chunk's state to nothing: above the diagonal the exponent would overflow."""
    q, k, v, g, beta = _delta_inputs(jax.random.PRNGKey(5))
    g = g * 60.0
    loss = lambda *x: jnp.sum(jnp.square(qwen3_next.chunk_delta_rule(*x, 8)))  # noqa: E731
    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert all(np.all(np.isfinite(x)) for x in grads)
    close(qwen3_next.chunk_delta_rule(q, k, v, g, beta, 8), ref.delta_rule(q, k, v, g, beta))


@pytest.mark.parametrize("chunk", [8, 64, 12], ids=["chunk8", "chunk64", "no_power_of_two"])
def test_the_inverse_by_products_is_the_triangular_solve_in_values_and_gradients(chunk):
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    a = jnp.tril(jax.random.normal(keys[0], (2, 3, chunk, chunk)) * 0.3, -1)
    rhs = jax.random.normal(keys[1], (2, 3, chunk, 5))
    cotangent = jax.random.normal(keys[2], rhs.shape)
    eye = jnp.eye(chunk)

    def substituted(a, rhs):  # `a` enters through its strictly lower triangle alone, as the rule's does
        return jax.scipy.linalg.solve_triangular(eye + jnp.tril(a, -1), rhs, lower=True, unit_diagonal=True)

    close(qwen3_next.unit_lower_inverse(a) @ (eye + a), jnp.broadcast_to(eye, a.shape), 1e-4)
    close(qwen3_next.unit_lower_solve(a, rhs), substituted(a, rhs), 1e-4)
    mine = jax.grad(lambda *x: jnp.sum(qwen3_next.unit_lower_solve(*x) * cotangent), argnums=(0, 1))(a, rhs)
    theirs = jax.grad(lambda *x: jnp.sum(substituted(*x) * cotangent), argnums=(0, 1))(a, rhs)
    for x, y in zip(mine, theirs):
        close(x, y, 1e-4 * float(jnp.max(jnp.abs(y))))
    assert not np.any(np.triu(np.asarray(mine[0])))  # nothing flows to what `a` is not


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, at any depth."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list)) else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("t, chunk", [(T, 8), (76, 8)], ids=["ragged", "more_chunks_than_a_trip"])
def test_the_rules_gradient_solves_nothing_and_its_loops_carry_the_state_alone(t, chunk):
    """The mechanism's counter, read from the program's text: no triangular solve in the value or
    the gradient, and each loop (the scan forward, its transpose backward) carries one array of
    ``S``'s shape: the chunks' own work is outside them."""
    inputs = _delta_inputs(jax.random.PRNGKey(7), t=t)
    loss = lambda *x: jnp.sum(jnp.square(qwen3_next.chunk_delta_rule(*x, chunk)))  # noqa: E731
    equations = list(_equations(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*inputs).jaxpr))
    names = {eqn.primitive.name for eqn in equations}
    assert "triangular_solve" not in names and "custom_linear_solve" not in names
    state = (inputs[0].shape[0], inputs[0].shape[2], inputs[0].shape[3], inputs[2].shape[3])  # [B, H, dk, dv]
    loops = [eqn for eqn in equations if eqn.primitive.name in ("scan", "while")]
    assert len(loops) == 2  # forward and backward
    for eqn in loops:
        consts, carried = eqn.params["num_consts"], eqn.params["num_carry"]
        assert [v.aval.shape for v in eqn.invars[consts:consts + carried]] == [state]
        assert eqn.params["length"] == -(-t // chunk)


def test_heads_that_forget_everything_beside_heads_that_forget_nothing_stay_finite():
    q, k, v, g, beta = _delta_inputs(jax.random.PRNGKey(8))
    g = jnp.broadcast_to(jnp.linspace(-20.0, 0.0, g.shape[-1]), g.shape)  # a head each: from exp(-20) a token to no decay
    loss = lambda *x: jnp.sum(jnp.square(qwen3_next.chunk_delta_rule(*x, 8)))  # noqa: E731
    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert all(np.all(np.isfinite(x)) for x in grads)
    close(qwen3_next.chunk_delta_rule(q, k, v, g, beta, 8), ref.delta_rule(q, k, v, g, beta))
    theirs = jax.grad(lambda *x: jnp.sum(jnp.square(ref.delta_rule(*x))), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for a, b in zip(grads, theirs):
        close(a, b, 1e-4)


def test_the_mixer_full_agrees_with_step_by_step_through_its_state():
    spec, m = sizes()
    p = ref.init_params(m, 1)["layer_0"]["op"]
    u = jax.random.normal(jax.random.PRNGKey(3), (2, T, spec.hidden_size))
    state, steps = qwen3_next.init_carry(spec, 2)["layer_0"], []
    assert state[0].shape == (2, 3, spec.conv_channels) and state[1].shape == (2, 4, 8, 8)
    for t in range(T):
        y, state = qwen3_next.linear_attention_step(p, state, u[:, t], spec)
        steps.append(y)
    close(jnp.stack(steps, axis=1), qwen3_next.linear_attention(p, u, spec))


def _tiled_sizes():
    """A small model whose matrix state the decode kernel tiles (key and value heads of 128)."""
    spec, _ = sizes(layer_types=("linear_attention", "linear_attention", "full_attention"))
    return qwen3_next.Qwen3NextSpec(**{**spec.__dict__, "linear_key_head_dim": 128, "linear_value_head_dim": 128})


def _decode(spec, params, tokens, carry):
    step = jax.jit(lambda p, c, t: qwen3_next.step(p, spec, c, t))
    outs = []
    for t in range(tokens.shape[1]):
        logits, values, carry, _, counters = step(params, carry, tokens[:, t])
        outs.append((logits, values))
    return outs, carry, counters


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_the_step_counts_the_layers_whose_rule_took_the_decode_kernel(kernel, monkeypatch):
    """`lin_attn/decode_kernel_share`, fixed when the step is traced: 0 off the TPU, where every
    layer takes the XLA form, and 1 where every layer takes the kernel (here forced, in Pallas'
    interpreter), whose logits, values and carried states are the XLA form's to rounding."""
    spec = _tiled_sizes()
    params = qwen3_next.init_params(spec, jax.random.PRNGKey(9))
    tokens = jax.random.randint(jax.random.PRNGKey(10), (2, 6), 0, spec.vocab_size)
    expected, expected_carry, counters = _decode(spec, params, tokens, qwen3_next.init_carry(spec, 2))
    assert counters["lin_attn/decode_kernel_share"] == 0.0  # off the TPU: the XLA form
    if kernel:
        monkeypatch.setattr(qwen3_next, "decode_kernel_taken", lambda shape: True)
        got, carry, counters = _decode(spec, params, tokens, qwen3_next.init_carry(spec, 2))
        assert counters["lin_attn/decode_kernel_share"] == 1.0
        for (logits, values), (want_logits, want_values) in zip(got, expected):
            close(logits, want_logits, 1e-5)
            close(values, want_values, 1e-5)
        for name in ("layer_0", "layer_1"):
            close(carry[name][1], expected_carry[name][1], 1e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_a_fault_planted_under_the_rules_step_reaches_the_decode_step(kernel, monkeypatch):
    """The seam a planted fault uses: `step` looks `delta_rule_step` up by its module-level name
    when it is traced, kernel or not, so a wrapper that zeroes the carried state changes what it returns."""
    spec = _tiled_sizes()
    monkeypatch.setattr(qwen3_next, "decode_kernel_taken", lambda shape: kernel)
    params = qwen3_next.init_params(spec, jax.random.PRNGKey(9))
    tokens = jax.random.randint(jax.random.PRNGKey(10), (2, 6), 0, spec.vocab_size)
    sound, _, _ = _decode(spec, params, tokens, qwen3_next.init_carry(spec, 2))
    rule = qwen3_next.delta_rule_step
    monkeypatch.setattr(qwen3_next, "delta_rule_step", lambda state, *x: rule(jnp.zeros_like(state), *x))
    zeroed, _, _ = _decode(spec, params, tokens, qwen3_next.init_carry(spec, 2))
    close(zeroed[0][0], sound[0][0])  # the first token sees a zero state either way
    assert float(jnp.max(jnp.abs(zeroed[-1][0] - sound[-1][0]))) > 1e-3


def test_attention_full_agrees_with_step_by_step_through_its_cache():
    spec, m = sizes()
    p = ref.init_params(m, 1)["layer_1"]["op"]
    u = jax.random.normal(jax.random.PRNGKey(4), (2, T, spec.hidden_size))
    cache, steps = qwen3_next.init_carry(spec, 2)["layer_1"], []
    for t in range(T):
        y, cache = qwen3_next.attention_step(p, cache, u[:, t], jnp.int32(t), spec)
        steps.append(y)
    close(jnp.stack(steps, axis=1), qwen3_next.attention(p, u, spec))


def test_rope_turns_a_quarter_of_each_head_and_the_gate_is_the_heads_second_half():
    spec, m = sizes()
    x = jax.random.normal(jax.random.PRNGKey(6), (1, T, 4, 16))
    turned = lm_layers.rope(x, jnp.arange(T), lm_layers.default_frequencies(spec.rope_theta, spec.rotary_dim))
    assert np.array_equal(turned[..., 4:], x[..., 4:]) and not np.allclose(turned[:, 1:, :, :4], x[:, 1:, :, :4])
    close(turned, ref.rope(x, m["rope_theta"], 4))
    close(lm_layers.rope(x, jnp.arange(T), lm_layers.default_frequencies(1e6, 16)), ref.rope(x, 1e6, 16))  # the whole head: LFM2's


def test_prefill_then_decode_logits_agree_with_the_references_full_forward():
    spec, m = sizes()
    params = ref.init_params(m, 7)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (3, T), 0, spec.vocab_size)
    logits, values, own, _ = ref.forward(params, m, tokens)
    carry, step = qwen3_next.init_carry(spec, 3), jax.jit(lambda p, c, t: qwen3_next.step(p, spec, c, t))
    for t in range(T):  # every token through the three kinds of state, prompt and response alike
        step_logits, step_values, carry, ids, counters = step(params, carry, tokens[:, t])
        close(step_logits, logits[:, t])
        close(step_values, values[:, t])
        assert np.array_equal(np.sort(ids, -1), np.sort(np.asarray(own[:, t]), -1))
    assert spec.linear_state_bytes_per_sequence == 4 * (4 * 8 * 8 + 3 * spec.conv_channels)
    assert spec.linear_state_bytes_per_sequence == sum(leaf.nbytes for leaf in carry["layer_0"]) // 3  # the one linear layer's carry
    full_logits, full_values, full_ids, _ = jax.jit(lambda p, t: qwen3_next.forward(p, spec, t))(params, tokens)
    close(full_logits, logits)
    close(full_values, values)
    assert int(carry["t"]) == T and full_ids.shape == (3, T, 2, spec.num_experts_per_tok)


def test_the_sixteen_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Experts 2i, 2i + 1 of 32 (the sixteen chips of the deployment, in small): what each
    share computes for its own experts, with the shared expert (which every chip computes
    alike) counted once, adds up to the whole layer of the uncut reference, which holds all 32."""
    spec_all, m_all = sizes(experts_held=(0, 32), num_experts=32)
    whole = ref.init_params(m_all, 11)["layer_1"]["ffn"]
    u = jax.random.normal(jax.random.PRNGKey(12), (7 * T, spec_all.hidden_size))  # 140 tokens: the grouped form
    expected, _ = ref.expert_layer(whole, u[None], m_all)
    shared = jax.nn.sigmoid(u @ whole["shared_gate"]) * lm_layers.swiglu(whole["shared"], u)
    total, pairs = 0.0, 0.0
    for first in range(0, 32, 2):
        spec, m = sizes(experts_held=(first, 2), num_experts=32)
        share = {**whole, **{k: whole[k][first:first + 2] for k in ("w1", "w3", "w2")}}
        y, _, counters = qwen3_next.expert_layer(share, u, spec)
        close(y, ref.expert_layer(share, u[None], m)[0][0])
        total, pairs = total + (y - shared), pairs + counters["pairs_held"]
    close(total + shared, expected[0], 5e-5)
    assert pairs == 7 * T * spec_all.num_experts_per_tok  # every (token, expert) pair lands on exactly one share


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
    # a constant channel carries the lift, so that it reaches every token alike
    u = u.at[:, 0].set(1.0)
    return router.at[0].set(lift), u


@pytest.mark.parametrize("kind, kernels", [("all", False), ("none", False), ("one_group", False), ("seeded", False),
                                           ("all", True), ("seeded", True)])
def test_no_pair_is_dropped_at_any_imbalance(kind, kernels, monkeypatch):
    """The bounded dispatch: buffers of `dispatch_rows` rows (here 2 x 160 x 4 x 4/16 = 384 of
    the 640 pairs), and every pair on a held expert computed whatever the routing, in further
    rounds where more land than a buffer holds: the reference's layer, values and gradients."""
    tokens = 160
    spec, m = sizes(experts_held=(4, 4), num_experts=16) if not kernels else sizes_tiled()
    if kernels:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(lm_layers, "_interpret", lambda: True)
    p = ref.init_params(m, 21)["layer_1"]["ffn"]
    p["router"], u = _routed(kind, tokens, spec)
    bound = lm_layers.dispatch_rows(spec, tokens)
    assert bound == 384 and bound < tokens * spec.num_experts_per_tok
    y, ids, counters = jax.jit(lambda p, u: qwen3_next.expert_layer(p, u, spec))(p, u)
    held = ((ids >= 4) & (ids < 8)).sum()
    assert held == {"all": 640, "none": 0, "one_group": 640}.get(kind, held)
    assert counters["pairs_dropped"] == 0 and counters["pairs_held"] == held
    rounds = max(1, -(-int(held) // bound))
    assert counters["dispatch_fill"] == pytest.approx(int(held) / (rounds * bound))
    if kernels:
        assert counters["grouped_product_passes"] == 6
    tol = 2e-5 if not kernels else 1e-4
    close(y, ref.expert_layer(p, u[None], m)[0][0], tol)
    grads = jax.jit(jax.grad(lambda p, u: jnp.sum(jnp.sin(qwen3_next.expert_layer(p, u, spec)[0])), argnums=(0, 1)))(p, u)
    expected = jax.jit(jax.grad(lambda p, u: jnp.sum(jnp.sin(ref.expert_layer(p, u[None], m)[0])), argnums=(0, 1)))(p, u)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(expected)):
        np.testing.assert_allclose(a, b, rtol=5 * tol, atol=5 * tol * max(1.0, float(jnp.abs(b).max())),
                                   err_msg=jax.tree_util.keystr(path))


def test_a_round_that_does_not_run_is_counted_as_dropped_pairs(monkeypatch):
    """`pairs_dropped` is the pairs landed less those the rounds' products were GIVEN, counted as
    the loop runs: a loop that stops a round early shows in it."""
    spec, m = sizes(experts_held=(4, 4), num_experts=16)
    p = ref.init_params(m, 21)["layer_1"]["ffn"]
    p["router"], u = _routed("all", 160, spec)  # 640 pairs on buffers of 384 rows: two rounds
    rounds = lm_layers._rounds
    monkeypatch.setattr(lm_layers, "_rounds", lambda p, u, w, route, n, bound: rounds(p, u, w, route, n - 1, bound))
    _, _, counters = qwen3_next.expert_layer(p, u, spec)
    assert counters["pairs_held"] == 640 and counters["pairs_dropped"] == 640 - 384


def sizes_tiled():
    """Widths the grouped kernels tile (multiples of 128), for their interpreter."""
    shared = dict(
        vocab_size=50, hidden_size=128, moe_intermediate_size=128, shared_expert_intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel_dim=4, num_experts_per_tok=4,
        norm_eps=1e-6, rope_theta=1e7)
    spec = qwen3_next.Qwen3NextSpec(**shared, layer_types=LAYERS, num_experts=16, experts_held=(4, 4), max_seq_len=T, chunk_size=8)
    return spec, dict(**shared, layer_types=list(LAYERS), num_experts_routed=16, experts_held=[4, 4], rotary_dim=4, vf_coef=1.0)


def test_the_dispatch_bound_follows_from_the_share_held():
    spec, _ = sizes(experts_held=(0, 32), num_experts=512)
    published = qwen3_next.Qwen3NextSpec(**{**spec.__dict__, "num_experts_per_tok": 10})
    assert lm_layers.dispatch_rows(published, 8192) == 10240  # twice the 5,120 pairs of uniform routing, of 81,920
    whole = qwen3_next.Qwen3NextSpec(**{**published.__dict__, "experts_held": (0, 512)})
    assert lm_layers.dispatch_rows(whole, 8192) == 81920  # never more than tokens x k, the static worst case


def test_loss_and_gradients_agree_with_jax_grad_of_the_reference():
    spec, m = sizes(layer_types=("linear_attention", "full_attention", "linear_attention"))
    params = ref.init_params(m, 13)
    keys = jax.random.split(jax.random.PRNGKey(14), 5)
    rows = 8  # 160 tokens: the update's bounded dispatch
    batch = {
        "tokens": jax.random.randint(keys[0], (rows, T), 0, spec.vocab_size),
        "actions": jax.random.randint(keys[1], (rows, T), 0, spec.vocab_size),
        "logprobs": -3.0 + 0.1 * jax.random.normal(keys[2], (rows, T)),
        "advantages": jax.random.normal(keys[3], (rows, T)),
        "returns": jax.random.normal(keys[4], (rows, T)),
        "mask": (jnp.arange(T)[None] >= jnp.array([3, 5, 4, 6, 3, 5, 4, 6])[:, None]).astype(jnp.float32),
    }

    def program_loss(p):
        from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss

        logits, values, _, _ = qwen3_next.forward(p, spec, batch["tokens"])
        logp_all = jax.nn.log_softmax(logits)
        logp = jnp.take_along_axis(logp_all, batch["actions"][..., None], -1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, -1)
        mean = lambda x: jnp.sum(x * batch["mask"]) / batch["mask"].sum()  # noqa: E731
        return (mean(policy_loss(logp, batch["logprobs"], batch["advantages"], 0.2, "none"))
                + mean(value_loss(values, values, batch["returns"], 0.2, False, "none"))
                + 0.01 * mean(entropy_loss(entropy, "none")))

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(params)
    ids = jax.jit(lambda p, t: qwen3_next.forward(p, spec, t)[2])(params, batch["tokens"])
    step = jax.jit(functools.partial(ref.block_grad, m))
    ref_grads, parts, own, _ = ref.minibatch_grad(step, params, batch, ids, 0.2, 0.01, block=4)
    close(loss, parts[0] + parts[1] + 0.01 * parts[2])
    assert np.array_equal(np.sort(ids, -1), np.sort(own, -1))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6 + 2e-4 * float(jnp.abs(b).max()),
                                   err_msg=jax.tree_util.keystr(path))


def test_a_spec_refuses_a_share_outside_the_routed_experts():
    with pytest.raises(ValueError, match="experts_held"):
        sizes(experts_held=(14, 4))
