"""The `kimi_linear` trunk (`sheeprl_tpu/models/kimi_linear.py`) against the plain reference the
benchmark owns (`perfbench/reference/kimi_linear.py`), at small widths on the CPU on seeded random
weights: the layer kinds from the published 1-indexed lists, the chunked Kimi delta attention
against the recurrence in values and gradients (decays near 0 and near 1 side by side, lengths that
are no multiple of the chunk), the step form through the carry against the whole-sequence form,
NoPE latent attention, the expert layer's shares against the uncut layer, loss and gradients, and
the CLI on `exp=ppo_anakin_kimi_linear`."""

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import deepseek_v3, kimi_linear, lm_layers
from sheeprl_tpu.ops import kda_pairs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT, "perfbench", "reference", "kimi_linear.py")


def _load_reference():
    spec = importlib.util.spec_from_file_location("kimi_linear_reference", REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()
T = 20  # no multiple of the chunk of 16: the last chunk is padded


def sizes(experts_held=(4, 4), num_experts=16, kda=(1, 2, 4), full=(3,), max_seq_len=T):
    """(the program's spec, the reference's `model` block) of one small model: a dense KDA layer,
    then expert layers, the latent one third as in the published 1-indexed lists' pattern."""
    both = dict(
        vocab_size=50, hidden_size=32, intermediate_size=48, moe_intermediate_size=24, num_attention_heads=4,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6, kv_lora_rank=16, num_hidden_layers=len(kda) + len(full),
        first_k_dense_replace=1, num_experts_per_tok=4, num_shared_experts=1, routed_scaling_factor=2.446,
        short_conv_kernel_size=4, norm_eps=1e-5, rope_theta=1e4, chunk_size=16)
    spec = kimi_linear.KimiLinearSpec(
        **both, kda_layers=tuple(kda), full_attn_layers=tuple(full), linear_num_heads=2, linear_head_dim=8,
        num_experts=num_experts, experts_held=tuple(experts_held), max_seq_len=max_seq_len)
    m = dict(**both, kda_layers=list(kda), full_attn_layers=list(full), linear_num_heads=2, linear_head_dim=8,
             num_experts_routed=num_experts, experts_held=list(experts_held), vf_coef=1.0)
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
    code = source.split('"""', 2)[2]
    assert "import sheeprl_tpu" not in source and "from sheeprl_tpu" not in source and "pallas" not in source
    assert "chunk" not in code and "cache" not in code and "dynamic_update_slice" not in code
    assert "precision" not in code  # callers set `highest`; the file pins nothing lower


def test_the_layer_kinds_are_read_from_the_published_one_indexed_lists():
    spec, _ = sizes(kda=(1, 2, 3, 5), full=(4,))
    assert spec.layers == [("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"), ("kda", "moe")]
    assert ref.layer_kinds(dataclasses.asdict(spec) | {"kda_layers": [1, 2, 3, 5]}) == spec.layers
    carry = kimi_linear.init_carry(spec, 3)
    assert carry["layer_3"].shape == (3, T, 16 + 4)  # the latent layer's cache, nothing per head
    assert [x.shape for x in carry["layer_0"]] == [(3, 3, 3 * 16), (3, 2, 8, 8)]  # three convolutions' columns, S
    for kda, full in (((1, 2), (4,)), ((1, 2, 3), (3, 4)), ((0, 1, 2), (3,))):
        with pytest.raises(ValueError, match="name each of the layers"):
            kimi_linear.KimiLinearSpec(
                **{**dataclasses.asdict(spec), "kda_layers": kda, "full_attn_layers": full, "num_hidden_layers": 4})


def test_weights_have_the_references_layout_and_values():
    spec, m = sizes()
    mine, theirs = kimi_linear.init_params(spec, jax.random.PRNGKey(5)), ref.init_params(m, 5)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
        close(a, b, 0)
    assert kimi_linear.parameter_count(spec) == sum(x.size for x in jax.tree_util.tree_leaves(theirs))
    op = mine["layer_0"]["op"]
    assert op["A_log"].shape == (2,) and op["dt_bias"].shape == (16,)  # a decay rate a head, a bias a key channel
    assert "router" not in mine["layer_0"]["ffn"] and "shared_gate" not in mine["layer_1"]["ffn"]
    assert mine["layer_2"]["op"]["w_kva"].shape == (32, 16 + 4)


def _kda_inputs(key, t=T, heads=3, dk=8, dv=6, decay=(0.2, 0.999)):
    """q, k, v, g, beta as the layer makes them; `decay` is the range of a step's exp(g), drawn a
    key channel each (log-uniform)."""
    keys = jax.random.split(key, 5)
    q = kimi_linear.l2_norm(jax.random.normal(keys[0], (2, t, heads, dk))) / np.sqrt(dk)
    k = kimi_linear.l2_norm(jax.random.normal(keys[1], (2, t, heads, dk)))
    v = jax.random.normal(keys[2], (2, t, heads, dv))
    lo, hi = np.log(decay[0]), np.log(decay[1])
    g = jax.random.uniform(keys[3], (2, t, heads, dk), minval=lo, maxval=hi)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, t, heads)))
    return q, k, v, g, beta


def _pairs_path(kernel: bool, monkeypatch) -> int:
    """Sends the chunked rule's pairs to the pairs kernel (in Pallas' interpreter) where ``kernel``
    and it tiles them, else to the XLA form: -> the head width the path needs."""
    if kernel:
        monkeypatch.setattr(kimi_linear, "pairs_kernel_taken", kda_pairs.supports)
        return kda_pairs.LANES
    assert not kimi_linear.pairs_kernel_taken((16, kda_pairs.LANES), 16)  # off the TPU: the XLA form
    return 8


# tolerances: float32 arithmetic in another order over chunks of up to 64 tokens, the recurrence's
# 2e-5 on values (as the scalar rule's tests), 1e-4 on gradients, which add up over the sequence
@pytest.mark.parametrize("t, chunk, sub, decay, kernel", [
    (T, 16, 16, (0.2, 0.999), False), (T, 16, 4, (0.2, 0.999), False), (5, 16, 4, (0.2, 0.999), False),
    (70, 32, 8, (0.2, 0.999), False), (70, 64, 16, (1e-4, 0.9999), False), (40, 16, 4, (0.9999, 1.0), False),
    (40, 16, 4, (1e-6, 1e-3), False), (T, 16, 8, (0.2, 0.999), True), (70, 64, 16, (1e-4, 0.9999), True),
    (40, 32, 32, (0.9999, 1.0), True), (40, 16, 8, (1e-6, 1e-3), True)],
    ids=["ragged", "four_subchunks", "short", "many_chunks", "near_0_beside_near_1", "near_1", "near_0",
         "kernel_ragged", "kernel_near_0_beside_near_1", "kernel_near_1", "kernel_near_0"])
def test_the_chunked_rule_is_the_recurrence_in_values_and_gradients(t, chunk, sub, decay, kernel, monkeypatch):
    dk = _pairs_path(kernel, monkeypatch)
    inputs = _kda_inputs(jax.random.PRNGKey(3), t=t, dk=dk, decay=decay)
    assert ("pallas_call" in str(jax.make_jaxpr(functools.partial(kimi_linear.chunk_kda, chunk=chunk, sub=sub))(*inputs))) == kernel
    cotangent = jax.random.normal(jax.random.PRNGKey(4), (2, t, 3, 6))
    chunked = lambda *x: jnp.sum(kimi_linear.chunk_kda(*x, chunk, sub) * cotangent)  # noqa: E731
    recurrent = lambda *x: jnp.sum(ref.delta_rule(*x) * cotangent)  # noqa: E731
    close(jax.jit(functools.partial(kimi_linear.chunk_kda, chunk=chunk, sub=sub))(*inputs), ref.delta_rule(*inputs))
    mine = jax.jit(jax.grad(chunked, argnums=(0, 1, 2, 3, 4)))(*inputs)
    theirs = jax.jit(jax.grad(recurrent, argnums=(0, 1, 2, 3, 4)))(*inputs)
    for a, b in zip(mine, theirs):
        assert np.all(np.isfinite(a))
        close(a, b, 1e-4)


def test_the_decay_a_key_channel_is_not_the_scalar_rule():
    """The same inputs with the decay averaged over the key channels (the scalar rule of
    `qwen3_next`) read otherwise: the chunked form keeps the channels apart."""
    q, k, v, g, beta = _kda_inputs(jax.random.PRNGKey(6), decay=(0.05, 0.999))
    scalar = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    from sheeprl_tpu.models import qwen3_next

    close(kimi_linear.chunk_kda(q, k, v, scalar, beta, 16), qwen3_next.chunk_delta_rule(q, k, v, g.mean(-1), beta, 16))
    assert float(jnp.max(jnp.abs(kimi_linear.chunk_kda(q, k, v, g, beta, 16) - kimi_linear.chunk_kda(q, k, v, scalar, beta, 16)))) > 1e-2


def test_the_pairs_of_a_chunk_never_form_an_exponent_over_0():
    """Decays that take a chunk's state to nothing (exp(g) 1e-30 a step in some channels): the
    naive split of the pair's decay into e^{G_t} and e^{-G_j} overflows; the sub-chunked form and
    its gradient stay finite and exact."""
    _never_over_0(_kda_inputs(jax.random.PRNGKey(7), t=32))


def test_the_pairs_kernel_never_forms_an_exponent_over_0(monkeypatch):
    """The same decays through the pairs kernel (forced, in Pallas' interpreter), forward and backward."""
    _never_over_0(_kda_inputs(jax.random.PRNGKey(7), t=32, dk=_pairs_path(True, monkeypatch)))


def _never_over_0(inputs):
    q, k, v, g, beta = inputs
    g = g.at[..., :3].set(np.log(1e-30))
    loss = lambda *x: jnp.sum(jnp.square(kimi_linear.chunk_kda(*x, 32, 8)))  # noqa: E731
    assert not np.all(np.isfinite(np.exp(-np.cumsum(np.asarray(g), axis=1))))  # the naive factor
    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert all(np.all(np.isfinite(x)) for x in grads)
    close(kimi_linear.chunk_kda(q, k, v, g, beta, 32, 8), ref.delta_rule(q, k, v, g, beta))


def test_each_mixer_full_agrees_with_step_by_step_through_its_state():
    spec, m = sizes()
    params = ref.init_params(m, 1)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, T, spec.hidden_size))
    carry = kimi_linear.init_carry(spec, 2)
    kda_p, mla_p = params["layer_1"]["op"], params["layer_2"]["op"]
    close(kimi_linear.kda(kda_p, u, spec), ref.kimi_delta_attention(kda_p, u, m))
    kda_step = jax.jit(lambda state, x: kimi_linear.kda_layer_step(kda_p, state, x, spec))
    mla_step = jax.jit(lambda cache, x, t: deepseek_v3.mla_step(mla_p, cache, x, t, spec))
    state, cache, kda_out, mla_out = carry["layer_1"], carry["layer_2"], [], []
    for t in range(T):
        y, state = kda_step(state, u[:, t])
        kda_out.append(y)
        y, cache = mla_step(cache, u[:, t], jnp.int32(t))
        mla_out.append(y)
    close(jnp.stack(kda_out, axis=1), kimi_linear.kda(kda_p, u, spec))
    close(jnp.stack(mla_out, axis=1), deepseek_v3.mla(mla_p, u, spec))


def test_nope_latent_attention_reads_no_position():
    """With `mla_use_nope` the latent attention's inputs are the same at any position (no
    rotary embedding on `q_pe` or on the shared `k_pe`), its expanded form is the reference's,
    and the cache's row holds `k_pe` as projected; with the Moonlight spec's default it turns."""
    spec, m = sizes()
    p = dict(ref.init_params(m, 1)["layer_2"]["op"], kv_norm=1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(3), (16,)))
    u = jax.random.normal(jax.random.PRNGKey(4), (2, T, spec.hidden_size))
    at_0 = deepseek_v3._latent_inputs(p, u, jnp.arange(T), spec)
    at_100 = deepseek_v3._latent_inputs(p, u, jnp.arange(T) + 100, spec)
    for a, b in zip(at_0, at_100):
        assert np.array_equal(a, b)
    np.testing.assert_array_equal(at_0[3], (u @ p["w_kva"])[..., 16:])
    turned = deepseek_v3._latent_inputs(p, u, jnp.arange(T) + 100, dataclasses.replace(spec, mla_use_nope=False))
    assert not np.allclose(turned[1], at_0[1]) and not np.allclose(turned[3], at_0[3])
    close(deepseek_v3.mla(p, u, spec), ref.latent_attention(p, u, m))
    # a score does not depend on where the pair lies: a sequence shifted by one token reads what it read
    shifted = jnp.concatenate([u[:, :1], u], axis=1)
    close(deepseek_v3.mla(p, shifted, spec)[:, 1:2], deepseek_v3.mla(p, shifted[:, :2], spec)[:, 1:2])


def test_prefill_then_decode_logits_agree_with_the_references_full_forward():
    spec, m = sizes()
    params = ref.init_params(m, 7)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (3, T), 0, spec.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits, values, own, _ = ref.forward(params, m, tokens)
    step = jax.jit(lambda carry, x: kimi_linear.step(params, spec, carry, x))
    carry = kimi_linear.init_carry(spec, 3)
    for t in range(T):
        step_logits, step_values, carry, ids, counters = step(carry, tokens[:, t])
        close(step_logits, logits[:, t])
        close(step_values, values[:, t])
        assert np.array_equal(np.sort(ids, -1), np.sort(own[:, t], -1))
    assert float(counters["kda/decode_kernel_share"]) == 0.0 and float(counters["mla/decode_kernel_share"]) == 0.0
    full_logits, full_values, full_ids, _ = jax.jit(lambda p, x: kimi_linear.forward(p, spec, x))(params, tokens)
    close(full_logits, logits)
    close(full_values, values)
    assert full_ids.shape == (3, T, 3, 4)  # three expert layers: the leading one is dense


@pytest.mark.parametrize("kernel", [False, True], ids=["xla_form", "the_kernel"])
def test_the_forward_counts_the_share_of_kda_layers_whose_pairs_took_the_kernel(kernel, monkeypatch):
    """`kda/pairs_kernel_share`, fixed when the forward is traced: 0 off the chip, where every KDA
    layer's chunked rule takes the XLA form, and 1 where the pairs kernel is forced (in Pallas'
    interpreter) at shapes it tiles, heads of 128, whose logits and values are the XLA form's to
    rounding. The loop files it as `kda/update_pairs_kernel_share`."""
    from sheeprl_tpu.algos.ppo.anakin import _counter_name

    spec = dataclasses.replace(sizes()[0], linear_head_dim=kda_pairs.LANES)
    params = kimi_linear.init_params(spec, jax.random.PRNGKey(15))
    tokens = jax.random.randint(jax.random.PRNGKey(16), (2, T), 0, spec.vocab_size)
    forward = lambda: jax.jit(lambda p, x: kimi_linear.forward(p, spec, x))(params, tokens)  # noqa: E731
    logits, values, _, counters = forward()
    assert counters["kda/pairs_kernel_share"] == 0.0
    if kernel:
        _pairs_path(True, monkeypatch)
        kernel_logits, kernel_values, _, counters = forward()
        assert counters["kda/pairs_kernel_share"] == 1.0
        close(kernel_logits, logits, 1e-5)
        close(kernel_values, values, 1e-5)
    assert _counter_name("update_kda/pairs_kernel_share") == "kda/update_pairs_kernel_share"


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Experts 4i to 4i + 3 of 16 (four chips of the deployment, in small): what each share
    computes for its own experts, with the shared expert (which every chip computes alike)
    counted once, adds up to the whole layer of the uncut reference, which holds all 16."""
    spec_all, m_all = sizes(experts_held=(0, 16), num_experts=16)
    whole = ref.init_params(m_all, 11)["layer_1"]["ffn"]
    u = jax.random.normal(jax.random.PRNGKey(12), (7 * T, spec_all.hidden_size))  # 140 tokens: the grouped form
    expected, _ = ref.expert_layer(whole, u[None], m_all)
    shared = lm_layers.swiglu(whole["shared"], u)
    total, pairs = 0.0, 0.0
    for first in range(0, 16, 4):
        spec, m = sizes(experts_held=(first, 4), num_experts=16)
        share = {**whole, **{k: whole[k][first:first + 4] for k in ("w1", "w3", "w2")}}
        y, _, counters = kimi_linear.expert_layer(share, u, spec)
        close(y, ref.expert_layer(share, u[None], m)[0][0])
        total, pairs = total + (y - shared), pairs + counters["pairs_held"]
    close(total + shared, expected[0], 5e-5)
    assert pairs == 7 * T * spec_all.num_experts_per_tok  # every (token, expert) pair lands on exactly one share


def test_loss_and_gradients_agree_with_jax_grad_of_the_reference():
    spec, m = sizes()
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

        logits, values, _, _ = kimi_linear.forward(p, spec, batch["tokens"])
        logp_all = jax.nn.log_softmax(logits)
        logp = jnp.take_along_axis(logp_all, batch["actions"][..., None], -1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, -1)
        mean = lambda x: jnp.sum(x * batch["mask"]) / batch["mask"].sum()  # noqa: E731
        return (mean(policy_loss(logp, batch["logprobs"], batch["advantages"], 0.2, "none"))
                + mean(value_loss(values, values, batch["returns"], 0.2, False, "none"))
                + 0.01 * mean(entropy_loss(entropy, "none")))

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(params)
    ids = jax.jit(lambda p, t: kimi_linear.forward(p, spec, t)[2])(params, batch["tokens"])
    step = jax.jit(functools.partial(ref.block_grad, m))
    ref_grads, parts, own, _ = ref.minibatch_grad(step, params, batch, ids, 0.2, 0.01, block=4)
    close(loss, parts[0] + parts[1] + 0.01 * parts[2])
    assert np.array_equal(np.sort(ids, -1), np.sort(own, -1))
    # the gradient through the chunked rule and the recurrence's: float32 in another order, 2e-4 of the leaf's largest
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6 + 2e-4 * float(jnp.abs(b).max()),
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.telemetry
@pytest.mark.timeout(300)
def test_cli_smoke_trains_through_run_anakin(tmp_path):
    from sheeprl_tpu.cli import run

    jsonl = tmp_path / "telemetry.jsonl"
    run(["exp=ppo_anakin_kimi_linear", "dry_run=False", "fabric.accelerator=cpu", "fabric.devices=1", "metric.log_level=0",
         "checkpoint.save_last=False", "env.num_envs=4", "algo.rollout_steps=40", "algo.per_rank_batch_size=2",
         "env.tokens.prompt_min=3", "env.tokens.prompt_max=6", "algo.lm.hidden_size=16", "algo.lm.intermediate_size=24",
         "algo.lm.moe_intermediate_size=8", "algo.lm.qk_nope_head_dim=8", "algo.lm.qk_rope_head_dim=4",
         "algo.lm.v_head_dim=8", "algo.lm.kv_lora_rank=12", "algo.lm.vocab_size=32", "algo.lm.experts_held=[8,8]",
         "algo.lm.linear_attn_config.head_dim=8", "algo.lm.chunk_size=16",
         "algo.total_steps=480", "algo.run_test=True", "metric.telemetry.enabled=true", "metric.telemetry.every=160",
         "metric.telemetry.compile_warmup_steps=0", f"metric.telemetry.jsonl_path={jsonl}", f"root_dir={tmp_path}/root",
         "run_name=smoke"])
    events = [json.loads(line) for line in open(jsonl) if line.strip()]
    summary = next(e for e in events if e["event"] == "summary")
    assert summary["clean_exit"] is True and summary["total_steps"] == 320
    counters = [e for e in events if e["event"] == "window"][-1]["counters"]
    assert counters["moe/update_pairs_dropped"][1] == 0 and counters["moe/rollout_pairs_dropped"][1] == 0
    # off the chip no decode step takes a kernel
    assert counters["kda/rollout_decode_kernel_share"][1] == 0 and counters["mla/rollout_decode_kernel_share"][1] == 0
    assert counters["kda/update_pairs_kernel_share"][1] == 0  # nor the update's chunked rule the pairs kernel
    assert not any(e["event"] == "health" and e.get("status") == "nonfinite" for e in events)
