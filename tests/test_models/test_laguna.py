"""The `laguna` trunk (`sheeprl_tpu/models/laguna.py`) against the plain reference the benchmark owns
(`perfbench/reference/laguna.py`), at small widths on the CPU on seeded random weights: the layer
kinds and head counts from the published lists, the band and the blocked causal attention against a
masked full softmax in values and gradients (lengths that are no multiple of the window), the ring
step past several wraps against the whole-sequence form, YaRN against its formula, the default
RoPE path as it was, the expert layer's shares against the uncut layer, loss and gradients, and the
CLI on `exp=ppo_anakin_laguna`."""

import dataclasses
import functools
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import laguna, lfm2, lm_layers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT, "perfbench", "reference", "laguna.py")


def _load_reference():
    spec = importlib.util.spec_from_file_location("laguna_reference", REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()
T, WINDOW = 40, 8  # five windows: the ring wraps four times
ROPE = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 64.0, "original_max_position_embeddings": 4096.0,
                       "beta_fast": 64.0, "beta_slow": 1.0, "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0, "partial_rotary_factor": 1.0},
}
KINDS = ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention")


def sizes(experts_held=(4, 8), num_experts=16, window=WINDOW, max_seq_len=T):
    """(the program's spec, the reference's `model` block) of one small model: one period of the
    published pattern and the next full layer, 6 query heads on a full layer and 8 on a sliding one."""
    both = dict(vocab_size=50, hidden_size=32, intermediate_size=48, moe_intermediate_size=24,
                shared_expert_intermediate_size=16, num_key_value_heads=2, head_dim=16, sliding_window=window,
                num_experts_per_tok=4, routed_scaling_factor=2.5)
    spec = laguna.LagunaSpec(
        **both, layer_types=KINDS, mlp_layer_types=("dense",) + ("sparse",) * 4, num_attention_heads_per_layer=(6, 8, 8, 8, 6),
        rope_parameters=tuple(sorted((kind, tuple(sorted(group.items()))) for kind, group in ROPE.items())),
        num_experts=num_experts, experts_held=tuple(experts_held), max_seq_len=max_seq_len)
    m = dict(**both, layer_types=list(KINDS), mlp_layer_types=["dense"] + ["sparse"] * 4,
             num_attention_heads_per_layer=[6, 8, 8, 8, 6], rope_parameters=ROPE, num_experts_routed=num_experts,
             experts_held=list(experts_held), norm_eps=1e-6, vf_coef=1.0)
    return spec, m


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)


def test_the_reference_imports_nothing_of_the_program_and_knows_no_ring_or_band():
    with open(REFERENCE) as fh:
        source = fh.read()
    code = source.split('"""', 2)[2]
    assert "import sheeprl_tpu" not in source and "from sheeprl_tpu" not in source and "pallas" not in source
    assert "ring" not in code and "cache" not in code and "dynamic_update_slice" not in code and "band" not in code
    assert "precision" not in code  # callers set `highest`; the file pins nothing lower


def test_the_layer_kinds_and_head_counts_are_read_from_the_published_lists():
    from sheeprl_tpu.config import compose

    cfg = compose(["exp=ppo_anakin_laguna", "algo.lm.num_attention_heads_per_layer=[4,6,6,6,4]"])
    spec = laguna.LagunaSpec.from_cfg(cfg.algo.lm, 64, 128)
    assert spec.layers == [("full_attention", "dense", 4), ("sliding_attention", "sparse", 6), ("sliding_attention", "sparse", 6),
                           ("sliding_attention", "sparse", 6), ("full_attention", "sparse", 4)]
    assert spec.rope_of("full_attention")["rope_type"] == "yarn" and spec.rope_of("sliding_attention")["rope_theta"] == 1e4
    assert spec.routed_scaling_factor == 2.5 and spec.norm_eps == 1e-6 and spec.sliding_window == 32
    params = laguna.init_params(spec, jax.random.PRNGKey(0))
    assert params["layer_0"]["op"]["wq"].shape == (64, 4 * 16) and params["layer_1"]["op"]["wq"].shape == (64, 6 * 16)
    assert params["layer_1"]["op"]["wg"].shape == (64, 6)  # the gate: one value a head
    carry = laguna.init_carry(spec, 3)
    assert carry["layer_0"][0].shape == (3, 128, 2, 16) and carry["layer_1"][0].shape == (3, 32, 2, 16)  # a cache, a ring
    with pytest.raises(ValueError, match="num_hidden_layers"):
        laguna.LagunaSpec.from_cfg(compose(["exp=ppo_anakin_laguna", "algo.lm.num_hidden_layers=4"]).algo.lm, 64, 128)
    for broken, match in ((dict(num_attention_heads_per_layer=(4, 6, 6, 6)), "an entry a layer"),
                          (dict(layer_types=("full_attention",) * 4 + ("local",)), "unknown layer types"),
                          (dict(num_attention_heads_per_layer=(4, 6, 6, 6, 5)), "divide every layer's query heads")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(spec, **broken)


def test_weights_have_the_references_layout_and_values():
    spec, m = sizes()
    mine, theirs = laguna.init_params(spec, jax.random.PRNGKey(5)), ref.init_params(m, 5)
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
        close(a, b, 0)
    assert laguna.parameter_count(spec) == sum(x.size for x in jax.tree_util.tree_leaves(theirs))
    assert "router" not in mine["layer_0"]["ffn"] and not np.any(mine["layer_1"]["ffn"]["bias"])


def _masked_softmax(q, k, v, window, nkv):
    """`lm_layers.attend` over the whole sequence, the window a mask only."""
    t = q.shape[1]
    i, j = np.arange(t)[:, None], np.arange(t)[None]
    mask = (j <= i) if window is None else (j <= i) & (j > i - window)
    return lm_layers.attend(q, k, v, mask, nkv)


# tolerances: float32 sums over the same keys in another grouping, 2e-5 on values; gradients add
# up over the blocks that read a key, 5e-5
@pytest.mark.parametrize("t, window", [(40, 8), (37, 8), (21, 16), (9, 16), (23, None), (600, 64), (600, None)],
                         ids=["five_windows", "no_multiple", "under_two", "under_one", "full_causal", "long_band",
                              "full_blocks"])
def test_the_band_and_the_blocks_are_the_masked_softmax_in_values_and_gradients(t, window):
    keys = jax.random.split(jax.random.PRNGKey(t), 4)
    q, k, v = (jax.random.normal(keys[i], (2, t, n, 16)) for i, n in ((0, 6), (1, 2), (2, 2)))
    cot = jax.random.normal(keys[3], (2, t, 6 * 16))
    if window is None:
        mine = lambda q, k, v: laguna.full_forward(q, k, v, 2)  # noqa: E731
    else:
        mine = lambda q, k, v: laguna.swa_forward(q, k, v, window, 2)  # noqa: E731
    plain = functools.partial(_masked_softmax, window=window, nkv=2)
    close(mine(q, k, v), plain(q, k, v))
    grads = jax.grad(lambda *x: jnp.sum(mine(*x) * cot), argnums=(0, 1, 2))(q, k, v)
    plain_grads = jax.grad(lambda *x: jnp.sum(plain(*x) * cot), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, plain_grads):
        close(a, b, 5e-5)


def test_the_band_forms_no_score_matrix_of_the_whole_sequence():
    t, window = 2048, 512
    q = jax.ShapeDtypeStruct((1, t, 8, 16), jnp.float32)
    k = jax.ShapeDtypeStruct((1, t, 2, 16), jnp.float32)
    text = jax.jit(lambda q, k, v: laguna.swa_forward(q, k, v, window, 2)).lower(q, k, k).as_text()
    assert f"{t}x{t}" not in text and "512x1024" in text  # a block of queries against its own and the previous block


def test_the_ring_step_agrees_with_the_whole_sequence_form_past_several_wraps():
    spec, m = sizes()
    params = ref.init_params(m, 1)
    u = jax.random.normal(jax.random.PRNGKey(2), (2, T, spec.hidden_size))
    carry = laguna.init_carry(spec, 2)
    assert carry["layer_1"][0].shape == (2, WINDOW, 2, 16) and carry["layer_0"][0].shape == (2, T, 2, 16)
    for layer, kind in ((1, "sliding_attention"), (0, "full_attention")):
        p = params[f"layer_{layer}"]["op"]
        whole = laguna.attention(p, u, kind, spec)
        close(whole, ref.attention(p, u, kind, 8 if kind == "sliding_attention" else 6, m))
        step = jax.jit(lambda cache, x, t, p=p, kind=kind: laguna.attention_step(p, cache, x, t, kind, spec))
        cache, out, rows = carry[f"layer_{layer}"], [], []
        for t in range(T):
            y, cache, read = step(cache, u[:, t], jnp.int32(t))
            out.append(y)
            rows.append(int(read))
        close(jnp.stack(out, axis=1), whole)
        # every step reads the whole ring or cache; the agreement above is what the mask of the rows written gives
        assert rows == [WINDOW if kind == "sliding_attention" else T] * T


def test_the_ring_holds_each_key_rotated_at_its_own_position():
    """After 13 steps through a ring of 8, slot `s` holds the key of the latest position `p` with
    `p mod 8 = s`, rotated at `p` (not at `s`): 8 to 12 in slots 0 to 4, 5 to 7 in slots 5 to 7."""
    spec, m = sizes()
    p = ref.init_params(m, 3)["layer_1"]["op"]
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 13, spec.hidden_size))
    ring = laguna.init_carry(spec, 1)["layer_1"]
    for t in range(13):
        _, ring, _ = laguna.attention_step(p, ring, u[:, t], jnp.int32(t), "sliding_attention", spec)
    _, k, _ = laguna._qkv(p, u, jnp.arange(13), "sliding_attention", spec)
    expected = np.asarray(k[0])[[8, 9, 10, 11, 12, 5, 6, 7]]
    close(ring[0][0], expected, 1e-6)


def test_the_decode_step_counts_the_ring_rows_it_reads():
    from sheeprl_tpu.algos.ppo.anakin import _counter_name

    spec, m = sizes()
    params = ref.init_params(m, 5)
    step = jax.jit(lambda carry, x: laguna.step(params, spec, carry, x))
    carry, read = laguna.init_carry(spec, 2), []
    for t in range(T):
        *_, carry, _, counters = step(carry, jnp.full((2,), t % 50, jnp.int32))
        read.append(float(counters["swa/ring_rows_read"]))
    # the XLA form reads every slot of the ring at every step, the slots not yet written masked
    assert read == [float(WINDOW)] * T
    assert _counter_name("rollout_swa/ring_rows_read") == "swa/rollout_ring_rows_read"
    # a ring as long as the episode (the window removed) reads T rows a step, and the counter says so
    wide = dataclasses.replace(spec, sliding_window=T)
    *_, counters = laguna.step(params, wide, laguna.init_carry(wide, 2), jnp.zeros((2,), jnp.int32))
    assert float(counters["swa/ring_rows_read"]) == T


def _yarn_written_out(rope, dim):
    """YaRN from its formula, in float64: the correction channel of `r` rotations, the ramp
    between the floor and the ceiling of those of `beta_fast` and `beta_slow`, the blend."""
    theta, factor, positions = rope["rope_theta"], rope["factor"], rope["original_max_position_embeddings"]

    def channel(rotations):
        return dim * math.log(positions / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(channel(rope["beta_fast"])), 0), min(math.ceil(channel(rope["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        extrap = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(extrap / factor * ramp + extrap * (1 - ramp))
    return np.array(out), rope.get("attention_factor") or 0.1 * math.log(factor) + 1


@pytest.mark.parametrize("rope, dim", [
    (ROPE["full_attention"], 64),  # Laguna-XS.2's full layers: the ramp from channel pair 5 to 16
    (ROPE["full_attention"], 128),
    ({"rope_theta": 10000.0, "factor": 8.0, "original_max_position_embeddings": 2048, "beta_fast": 32, "beta_slow": 1}, 64),
    ({"rope_theta": 1e6, "factor": 4.0, "original_max_position_embeddings": 8192, "beta_fast": 32.0, "beta_slow": 1.0,
      "attention_factor": 1.2}, 32),
], ids=["published", "whole_head", "default_factor", "other_law"])
def test_yarn_frequencies_follow_the_formula(rope, dim):
    inv, factor = lm_layers.yarn_frequencies(rope, dim)
    expected, expected_factor = _yarn_written_out(rope, dim)
    np.testing.assert_allclose(np.asarray(inv), expected, rtol=2e-6)  # float32 against float64
    assert factor == pytest.approx(expected_factor)
    # the fastest channels keep the default law, the slowest are the default law over `factor`
    default = np.asarray(lm_layers.default_frequencies(rope["rope_theta"], dim))
    np.testing.assert_allclose(np.asarray(inv)[0], default[0], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv)[-1], default[-1] / rope["factor"], rtol=1e-6)
    head = int(dim / rope.get("partial_rotary_factor", 1.0))
    ref_inv, ref_factor = ref.rotary({**rope, "rope_type": "yarn", "attention_factor": factor}, head)
    np.testing.assert_allclose(ref_inv, np.asarray(inv), rtol=1e-6)  # the reference's own copy, from the head's width
    assert ref_factor == factor


def test_yarn_with_factor_1_is_the_default_law():
    rope = {**ROPE["full_attention"], "factor": 1.0, "attention_factor": 1.0}
    inv, factor = lm_layers.yarn_frequencies(rope, 64)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(lm_layers.default_frequencies(500000.0, 64)), rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 3, 128))
    positions = jnp.arange(7) + 300
    # the blend of two equal laws rounds a frequency by an ulp, which a position of 300 turns into 2e-6
    close(lm_layers.rope(x, positions, inv, factor), lm_layers.rope(x, positions, lm_layers.default_frequencies(500000.0, 64)), 5e-6)


def test_the_attention_factor_scales_the_rotated_channels_only():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 2, 128))
    inv, _ = lm_layers.yarn_frequencies(ROPE["full_attention"], 64)
    plain = lm_layers.rope(x, jnp.arange(5), inv)
    scaled = lm_layers.rope(x, jnp.arange(5), inv, 1.5)
    close(scaled[..., :64], 1.5 * plain[..., :64], 1e-6)
    assert np.array_equal(scaled[..., 64:], x[..., 64:]) and np.array_equal(plain[..., 64:], x[..., 64:])


def _rope_as_it_was(x, positions, theta, rotary_dim=None):
    """`lm_layers.rope` before YaRN came in, copied: the default law, given to `rope` as
    `default_frequencies`, must stay this program."""
    d = x.shape[-1] if rotary_dim is None else rotary_dim
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * inv[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    if d == x.shape[-1]:
        return x * jnp.cos(angles) + lm_layers.rotate_half(x) * jnp.sin(angles)
    turned, kept = x[..., :d], x[..., d:]
    return jnp.concatenate([turned * jnp.cos(angles) + lm_layers.rotate_half(turned) * jnp.sin(angles), kept], axis=-1)


@pytest.mark.parametrize("theta, rotary_dim", [(1e6, None), (1e7, 64), (1e4, None)], ids=["lfm2", "qwen3_next", "moonlight"])
def test_the_default_rope_path_is_the_function_as_it_was(theta, rotary_dim):
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 4, 256 if rotary_dim else 64))
    positions = jnp.arange(9) + 17
    inv = lambda: lm_layers.default_frequencies(theta, rotary_dim or x.shape[-1])  # noqa: E731  (inside the trace, as a trunk calls it)
    assert np.array_equal(lm_layers.rope(x, positions, inv()), _rope_as_it_was(x, positions, theta, rotary_dim))
    now = jax.jit(lambda x, p: lm_layers.rope(x, p, inv())).lower(x, positions).as_text()
    before = jax.jit(lambda x, p: _rope_as_it_was(x, p, theta, rotary_dim)).lower(x, positions).as_text()
    assert now == before


def test_the_lfm2_decode_step_through_the_shared_cache_step_is_the_program_as_it_was():
    """`lfm2.attention_step` reads its cache through `lm_layers.kv_cache_step` now: the same ops
    in the same order as the lines it had, so the same program."""
    spec = lfm2.LFM2Spec(vocab_size=32, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
                         num_attention_heads=4, num_key_value_heads=2, layer_types=("full_attention",), num_dense_layers=1,
                         num_experts=8, num_experts_per_tok=2, experts_held=(0, 8), max_seq_len=12)
    p = lfm2.init_params(spec, jax.random.PRNGKey(0))["layer_0"]["op"]

    def as_it_was(p, cache, u, t):
        q, k, v = lfm2._qkv(p, u[:, None], t[None], spec)
        keys = jax.lax.dynamic_update_slice_in_dim(cache[0], k, t, axis=1)
        values = jax.lax.dynamic_update_slice_in_dim(cache[1], v, t, axis=1)
        mask = (jnp.arange(keys.shape[1]) <= t)[None]
        return lm_layers.attend(q, keys, values, mask, spec.num_key_value_heads)[:, 0] @ p["wo"], (keys, values)

    def program(fn):  # the lowered text without the module's name, which is the function's
        text = jax.jit(fn).lower(p, lfm2.init_carry(spec, 2)["layer_0"], jnp.ones((2, 32)), jnp.int32(3)).as_text()
        return text.split("\n", 1)[1]

    assert program(lambda *a: lfm2.attention_step(*a, spec)) == program(as_it_was)


# sha256 of the lowered text (no source locations) of the latent-attention trunks' toy fused programs, matmuls
# at `highest`, jax 0.9.0, taken on the commit before this trunk (68447fa); the LFM2 and `qwen3_next` programs
# are pinned in tests/test_algos/test_anakin_sequence_deepseek_v3.py
OTHER_TRUNKS_SHA256 = {
    "ppo_anakin_deepseek_v3": ("28de11adbaf7249b764f153308533e28f03e95b0422dae00740db11571708b19", [
        "algo.lm.intermediate_size=24", "algo.lm.qk_nope_head_dim=8", "algo.lm.qk_rope_head_dim=4", "algo.lm.v_head_dim=8",
        "algo.lm.kv_lora_rank=12", "algo.lm.experts_held=[8,8]"]),
    "ppo_anakin_kimi_linear": ("e17faba4aa6682ebba16fa5da91a01b707eec6206fca9bfce7bdfe27e89fa3fc", [
        "algo.lm.intermediate_size=24", "algo.lm.qk_nope_head_dim=8", "algo.lm.qk_rope_head_dim=4", "algo.lm.v_head_dim=8",
        "algo.lm.kv_lora_rank=12", "algo.lm.experts_held=[8,8]", "algo.lm.linear_attn_config.head_dim=8",
        "algo.lm.chunk_size=16"]),
}


@pytest.mark.timeout(300)
@pytest.mark.parametrize("exp", sorted(OTHER_TRUNKS_SHA256))
def test_the_other_trunks_lower_to_the_programs_they_had(exp):
    """`rope` takes its frequencies now, `default_frequencies` for the default law: the Moonlight and
    Kimi-Linear trunks' fused programs (rollout, dispatch, update) lower to the text they had before
    this trunk, byte for byte once source locations are left out."""
    import hashlib

    path = os.path.join(ROOT, "tests", "test_algos", "test_anakin_sequence_deepseek_v3.py")
    spec = importlib.util.spec_from_file_location("anakin_sequence_deepseek_v3_tests", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    digest, widths = OTHER_TRUNKS_SHA256[exp]
    run = [t for t in module.TOY if not t.startswith(("exp=", "algo.lm."))]
    with jax.default_matmul_precision("highest"):
        fused, args, _, _ = module._toy_program([f"exp={exp}", *run, "algo.lm.hidden_size=16",
                                                 "algo.lm.moe_intermediate_size=8", "algo.lm.vocab_size=32", *widths])
        text = fused.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_prefill_then_decode_logits_agree_with_the_references_full_forward():
    spec, m = sizes()
    params = ref.init_params(m, 7)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (3, T), 0, spec.vocab_size)
    logits, values, own, _ = jax.jit(lambda p, x: ref.forward(p, m, x))(params, tokens)
    step = jax.jit(lambda carry, x: laguna.step(params, spec, carry, x))
    carry = laguna.init_carry(spec, 3)
    for t in range(T):
        step_logits, step_values, carry, ids, _ = step(carry, tokens[:, t])
        close(step_logits, logits[:, t])
        close(step_values, values[:, t])
        assert np.array_equal(np.sort(ids, -1), np.sort(own[:, t], -1))
    full_logits, full_values, full_ids, _ = jax.jit(lambda p, x: laguna.forward(p, spec, x))(params, tokens)
    close(full_logits, logits)
    close(full_values, values)
    assert full_ids.shape == (3, T, 4, 4)  # four expert layers: the leading one is dense


def test_a_window_one_key_wider_and_the_gate_a_head_are_seen_by_the_logits():
    """A window of 9 keys in the place of 8 moves the logits from the ninth position on and not
    before; the gate multiplies each head's channels by its own sigmoid."""
    spec, m = sizes()
    params = ref.init_params(m, 9)
    tokens = jax.random.randint(jax.random.PRNGKey(10), (2, T), 0, spec.vocab_size)
    logits = jax.jit(lambda p, x: laguna.forward(p, spec, x)[0])(params, tokens)
    wide = dataclasses.replace(spec, sliding_window=WINDOW + 1)
    wider = jax.jit(lambda p, x: laguna.forward(p, wide, x)[0])(params, tokens)
    assert float(jnp.abs(wider - logits)[:, WINDOW:].max()) > 1e-4
    close(wider[:, :WINDOW], logits[:, :WINDOW], 1e-6)
    p = params["layer_1"]["op"]
    u = jax.random.normal(jax.random.PRNGKey(11), (3, spec.hidden_size))
    out = jax.random.normal(jax.random.PRNGKey(12), (3, 8 * 16))
    gate = jax.nn.sigmoid(u @ p["wg"])  # [3, heads]
    close(laguna.output_gate(p, u, out), (out.reshape(3, 8, 16) * gate[..., None]).reshape(3, -1), 1e-6)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Experts 4i to 4i + 3 of 16 (four chips of the deployment, in small): what each share
    computes for its own experts, with the shared expert (which every chip computes alike)
    counted once, adds up to the whole layer of the uncut reference, which holds all 16."""
    spec_all, m_all = sizes(experts_held=(0, 16), num_experts=16)
    whole = ref.init_params(m_all, 11)["layer_1"]["ffn"]
    u = jax.random.normal(jax.random.PRNGKey(12), (4 * T, spec_all.hidden_size))  # 160 tokens: the grouped form
    expected, _ = ref.expert_layer(whole, u[None], m_all)
    shared = lm_layers.swiglu(whole["shared"], u)
    total, pairs = 0.0, 0.0
    for first in range(0, 16, 4):
        spec, m = sizes(experts_held=(first, 4), num_experts=16)
        share = {**whole, **{k: whole[k][first:first + 4] for k in ("w1", "w3", "w2")}}
        y, _, counters = laguna.expert_layer(share, u, spec)
        close(y, ref.expert_layer(share, u[None], m)[0][0])
        total, pairs = total + (y - shared), pairs + counters["pairs_held"]
    close(total + shared, expected[0], 5e-5)
    assert pairs == 4 * T * spec_all.num_experts_per_tok  # every (token, expert) pair lands on exactly one share


def test_loss_and_gradients_agree_with_jax_grad_of_the_reference():
    spec, m = sizes()
    params = ref.init_params(m, 13)
    keys = jax.random.split(jax.random.PRNGKey(14), 5)
    rows = 4  # 160 tokens: the update's bounded dispatch
    batch = {
        "tokens": jax.random.randint(keys[0], (rows, T), 0, spec.vocab_size),
        "actions": jax.random.randint(keys[1], (rows, T), 0, spec.vocab_size),
        "logprobs": -3.0 + 0.1 * jax.random.normal(keys[2], (rows, T)),
        "advantages": jax.random.normal(keys[3], (rows, T)),
        "returns": jax.random.normal(keys[4], (rows, T)),
        "mask": (jnp.arange(T)[None] >= jnp.array([3, 5, 4, 6])[:, None]).astype(jnp.float32),
    }

    def program_loss(p):
        from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss

        logits, values, _, _ = laguna.forward(p, spec, batch["tokens"])
        logp_all = jax.nn.log_softmax(logits)
        logp = jnp.take_along_axis(logp_all, batch["actions"][..., None], -1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, -1)
        mean = lambda x: jnp.sum(x * batch["mask"]) / batch["mask"].sum()  # noqa: E731
        return (mean(policy_loss(logp, batch["logprobs"], batch["advantages"], 0.2, "none"))
                + mean(value_loss(values, values, batch["returns"], 0.2, False, "none"))
                + 0.01 * mean(entropy_loss(entropy, "none")))

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(params)
    ids = jax.jit(lambda p, t: laguna.forward(p, spec, t)[2])(params, batch["tokens"])
    step = jax.jit(functools.partial(ref.block_grad, m))
    ref_grads, parts, own, _ = ref.minibatch_grad(step, params, batch, ids, 0.2, 0.01, block=2)
    close(loss, parts[0] + parts[1] + 0.01 * parts[2])
    assert np.array_equal(np.sort(ids, -1), np.sort(own, -1))
    # the gradient through the band and the blocks and the masked softmax's: float32 in another order
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6 + 2e-4 * float(jnp.abs(b).max()),
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.telemetry
@pytest.mark.timeout(300)
def test_cli_smoke_trains_through_run_anakin(tmp_path):
    from sheeprl_tpu.cli import run

    jsonl = tmp_path / "telemetry.jsonl"
    run(["exp=ppo_anakin_laguna", "dry_run=False", "fabric.accelerator=cpu", "fabric.devices=1", "metric.log_level=0",
         "checkpoint.save_last=False", "env.num_envs=4", "algo.rollout_steps=40", "algo.per_rank_batch_size=2",
         "env.tokens.prompt_min=3", "env.tokens.prompt_max=6", "algo.lm.hidden_size=16", "algo.lm.intermediate_size=24",
         "algo.lm.moe_intermediate_size=8", "algo.lm.shared_expert_intermediate_size=8", "algo.lm.head_dim=8",
         "algo.lm.vocab_size=32", "algo.lm.experts_held=[8,8]", "algo.lm.sliding_window=8",
         "algo.total_steps=480", "algo.run_test=True", "metric.telemetry.enabled=true", "metric.telemetry.every=160",
         "metric.telemetry.compile_warmup_steps=0", f"metric.telemetry.jsonl_path={jsonl}", f"root_dir={tmp_path}/root",
         "run_name=smoke"])
    events = [json.loads(line) for line in open(jsonl) if line.strip()]
    summary = next(e for e in events if e["event"] == "summary")
    assert summary["clean_exit"] is True and summary["total_steps"] == 320
    counters = [e for e in events if e["event"] == "window"][-1]["counters"]
    assert counters["moe/update_pairs_dropped"][1] == 0 and counters["moe/rollout_pairs_dropped"][1] == 0
    # the rollout's sliding layers read their rings' 8 rows at every step of an episode of 40
    seen = counters["swa/rollout_ring_rows_read"]
    assert seen[1] / seen[0] == pytest.approx(8.0)
