"""The plain reference of the `qwen3_next` sequence policy (Qwen3-Next-80B-A3B-Instruct,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json) and of the
PPO step that trains it: the same equations as `sheeprl_tpu/models/qwen3_next.py` and the
sequence flavour of `algos/ppo/anakin.py`, in plain `jax.numpy` and float32. A full forward
over whole sequences: no cache, no step form, no grouped products (a loop over the experts
held), no kernels, and the gated delta rule in its per-token RECURRENT form (a `lax.scan`
over time), never the chunked form the program differentiates: the two are independent.
A copy the benchmark owns: it imports nothing of `sheeprl_tpu`, and nothing imports it by
name (the adapter loads it by its path). Callers set `jax.default_matmul_precision("highest")`.
The env, GAE, Adam and the minibatch loop are `reference/lfm2_moe.py`'s, loaded by its path.

`m` is the configuration's `model` block. Layer equations (no bias anywhere):

- norm of the trunk, zero-centred: `x * rsqrt(mean(x^2) + eps) * (1 + w)`; block
  `h = x + Mixer(Norm(x))`, `x' = h + MoE(Norm(h))`; a final norm before the heads;
- `linear_attention`: `[q, k, v, z] = W_qkvz u`, `[b, a] = W_ba u`; `[q, k, v] <-
  silu(causal_depthwise_conv1d([q, k, v]))`; `q, k <- l2norm(q), l2norm(k)` per head, each
  key head serving `value heads / key heads` value heads; `q <- q / sqrt(key dim)`;
  `beta = sigmoid(b)`, `g = -exp(A_log) * softplus(a + dt_bias)`; per value head, `S_0 = 0`:
  `S <- exp(g_t) S; r = S^T k_t; S <- S + k_t (beta_t (v_t - r))^T; o_t = S^T q_t`; output
  `W_o (w_n * o * rsqrt(mean(o^2) + eps) * silu(z))`, the norm per head, not zero-centred;
- `full_attention`: `[Q, G] = W_q u` (a head's query then its gate), the trunk's norm over
  each head on `Q` and `K`, rotate-half RoPE on the first `rotary_dim` of each head, causal
  softmax of `Q K^T / sqrt(head dim)`, output `W_o (attn * sigmoid(G))`;
- expert layer: `p = softmax(W_g u)` over all experts in float32, the k largest, their
  weights over their sum; the sum over the chosen experts HELD here (`experts_held`), each
  a SwiGLU; plus `sigmoid(w_s . u) * E_shared(u)`. What absent experts would add is left
  out, and that partial result goes on.

Departures from the published model, each under `assumed` in the configuration's file:
`W_qkvz` and `W_ba` lay their outputs out in plain blocks, `A_log` and `dt_bias` are drawn
from the seed, a linear value head reads the final hidden state, the head is untied, the
multi-token-prediction module is left out. A memory device of this file, no part of the
model: `forward(remat=True)` recomputes a layer in its backward pass and the recurrence's
scan recomputes a token's step, since the recurrence keeps a matrix state a token a head for it.
"""

from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location("perfbench_reference_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_lm = _beside("lfm2_moe.py")
copy_env, gae, minibatch_grad, adam_init, adam_step = _lm.copy_env, _lm.gae, _lm.minibatch_grad, _lm.adam_init, _lm.adam_step
swiglu, rotate_half = _lm.swiglu, _lm.rotate_half

INIT_STD = 0.02
CONV_TAP_STD = 0.3
L2_EPS = 1e-6
WEIGHT_SUM_EPS = 1e-20  # the epsilon in the sum of the chosen weights (of a softmax: never near 0)


# ---------------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------------
def widths(m: dict):
    key_width = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    value_width = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    return key_width, value_width


def init_params(m: dict, seed):
    """The weights from the seed, in the program's layout (`models/qwen3_next.py::init_params`)."""
    h, d = m["hidden_size"], m["head_dim"]
    nq, nkv, hv = m["num_attention_heads"], m["num_key_value_heads"], m["linear_num_value_heads"]
    key_width, value_width = widths(m)
    channels = 2 * key_width + value_width
    key = jax.random.PRNGKey(seed)
    count = [0]

    def fresh():
        count[0] += 1
        return jax.random.fold_in(key, count[0])

    def normal(*shape, std=INIT_STD):
        return std * jax.random.normal(fresh(), shape, jnp.float32)

    def uniform(shape, low, high):
        return jax.random.uniform(fresh(), shape, jnp.float32, low, high)

    zeros = lambda n: jnp.zeros((n,), jnp.float32)  # noqa: E731
    params = {"embed": normal(m["vocab_size"], h)}
    for i, op in enumerate(m["layer_types"]):
        layer = {"op_norm": zeros(h), "ffn_norm": zeros(h)}
        if op == "linear_attention":
            dt = jnp.exp(uniform((hv,), math.log(1e-3), math.log(1e-1)))
            layer["op"] = {
                "w_qkvz": normal(h, channels + value_width), "w_ba": normal(h, 2 * hv),
                "w_conv": normal(m["linear_conv_kernel_dim"], channels, std=CONV_TAP_STD),
                "A_log": jnp.log(uniform((hv,), 1.0, 16.0)), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": jnp.ones((m["linear_value_head_dim"],), jnp.float32), "w_out": normal(value_width, h)}
        else:
            layer["op"] = {"wq": normal(h, nq * d * 2), "wk": normal(h, nkv * d), "wv": normal(h, nkv * d),
                           "wo": normal(nq * d, h), "q_norm": zeros(d), "k_norm": zeros(d)}
        f, fs, n = m["moe_intermediate_size"], m["shared_expert_intermediate_size"], m["experts_held"][1]
        layer["ffn"] = {"router": normal(h, m["num_experts_routed"]),
                        "w1": normal(n, h, f), "w3": normal(n, h, f), "w2": normal(n, f, h),
                        "shared": {"w1": normal(h, fs), "w3": normal(h, fs), "w2": normal(fs, h)},
                        "shared_gate": normal(h, 1)}
        params[f"layer_{i}"] = layer
    params["norm"] = zeros(h)
    params["lm_head"] = normal(h, m["vocab_size"])
    params["value_head"] = normal(h, 1)
    return params


# ---------------------------------------------------------------------------------
# layers, over whole sequences [B, T, H]
# ---------------------------------------------------------------------------------
def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def norm(x, weight, eps):
    return rms(x, eps) * (1.0 + weight)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The recurrence itself, a token at a time: q, k [B, T, H, dk], v [B, T, H, dv], g and
    beta [B, T, H] -> o [B, T, H, dv], from S = 0."""

    def one_token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - read))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    start = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), jnp.float32)
    by_time = tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta))
    # a memory device: a backward pass keeps the state before each token (not the three
    # states inside a step as well) and recomputes the step
    return jnp.swapaxes(jax.lax.scan(jax.checkpoint(one_token), start, by_time)[1], 0, 1)


def linear_attention(p, u, m):
    bsz, t, _ = u.shape
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    key_width, value_width = widths(m)
    q, k, v, z = jnp.split(u @ p["w_qkvz"], [key_width, 2 * key_width, 2 * key_width + value_width], axis=-1)
    b, a = jnp.split(u @ p["w_ba"], 2, axis=-1)
    mixed = jnp.concatenate([q, k, v], axis=-1)
    taps = p["w_conv"].shape[0]
    padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))  # tap j multiplies the input K-1-j steps back
    mixed = jax.nn.silu(sum(padded[:, j:j + t] * p["w_conv"][j] for j in range(taps)))
    q, k, v = jnp.split(mixed, [key_width, 2 * key_width], axis=-1)
    q = l2norm(q.reshape(bsz, t, hk, dk)) / math.sqrt(dk)
    k = l2norm(k.reshape(bsz, t, hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))  # value head i reads key head i // (hv / hk)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    out = delta_rule(q, k, v.reshape(bsz, t, hv, dv), g, beta)
    out = p["norm"] * rms(out, m["norm_eps"]) * jax.nn.silu(z.reshape(bsz, t, hv, dv))
    return out.reshape(bsz, t, value_width) @ p["w_out"]


def rope(x, theta, rotary_dim):
    """x: [B, T, heads, d], positions 0..T-1: rotate-half over the first `rotary_dim` of each head."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    turned, kept = x[..., :rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([turned * jnp.cos(angles) + rotate_half(turned) * jnp.sin(angles), kept], axis=-1)


def attention(p, u, m):
    bsz, t, _ = u.shape
    nq, nkv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q, gate = jnp.split((u @ p["wq"]).reshape(bsz, t, nq, 2 * d), 2, axis=-1)
    q = rope(norm(q, p["q_norm"], m["norm_eps"]), m["rope_theta"], m["rotary_dim"])
    k = rope(norm((u @ p["wk"]).reshape(bsz, t, nkv, d), p["k_norm"], m["norm_eps"]), m["rope_theta"], m["rotary_dim"])
    v = (u @ p["wv"]).reshape(bsz, t, nkv, d)
    k, v = (jnp.repeat(x, nq // nkv, axis=2) for x in (k, v))  # query head i reads key/value head i // (nq / nkv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(bsz, t, nq * d)
    return (out * jax.nn.sigmoid(gate.reshape(bsz, t, nq * d))) @ p["wo"]


def route(p, u, m, chosen=None):
    """A float32 softmax over all routed experts, the k largest (or `chosen` where the
    caller follows another side's choice) and their weights over their sum; the
    reference's own choice and the margin between its k-th and (k+1)-th score."""
    s = jax.nn.softmax((u @ p["router"]).astype(jnp.float32), axis=-1)
    k = m["num_experts_per_tok"]
    own = jax.lax.top_k(s, k)[1]
    ids = own if chosen is None else chosen
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + WEIGHT_SUM_EPS)
    ordered = jnp.sort(s, axis=-1)[..., ::-1]
    return ids, w, own, ordered[..., k - 1] - ordered[..., k]


def expert_layer(p, u, m, chosen=None):
    """The held experts' part of the layer, a loop over the experts held, each over every
    token, weighted by the token's weight for it (0 where it was not chosen); then the
    shared expert behind its gate, which every share computes whole."""
    ids, w, own, margin = route(p, u, m, chosen)
    e0, n = m["experts_held"]

    def one_expert(out, expert):  # the loop is a `lax.scan`: one body for the 32 experts, not 32 copies of it
        e, w1, w3, w2 = expert
        weight = jnp.sum(jnp.where(ids == e0 + e, w, 0.0), axis=-1, keepdims=True)
        return out + weight * swiglu(w1, w3, w2, u), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), (jnp.arange(n), p["w1"], p["w3"], p["w2"]))
    shared = p["shared"]
    out = out + jax.nn.sigmoid(u @ p["shared_gate"]) * swiglu(shared["w1"], shared["w3"], shared["w2"], u)
    return out, {"own": own, "margin": margin}


def forward(params, m, tokens, chosen=None, remat=False):
    """tokens [B, T] -> logits [B, T, V], values [B, T], and per layer the reference's own
    choice `own` [B, T, layers, k] and the margin between its k-th and (k+1)-th score.
    `chosen` ([B, T, layers, k]) makes every expert layer follow those choices downstream."""

    def layer(p, x, ids, op):
        u = norm(x, p["op_norm"], m["norm_eps"])
        x = x + (linear_attention(p["op"], u, m) if op == "linear_attention" else attention(p["op"], u, m))
        y, info = expert_layer(p["ffn"], norm(x, p["ffn_norm"], m["norm_eps"]), m, ids)
        return x + y, info

    x = params["embed"][tokens]
    routes = []
    for i, op in enumerate(m["layer_types"]):
        fn = jax.checkpoint(layer, static_argnums=(3,)) if remat else layer
        x, info = fn(params[f"layer_{i}"], x, None if chosen is None else chosen[:, :, i], op)
        routes.append(info)
    x = norm(x, params["norm"], m["norm_eps"])
    own = jnp.stack([r["own"] for r in routes], axis=2)
    margin = jnp.stack([r["margin"] for r in routes], axis=2)
    return x @ params["lm_head"], (x @ params["value_head"])[..., 0], own, margin


# ---------------------------------------------------------------------------------
# the PPO step (algos/ppo/loss.py over whole sequences, masked steps left out)
# ---------------------------------------------------------------------------------
def loss_terms(params, m, batch, chosen, clip_coef):
    """Sums over the block's unmasked steps of the three PPO terms (the caller divides by
    the minibatch's count of unmasked steps, so blocks add up)."""
    logits, values, own, margin = forward(params, m, batch["tokens"], chosen, remat=True)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.take_along_axis(logp_all, batch["actions"][..., None], axis=-1)[..., 0]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    ratio = jnp.exp(logp - batch["logprobs"])
    adv = batch["advantages"]
    pg = jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 1 - clip_coef, 1 + clip_coef))
    vl = jnp.square(values - batch["returns"])
    mask = batch["mask"]
    return jnp.stack([jnp.sum(pg * mask), jnp.sum(vl * mask), -jnp.sum(entropy * mask)]), (own, margin)


def block_grad(m: dict, params, grads, part, chosen, count, clip_coef, ent_coef):
    """`grads` plus the gradient of one block's share of a minibatch's loss, the block's
    share of the three loss parts, and its routing (as `lfm2_moe.block_grad`, over this
    file's forward). Jit it once with `m` bound: nothing of a run is a constant of it."""

    def block_loss(p):
        terms, aux = loss_terms(p, m, part, chosen, clip_coef)
        terms = terms / count
        return terms[0] + m["vf_coef"] * terms[1] + ent_coef * terms[2], (terms, aux)

    g, (terms, aux) = jax.grad(block_loss, has_aux=True)(params)
    return jax.tree_util.tree_map(jnp.add, grads, g), terms, aux
