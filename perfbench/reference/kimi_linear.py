"""The plain reference of the `kimi_linear` sequence policy (Kimi-Linear-48B-A3B-Instruct,
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json, `model_type:
kimi_linear`) and of the PPO step that trains it: the same equations as
`sheeprl_tpu/models/kimi_linear.py` and the sequence flavour of `algos/ppo/anakin.py`, in plain
`jax.numpy` and float32. A full forward over whole sequences: Kimi delta attention in its
per-token RECURRENT form (a `lax.scan` over time, never a chunk), the latent attention in its
EXPANDED form only (per-head keys and values made from the latent), no cache, no step form, no
grouped products (a loop over the experts held), no kernels. A copy the benchmark owns: it imports
nothing of `sheeprl_tpu`, and nothing imports it by name (the adapter loads it by its path).
Callers set `jax.default_matmul_precision("highest")`. The env, GAE, Adam and the minibatch loop
are `reference/lfm2_moe.py`'s, the router `reference/deepseek_v3.py`'s, both loaded by path.

`m` is the configuration's `model` block. Layer equations (no bias but the output gate's; `Norm(x)
= x * rsqrt(mean(x^2) + eps) * w`):

- block `h = x + Mixer(Norm(x))`, `x' = h + FFN(Norm(h))`; a final Norm before the heads; the
  layer `i` (1-indexed) is Kimi delta attention where `kda_layers` names it and latent attention
  where `full_attn_layers` does; the first `first_k_dense_replace` layers' FFN is a dense SwiGLU,
  every later one the expert layer;
- Kimi delta attention, per head of `linear_head_dim` channels: `q, k, v = silu(conv_q(W_q u)),
  silu(conv_k(W_k u)), silu(conv_v(W_v u))`, three causal depthwise convolutions (tap `j` times
  the input `K - 1 - j` steps back); `q <- l2norm(q) / sqrt(dk)`, `k <- l2norm(k)`; `beta =
  sigmoid(W_b u)` a head; `g = -exp(A_log[h]) * softplus(W_f_up W_f_down u + dt_bias)` a key
  channel; with `S_0 = 0`: `S <- Diag(exp(g_t)) S; r = S^T k_t; S <- S + k_t (beta_t (v_t -
  r))^T; o_t = S^T q_t`; output `W_o (w_n * o * rsqrt(mean(o^2) + eps) * sigmoid(W_g_up W_g_down
  u + b_g))`, the norm a head;
- latent attention with NoPE (`mla_use_nope`): `q = W_q u`, a head's `[q_nope | q_pe]`;
  `[c | k_pe] = W_kva u`; `c <- Norm_c(c)`; `[k_nope_h | v_h] = W_kvb c` a head; `k_h = [k_nope_h
  | k_pe]`, the one `k_pe` all heads share, no rotary embedding on it or on `q_pe`; causal
  `softmax(q_h k_h^T / sqrt(nope + rope))`; `W_o concat_h(attn_h v_h)`;
- expert layer: `s = sigmoid(W_g u)`; the k largest of `s + b`; their weights `s_i` without `b`,
  over their sum plus 1e-20, times `routed_scaling_factor`; the sum over the chosen experts HELD
  here (`experts_held`), each a SwiGLU; plus ONE ungated SwiGLU of width `num_shared_experts x
  moe_intermediate_size`. What absent experts would add is left out, and that partial result goes on.

Departures from the published model, each under `assumed` in the configuration's file: the
decay's and the gate's initialisation, the output gate's bias, the bias `b` drawn from the seed
and never trained, N(0, 0.02) matrices and unit norm weights, a linear value head on the final
hidden state. A memory device of this file, no part of the model: `forward(remat=True)`
recomputes a layer in its backward pass, and the recurrence's scan recomputes a token's step.
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
swiglu, rms_norm = _lm.swiglu, _lm.rms_norm
route = _beside("deepseek_v3.py").route

INIT_STD = 0.02
BIAS_STD = 0.05
CONV_TAP_STD = 0.3
L2_EPS = 1e-6


# ---------------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------------
def layer_kinds(m: dict):
    """(mixer, feed-forward) a layer: `kda` or `mla` by the 1-indexed lists, `dense` or `moe`."""
    return [("kda" if i + 1 in m["kda_layers"] else "mla", "dense" if i < m["first_k_dense_replace"] else "moe")
            for i in range(m["num_hidden_layers"])]


def init_params(m: dict, seed):
    """The weights from the seed, in the program's layout (`models/kimi_linear.py::init_params`)."""
    h, nh, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    hk, dk, taps = m["linear_num_heads"], m["linear_head_dim"], m["short_conv_kernel_size"]
    width = hk * dk
    key = jax.random.PRNGKey(seed)
    count = [0]

    def fresh():
        count[0] += 1
        return jax.random.fold_in(key, count[0])

    def normal(*shape, std=INIT_STD):
        return std * jax.random.normal(fresh(), shape, jnp.float32)

    def uniform(shape, low, high):
        return jax.random.uniform(fresh(), shape, jnp.float32, low, high)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params = {"embed": normal(m["vocab_size"], h)}
    for i, (mixer, ffn) in enumerate(layer_kinds(m)):
        layer = {"op_norm": ones(h), "ffn_norm": ones(h)}
        if mixer == "kda":
            dt = jnp.exp(uniform((width,), math.log(1e-3), math.log(1e-1)))
            layer["op"] = {
                "wq": normal(h, width), "wk": normal(h, width), "wv": normal(h, width),
                "conv_q": normal(taps, width, std=CONV_TAP_STD), "conv_k": normal(taps, width, std=CONV_TAP_STD),
                "conv_v": normal(taps, width, std=CONV_TAP_STD),
                "w_f_down": normal(h, dk), "w_f_up": normal(dk, width),
                "A_log": jnp.log(uniform((hk,), 1.0, 16.0)), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "w_b": normal(h, hk), "w_g_down": normal(h, dk), "w_g_up": normal(dk, width),
                "g_bias": jnp.zeros((width,), jnp.float32), "norm": ones(dk), "wo": normal(width, h)}
        else:
            layer["op"] = {"wq": normal(h, nh * (dn + dr)), "w_kva": normal(h, r + dr), "kv_norm": ones(r),
                           "w_kvb": normal(r, nh * (dn + dv)), "wo": normal(nh * dv, h)}
        if ffn == "dense":
            f = m["intermediate_size"]
            layer["ffn"] = {"w1": normal(h, f), "w3": normal(h, f), "w2": normal(f, h)}
        else:
            f, n = m["moe_intermediate_size"], m["experts_held"][1]
            layer["ffn"] = {"router": normal(h, m["num_experts_routed"]),
                            "bias": normal(m["num_experts_routed"], std=BIAS_STD),
                            "w1": normal(n, h, f), "w3": normal(n, h, f), "w2": normal(n, f, h)}
            if m["num_shared_experts"]:
                fs = m["num_shared_experts"] * f
                layer["ffn"]["shared"] = {"w1": normal(h, fs), "w3": normal(h, fs), "w2": normal(fs, h)}
        params[f"layer_{i}"] = layer
    params["norm"] = ones(h)
    params["lm_head"] = normal(h, m["vocab_size"])
    params["value_head"] = normal(h, 1)
    return params


# ---------------------------------------------------------------------------------
# layers, over whole sequences [B, T, H]
# ---------------------------------------------------------------------------------
def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def causal_conv(x, taps):
    """x [B, T, C], taps [K, C]: tap j multiplies the input K-1-j steps back."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * taps[j] for j in range(k))


def delta_rule(q, k, v, g, beta):
    """The recurrence itself, a token at a time: q, k, g [B, T, H, dk], v [B, T, H, dv], beta
    [B, T, H] -> o [B, T, H, dv], from S = 0; the decay scales the state's rows, a key channel each."""

    def one_token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - read))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    start = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), jnp.float32)
    by_time = tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta))
    # a memory device: a backward pass keeps the state before each token and recomputes the step
    return jnp.swapaxes(jax.lax.scan(jax.checkpoint(one_token), start, by_time)[1], 0, 1)


def kimi_delta_attention(p, u, m):
    bsz, t, _ = u.shape
    hk, dk = m["linear_num_heads"], m["linear_head_dim"]
    heads = lambda x: x.reshape(bsz, t, hk, dk)  # noqa: E731
    q = l2norm(heads(jax.nn.silu(causal_conv(u @ p["wq"], p["conv_q"])))) / math.sqrt(dk)
    k = l2norm(heads(jax.nn.silu(causal_conv(u @ p["wk"], p["conv_k"]))))
    v = heads(jax.nn.silu(causal_conv(u @ p["wv"], p["conv_v"])))
    beta = jax.nn.sigmoid(u @ p["w_b"])
    g = -jnp.exp(p["A_log"])[:, None] * heads(jax.nn.softplus((u @ p["w_f_down"]) @ p["w_f_up"] + p["dt_bias"]))
    out = delta_rule(q, k, v, g, beta)
    gate = heads((u @ p["w_g_down"]) @ p["w_g_up"] + p["g_bias"])
    out = p["norm"] * out * jax.lax.rsqrt(jnp.mean(jnp.square(out), axis=-1, keepdims=True) + m["norm_eps"])
    return (out * jax.nn.sigmoid(gate)).reshape(bsz, t, hk * dk) @ p["wo"]


def latent_attention(p, u, m):
    """The expanded form with NoPE: no rotary embedding on `q_pe` or on the shared `k_pe`."""
    bsz, t, _ = u.shape
    nh, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = (u @ p["wq"]).reshape(bsz, t, nh, dn + dr)
    kva = u @ p["w_kva"]
    c = rms_norm(kva[..., :r], p["kv_norm"], m["norm_eps"])
    k_pe = kva[..., r:].reshape(bsz, t, 1, dr)
    kv = (c @ p["w_kvb"]).reshape(bsz, t, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (bsz, t, nh, dr))], axis=-1)  # every head reads the one k_pe
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dn + dr))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:]).reshape(bsz, t, nh * dv) @ p["wo"]


def expert_layer(p, u, m, chosen=None):
    """The held experts' part of the layer: a loop over the experts held, each over every
    token, weighted by the token's weight for it (0 where it was not chosen); then the shared
    expert's one ungated SwiGLU, which every share computes whole."""
    ids, w, own, margin = route(p, u, m, chosen)
    e0, n = m["experts_held"]
    out = jnp.zeros_like(u)
    for e in range(n):
        weight = jnp.sum(jnp.where(ids == e0 + e, w, 0.0), axis=-1, keepdims=True)
        out = out + weight * swiglu(p["w1"][e], p["w3"][e], p["w2"][e], u)
    if m["num_shared_experts"]:
        shared = p["shared"]
        out = out + swiglu(shared["w1"], shared["w3"], shared["w2"], u)
    return out, {"own": own, "margin": margin}


def forward(params, m, tokens, chosen=None, remat=False):
    """tokens [B, T] -> logits [B, T, V], values [B, T], and per expert layer the reference's
    own choice `own` [B, T, expert layers, k] and the margin between its k-th and (k+1)-th
    score. `chosen` ([B, T, expert layers, k]) makes every expert layer follow those choices
    downstream. `remat` recomputes a layer in a backward pass: a memory device of this file,
    no part of the model."""

    def layer(p, x, ids, kinds):
        mixer, ffn = kinds
        u = rms_norm(x, p["op_norm"], m["norm_eps"])
        x = x + (kimi_delta_attention(p["op"], u, m) if mixer == "kda" else latent_attention(p["op"], u, m))
        u = rms_norm(x, p["ffn_norm"], m["norm_eps"])
        if ffn == "dense":
            return x + swiglu(p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"], u), None
        y, info = expert_layer(p["ffn"], u, m, ids)
        return x + y, info

    x = params["embed"][tokens]
    routes, at = [], 0
    for i, kinds in enumerate(layer_kinds(m)):
        fn = jax.checkpoint(layer, static_argnums=(3,)) if remat else layer
        x, info = fn(params[f"layer_{i}"], x, None if chosen is None or kinds[1] == "dense" else chosen[:, :, at], kinds)
        if info is not None:
            routes.append(info)
            at += 1
    x = rms_norm(x, params["norm"], m["norm_eps"])
    own = jnp.stack([r["own"] for r in routes], axis=2) if routes else None
    margin = jnp.stack([r["margin"] for r in routes], axis=2) if routes else None
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
