"""The plain reference of the `deepseek_v3` sequence policy (Moonlight-16B-A3B,
https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json, `model_type:
deepseek_v3`) and of the PPO step that trains it: the same equations as
`sheeprl_tpu/models/deepseek_v3.py` and the sequence flavour of `algos/ppo/anakin.py`, in plain
`jax.numpy` and float32. A full forward over whole sequences with the latent attention in its
EXPANDED form only (per-head keys and values made from the latent, as published): no cache, no
step form, no absorbed products, no grouped products (a loop over the experts held), no
kernels. The program's rollout decodes in the absorbed form through a latent cache, so the two
sides are independent forms of the same mathematics. A copy the benchmark owns: it imports
nothing of `sheeprl_tpu`, and nothing imports it by name (the adapter loads it by its path).
Callers set `jax.default_matmul_precision("highest")`. The env, GAE, Adam and the minibatch
loop are `reference/lfm2_moe.py`'s, loaded by its path.

`m` is the configuration's `model` block. Layer equations (no bias in any projection; `Norm(x)
= x * rsqrt(mean(x^2) + eps) * w`):

- block `h = x + MLA(Norm(x))`, `x' = h + FFN(Norm(h))`; a final Norm before the heads; the
  first `first_k_dense_replace` layers' FFN is a dense SwiGLU, every later one the expert layer;
- `MLA`: `q = W_q u`, a head's `[q_nope | q_pe]`; `[c | k_pe] = W_kva u`; `c <- Norm_c(c)`;
  `[k_nope_h | v_h] = W_kvb c` a head; rotate-half RoPE on each head's `q_pe` and on the one
  `k_pe` all heads share; `k_h = [k_nope_h | k_pe]`; causal `softmax(q_h k_h^T / sqrt(nope +
  rope))`; `W_o concat_h(attn_h v_h)`;
- expert layer: `s = sigmoid(W_g u)`; the k largest of `s + b`; their weights `s_i` without
  `b`, over their sum plus 1e-20, times `routed_scaling_factor`; the sum over the chosen
  experts HELD here (`experts_held`), each a SwiGLU; plus ONE ungated SwiGLU of width
  `n_shared_experts x moe_intermediate_size`. What absent experts would add is left out, and
  that partial result goes on.

Departures from the published model, each under `assumed` in the configuration's file:
rotate-half over the rotary channels (the checkpoint stores them interleaved: a fixed
permutation of `W_q`'s and `W_kva`'s rotary columns), the bias `b` drawn from the seed and
never trained, N(0, 0.02) matrices and unit norm weights, a linear value head on the final
hidden state, the auxiliary sequence balance loss left out.
"""

from __future__ import annotations

import importlib.util
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
swiglu, rotate_half, rms_norm = _lm.swiglu, _lm.rotate_half, _lm.rms_norm

INIT_STD = 0.02
BIAS_STD = 0.05
WEIGHT_SUM_EPS = 1e-20  # the epsilon in the sum of the chosen weights (sigmoids: the sum is never near 0)


# ---------------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------------
def layer_kinds(m: dict):
    return ["dense" if i < m["first_k_dense_replace"] else "moe" for i in range(m["num_hidden_layers"])]


def init_params(m: dict, seed):
    """The weights from the seed, in the program's layout (`models/deepseek_v3.py::init_params`)."""
    h, nh, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    key = jax.random.PRNGKey(seed)
    count = [0]

    def normal(*shape, std=INIT_STD):
        count[0] += 1
        return std * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params = {"embed": normal(m["vocab_size"], h)}
    for i, ffn in enumerate(layer_kinds(m)):
        layer = {"op_norm": ones(h), "ffn_norm": ones(h)}
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
            if m["n_shared_experts"]:
                fs = m["n_shared_experts"] * f
                layer["ffn"]["shared"] = {"w1": normal(h, fs), "w3": normal(h, fs), "w2": normal(fs, h)}
        params[f"layer_{i}"] = layer
    params["norm"] = ones(h)
    params["lm_head"] = normal(h, m["vocab_size"])
    params["value_head"] = normal(h, 1)
    return params


# ---------------------------------------------------------------------------------
# layers, over whole sequences [B, T, H]
# ---------------------------------------------------------------------------------
def rope(x, theta):
    """x: [B, T, heads, d], positions 0..T-1, rotate-half over the whole of d."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    return x * jnp.cos(angles) + rotate_half(x) * jnp.sin(angles)


def latent_attention(p, u, m):
    bsz, t, _ = u.shape
    nh, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = (u @ p["wq"]).reshape(bsz, t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], m["rope_theta"])], axis=-1)
    kva = u @ p["w_kva"]
    c = rms_norm(kva[..., :r], p["kv_norm"], m["norm_eps"])
    k_pe = rope(kva[..., r:].reshape(bsz, t, 1, dr), m["rope_theta"])
    kv = (c @ p["w_kvb"]).reshape(bsz, t, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (bsz, t, nh, dr))], axis=-1)  # every head reads the one k_pe
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dn + dr))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:]).reshape(bsz, t, nh * dv) @ p["wo"]


def route(p, u, m, chosen=None):
    """Sigmoid scores over all routed experts, the k chosen (by `s + b`, or `chosen` where the
    caller follows another side's choice), their weights (`s` without `b`, over their sum,
    times the routed scale), the reference's own choice and the margin between its k-th and
    (k+1)-th of `s + b`."""
    s = jax.nn.sigmoid(u @ p["router"])
    sel = s + p["bias"]
    k = m["num_experts_per_tok"]
    own = jax.lax.top_k(sel, k)[1]
    ids = own if chosen is None else chosen
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + WEIGHT_SUM_EPS) * m["routed_scaling_factor"]
    ordered = jnp.sort(sel, axis=-1)[..., ::-1]
    return ids, w, own, ordered[..., k - 1] - ordered[..., k]


def expert_layer(p, u, m, chosen=None):
    """The held experts' part of the layer: a loop over the experts held, each over every
    token, weighted by the token's weight for it (0 where it was not chosen); then the shared
    experts' one SwiGLU, ungated, which every share computes whole."""
    ids, w, own, margin = route(p, u, m, chosen)
    e0, n = m["experts_held"]
    out = jnp.zeros_like(u)
    for e in range(n):
        weight = jnp.sum(jnp.where(ids == e0 + e, w, 0.0), axis=-1, keepdims=True)
        out = out + weight * swiglu(p["w1"][e], p["w3"][e], p["w2"][e], u)
    if m["n_shared_experts"]:
        shared = p["shared"]
        out = out + swiglu(shared["w1"], shared["w3"], shared["w2"], u)
    return out, {"own": own, "margin": margin}


def forward(params, m, tokens, chosen=None, remat=False):
    """tokens [B, T] -> logits [B, T, V], values [B, T], and per expert layer the reference's
    own choice `own` [B, T, expert layers, k] and the margin between its k-th and (k+1)-th
    score. `chosen` ([B, T, expert layers, k]) makes every expert layer follow those choices
    downstream. `remat` recomputes a layer in a backward pass: a memory device of this file,
    no part of the model."""

    def layer(p, x, ids, ffn):
        x = x + latent_attention(p["op"], rms_norm(x, p["op_norm"], m["norm_eps"]), m)
        u = rms_norm(x, p["ffn_norm"], m["norm_eps"])
        if ffn == "dense":
            return x + swiglu(p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"], u), None
        y, info = expert_layer(p["ffn"], u, m, ids)
        return x + y, info

    x = params["embed"][tokens]
    routes, at = [], 0
    for i, ffn in enumerate(layer_kinds(m)):
        fn = jax.checkpoint(layer, static_argnums=(3,)) if remat else layer
        x, info = fn(params[f"layer_{i}"], x, None if chosen is None or ffn == "dense" else chosen[:, :, at], ffn)
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
