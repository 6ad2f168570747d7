"""The plain reference of the `lfm2_moe` sequence policy (LFM2-8B-A1B,
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json) and of the PPO step
that trains it: the same equations as `sheeprl_tpu/models/lfm2.py` and the sequence
flavour of `algos/ppo/anakin.py`, in plain `jax.numpy` and float32. A full forward over
whole sequences: no cache, no step form, no grouped products (a loop over the experts
held), no kernels. A copy the benchmark owns: it imports nothing of `sheeprl_tpu`, and
nothing imports it by name (the adapter loads it by its path). Callers set
`jax.default_matmul_precision("highest")`.

`m` is the configuration's `model` block. A layer that holds experts `[e0, e0 + n)`
routes over all `num_experts_routed`, normalises the four chosen weights over all four,
and sums over the chosen experts it holds; what the absent experts would add is left
out, and that partial result goes on to the next layer.

Departures from the published model, each under `assumed` in the configuration's file:
embedding and head are two matrices, a linear value head reads the final hidden state,
the expert bias `b` is drawn from the seed and never trained, weights are N(0, 0.02).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
BIAS_STD = 0.05
WEIGHT_SUM_EPS = 1e-20  # the epsilon in the sum of the four chosen weights (sigmoids: the sum is never near 0)


# ---------------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------------
def layer_kinds(m: dict):
    """(operator, feed-forward) of each layer held: `conv`/`full_attention`, `dense`/`moe`."""
    return [(op, "dense" if i < m["num_dense_layers"] else "moe") for i, op in enumerate(m["layer_types"])]


def init_params(m: dict, seed):
    """The weights from the seed, in the program's layout (`models/lfm2.py::init_params`)."""
    h, d = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    key = jax.random.PRNGKey(seed)
    count = [0]

    def normal(*shape, std=INIT_STD):
        count[0] += 1
        return std * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

    params = {"embed": normal(m["vocab_size"], h)}
    for i, (op, ffn) in enumerate(layer_kinds(m)):
        layer = {"op_norm": jnp.ones((h,), jnp.float32), "ffn_norm": jnp.ones((h,), jnp.float32)}
        if op == "conv":
            layer["op"] = {"w_in": normal(h, 3 * h), "w_conv": normal(m["conv_L_cache"], h, std=0.3), "w_out": normal(h, h)}
        else:
            layer["op"] = {"wq": normal(h, nq * d), "wk": normal(h, nkv * d), "wv": normal(h, nkv * d),
                           "wo": normal(nq * d, h), "q_norm": jnp.ones((d,), jnp.float32),
                           "k_norm": jnp.ones((d,), jnp.float32)}
        if ffn == "dense":
            f = m["intermediate_size"]
            layer["ffn"] = {"w1": normal(h, f), "w3": normal(h, f), "w2": normal(f, h)}
        else:
            f, n = m["moe_intermediate_size"], m["experts_held"][1]
            layer["ffn"] = {"router": normal(h, m["num_experts_routed"]),
                            "bias": normal(m["num_experts_routed"], std=BIAS_STD),
                            "w1": normal(n, h, f), "w3": normal(n, h, f), "w2": normal(n, f, h)}
        params[f"layer_{i}"] = layer
    params["norm"] = jnp.ones((h,), jnp.float32)
    params["lm_head"] = normal(h, m["vocab_size"])
    params["value_head"] = normal(h, 1)
    return params


# ---------------------------------------------------------------------------------
# layers, over whole sequences [B, T, H]
# ---------------------------------------------------------------------------------
def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def short_conv(p, u):
    """`[B, C, z] = split(W_in u)`, `y = C * causal_depthwise_conv(B * z)`, `W_out y`; tap
    `j` of the kernel multiplies the input `K - 1 - j` steps back."""
    b, c, z = jnp.split(u @ p["w_in"], 3, axis=-1)
    bz = b * z
    taps = p["w_conv"].shape[0]
    padded = jnp.pad(bz, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + bz.shape[1]] * p["w_conv"][j] for j in range(taps))
    return (c * y) @ p["w_out"]


def rotate_half(x):
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-b, a], axis=-1)


def rope(x, theta):
    """x: [B, T, heads, d], positions 0..T-1, rotate-half over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    return x * jnp.cos(angles) + rotate_half(x) * jnp.sin(angles)


def attention(p, u, m):
    bsz, t, _ = u.shape
    nq, nkv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = rope(rms_norm((u @ p["wq"]).reshape(bsz, t, nq, d), p["q_norm"], m["norm_eps"]), m["rope_theta"])
    k = rope(rms_norm((u @ p["wk"]).reshape(bsz, t, nkv, d), p["k_norm"], m["norm_eps"]), m["rope_theta"])
    v = (u @ p["wv"]).reshape(bsz, t, nkv, d)
    k, v = (jnp.repeat(a, nq // nkv, axis=2) for a in (k, v))  # query head i reads key/value head i // 4
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(bsz, t, nq * d) @ p["wo"]


def swiglu(w1, w3, w2, u):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def route(p, u, m, chosen=None):
    """Scores over all routed experts, the four chosen (by `s + b`, or `chosen` where the
    caller follows another side's choice) and their weights (`s` without `b`, over their
    sum). As published: `use_expert_bias` and `norm_topk_prob` true, `routed_scaling_factor` 1."""
    s = jax.nn.sigmoid(u @ p["router"])
    sel = s + p["bias"]
    own = jax.lax.top_k(sel, m["num_experts_per_tok"])[1]
    ids = own if chosen is None else chosen
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + WEIGHT_SUM_EPS)
    ordered = jnp.sort(sel, axis=-1)[..., ::-1]
    margin = ordered[..., m["num_experts_per_tok"] - 1] - ordered[..., m["num_experts_per_tok"]]
    return ids, w, own, margin


def expert_layer(p, u, m, chosen=None):
    """The held experts' part of the layer: a loop over the experts held, each over every
    token, weighted by the token's weight for it (0 where it was not chosen)."""
    ids, w, own, margin = route(p, u, m, chosen)
    e0, n = m["experts_held"]
    out = jnp.zeros_like(u)
    for e in range(n):
        weight = jnp.sum(jnp.where(ids == e0 + e, w, 0.0), axis=-1, keepdims=True)
        out = out + weight * swiglu(p["w1"][e], p["w3"][e], p["w2"][e], u)
    return out, {"own": own, "margin": margin}


def forward(params, m, tokens, chosen=None):
    """tokens [B, T] -> logits [B, T, V], values [B, T], and per expert layer the
    reference's own choice `own` [B, T, 4] and the margin between its fourth and fifth
    score. `chosen` ([B, T, layers with experts, 4]) makes every expert layer follow
    those choices downstream."""
    x = params["embed"][tokens]
    routes, at = [], 0
    for i, (op, ffn) in enumerate(layer_kinds(m)):
        p = params[f"layer_{i}"]
        u = rms_norm(x, p["op_norm"], m["norm_eps"])
        x = x + (short_conv(p["op"], u) if op == "conv" else attention(p["op"], u, m))
        u = rms_norm(x, p["ffn_norm"], m["norm_eps"])
        if ffn == "dense":
            x = x + swiglu(p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"], u)
        else:
            y, info = expert_layer(p["ffn"], u, m, None if chosen is None else chosen[:, :, at])
            x, at = x + y, at + 1
            routes.append(info)
    x = rms_norm(x, params["norm"], m["norm_eps"])
    own = jnp.stack([r["own"] for r in routes], axis=2) if routes else None
    margin = jnp.stack([r["margin"] for r in routes], axis=2) if routes else None
    return x @ params["lm_head"], (x @ params["value_head"])[..., 0], own, margin


# ---------------------------------------------------------------------------------
# the env (`envs/jax/tokens.py`: copy the prompt back), from its first state and the actions
# ---------------------------------------------------------------------------------
def copy_env(prompt, prompt_len, actions):
    """What the token env shows and pays for `actions` [E, T], given each episode's `prompt`
    [E, >= P] and its length `prompt_len` [E]: the observations (the prompt, one id a step,
    then the agent's last action), the rewards (1 where a response step's action is the
    prompt's token at `(t - P) mod P`), the mask (1 on response steps) and the dones (the
    last step). Plain numpy, all [E, T]."""
    prompt, p, actions = np.asarray(prompt), np.asarray(prompt_len)[:, None], np.asarray(actions)
    t = np.arange(actions.shape[1])[None]
    answers = t >= p
    last_action = np.concatenate([np.zeros_like(actions[:, :1]), actions[:, :-1]], axis=1)
    fed = np.take_along_axis(prompt, np.minimum(t, prompt.shape[1] - 1), axis=1)
    tokens = np.where(answers, last_action, fed)
    target = np.take_along_axis(prompt, np.mod(t - p, p), axis=1)
    rewards = (answers & (actions == target)).astype(np.float32)
    dones = np.broadcast_to(t == actions.shape[1] - 1, actions.shape).astype(np.float32)
    return {"tokens": tokens, "rewards": rewards, "mask": answers.astype(np.float32), "dones": dones}


# ---------------------------------------------------------------------------------
# the PPO step (algos/ppo/loss.py over whole sequences, masked steps left out)
# ---------------------------------------------------------------------------------
def gae(rewards, values, dones, next_value, gamma, lam):
    """[T, B] arrays; `dones[t]` ends the episode at step t."""
    adv, last = [None] * rewards.shape[0], jnp.zeros_like(rewards[0])
    for t in reversed(range(rewards.shape[0])):
        nxt = next_value if t == rewards.shape[0] - 1 else values[t + 1]
        nonterminal = 1.0 - dones[t]
        delta = rewards[t] + gamma * nxt * nonterminal - values[t]
        last = delta + gamma * lam * nonterminal * last
        adv[t] = last
    adv = jnp.stack(adv)
    return adv + values, adv


def loss_terms(params, m, batch, chosen, clip_coef):
    """Sums over the block's unmasked steps of the three PPO terms (the caller divides by
    the minibatch's count of unmasked steps, so blocks add up)."""
    logits, values, own, margin = forward(params, m, batch["tokens"], chosen)
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
    """`grads` plus the gradient of one block's share of a minibatch's loss (every term is a
    sum over the block's unmasked steps over the minibatch's `count` of them, so blocks add
    up), the block's share of the three loss parts, and its routing. Jit it once with `m`
    bound: nothing of a run is a constant of it."""

    def block_loss(p):
        terms, aux = loss_terms(p, m, part, chosen, clip_coef)
        terms = terms / count
        return terms[0] + m["vf_coef"] * terms[1] + ent_coef * terms[2], (terms, aux)

    g, (terms, aux) = jax.grad(block_loss, has_aux=True)(params)
    return jax.tree_util.tree_map(jnp.add, grads, g), terms, aux


def minibatch_grad(step, params, batch, chosen, clip_coef, ent_coef, block: int):
    """Loss parts and gradient of one minibatch of whole sequences, `block` sequences at a
    time through `step` (a jitted `block_grad` with its `m` bound)."""
    count = jnp.maximum(batch["mask"].sum(), 1.0)
    grads, parts, owns, margins = jax.tree_util.tree_map(jnp.zeros_like, params), 0.0, [], []
    for lo in range(0, batch["tokens"].shape[0], block):
        part = {k: v[lo:lo + block] for k, v in batch.items()}
        grads, terms, (own, margin) = step(params, grads, part, chosen[lo:lo + block], count, clip_coef, ent_coef)
        parts = parts + terms
        owns.append(np.asarray(own))
        margins.append(np.asarray(margin))
    return grads, parts, np.concatenate(owns), np.concatenate(margins)


def adam_init(params):
    return {"mu": jax.tree_util.tree_map(jnp.zeros_like, params),
            "nu": jax.tree_util.tree_map(jnp.zeros_like, params), "count": jnp.zeros((), jnp.float32)}


def adam_step(params, opt, grads, lr, eps, b1=0.9, b2=0.999):
    """optax.adam: moments, bias correction, `lr * m_hat / (sqrt(v_hat) + eps)`."""
    count = opt["count"] + 1
    mu = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, opt["nu"], grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps), params, mu, nu)
    return params, {"mu": mu, "nu": nu, "count": count}
