"""The plain reference of the `laguna` sequence policy (Laguna-XS.2,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json, `model_type: laguna`) and of the PPO
step that trains it: the same equations as `sheeprl_tpu/models/laguna.py` and the sequence flavour of
`algos/ppo/anakin.py`, in plain `jax.numpy` and float32. A full forward over whole sequences with a
masked `[T, T]` softmax on every layer (the window is a mask and nothing else), no cache, no step
form, no blocks of queries, no grouped products (every expert held over every token), no kernels. A copy the
benchmark owns: it imports nothing of `sheeprl_tpu`, and nothing imports it by name (the adapter loads
it by its path). Callers set `jax.default_matmul_precision("highest")`. The env, GAE, Adam and the
minibatch loop are `reference/lfm2_moe.py`'s, the router `reference/deepseek_v3.py`'s (its bias is
held at 0 here), both loaded by path.

`m` is the configuration's `model` block. Layer equations (no bias; `Norm(x) = x * rsqrt(mean(x^2) +
eps) * w`):

- block `h = x + Attn(Norm(x))`, `x' = h + FFN(Norm(h))`; a final Norm before the heads; layer `i`'s
  attention kind, feed-forward kind and query heads are the `i`-th entries of `layer_types`,
  `mlp_layer_types` and `num_attention_heads_per_layer`;
- attention: `q = W_q u` (`n_q` heads of `head_dim`), `k = W_k u`, `v = W_v u` (`num_key_value_heads`
  heads), query head `h` reading key/value head `h // (n_q / n_kv)`; q and k rotated (below); scores
  `q . k / sqrt(head_dim)`, masked to `j <= i` on a full layer and `i - sliding_window < j <= i` on a
  sliding one; `o_h = softmax(scores) v`; the gate a head `o_h <- sigmoid(u W_g)_h o_h`; out `W_o o`;
- rotation, by the layer kind's `rope_parameters`: rotate-half over the first `dim =
  partial_rotary_factor x head_dim` channels of a head (the rest pass), angle `position x inv_i` a
  channel pair, and cos and sin times the attention factor. The default law: `inv_i = theta^(-2i /
  dim)`, factor 1. YaRN: with `extrap_i = theta^(-2i / dim)` and `interp_i = extrap_i / factor`, the
  correction channel of `r` rotations `c(r) = dim ln(P / (2 pi r)) / (2 ln theta)` (`P =
  original_max_position_embeddings`), `low = max(floor(c(beta_fast)), 0)`, `high = min(ceil(c(beta_slow)),
  dim - 1)`, `ramp_i = clip((i - low) / (high - low), 0, 1)`: `inv_i = interp_i ramp_i + extrap_i (1 -
  ramp_i)`, and the factor the given `attention_factor`;
- the dense SwiGLU `W_2 (silu(W_1 u) * W_3 u)`;
- the expert layer: `s = sigmoid(W_r u)`; the k largest of `s + b` (`b = 0`); their weights `s_i`
  over their sum plus 1e-20, times `routed_scaling_factor`; the sum over the chosen experts HELD
  here (`experts_held`), each a SwiGLU; plus ONE ungated SwiGLU of `shared_expert_intermediate_size`.
  What absent experts would add is left out, and that partial result goes on.

Departures from the published model, each under `assumed` in the configuration's file: the gate a
head, sigmoid scores with a zero bias, N(0, 0.02) matrices and unit norm weights, a linear value head
on the final hidden state. A memory device of this file, no part of the model: `forward(remat=True)`
recomputes a layer in its backward pass.
"""

from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np


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


# ---------------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------------
def layer_kinds(m: dict):
    """(attention kind, feed-forward kind, query heads) a layer, from the three published lists."""
    return list(zip(m["layer_types"], m["mlp_layer_types"], m["num_attention_heads_per_layer"]))


def init_params(m: dict, seed):
    """The weights from the seed, in the program's layout (`models/laguna.py::init_params`)."""
    h, d, nkv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    key = jax.random.PRNGKey(seed)
    count = [0]

    def normal(*shape):
        count[0] += 1
        return INIT_STD * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    params = {"embed": normal(m["vocab_size"], h)}
    for i, (_, ffn, nq) in enumerate(layer_kinds(m)):
        layer = {"op_norm": ones(h), "ffn_norm": ones(h),
                 "op": {"wq": normal(h, nq * d), "wk": normal(h, nkv * d), "wv": normal(h, nkv * d),
                        "wg": normal(h, nq), "wo": normal(nq * d, h)}}
        if ffn == "dense":
            f = m["intermediate_size"]
            layer["ffn"] = {"w1": normal(h, f), "w3": normal(h, f), "w2": normal(f, h)}
        else:
            f, n, fs = m["moe_intermediate_size"], m["experts_held"][1], m["shared_expert_intermediate_size"]
            layer["ffn"] = {"router": normal(h, m["num_experts_routed"]),
                            "bias": jnp.zeros((m["num_experts_routed"],), jnp.float32),
                            "w1": normal(n, h, f), "w3": normal(n, h, f), "w2": normal(n, f, h),
                            "shared": {"w1": normal(h, fs), "w3": normal(h, fs), "w2": normal(fs, h)}}
        params[f"layer_{i}"] = layer
    params["norm"] = ones(h)
    params["lm_head"] = normal(h, m["vocab_size"])
    params["value_head"] = normal(h, 1)
    return params


# ---------------------------------------------------------------------------------
# layers, over whole sequences [B, T, H]
# ---------------------------------------------------------------------------------
def rotary(rope: dict, head_dim: int):
    """(inverse frequencies [dim / 2], the factor on cos and sin) of one layer kind, in float32 as the
    transformers library computes them."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = np.float32(rope["rope_theta"])
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    if rope.get("rope_type", "default") != "yarn":
        return np.float32(1.0) / pos_freqs, 1.0
    factor, positions = float(rope["factor"]), float(rope["original_max_position_embeddings"])
    corr = lambda r: dim * math.log(positions / (2 * math.pi * r)) / (2 * math.log(float(theta)))  # noqa: E731
    low, high = max(math.floor(corr(rope["beta_fast"])), 0), min(math.ceil(corr(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / np.float32(max(high - low, 1e-3)), 0.0, 1.0)
    extrap, interp = np.float32(1.0) / pos_freqs, np.float32(1.0) / (np.float32(factor) * pos_freqs)
    return (interp * ramp + extrap * (np.float32(1.0) - ramp)).astype(np.float32), float(rope["attention_factor"])


def rotate(x, rope: dict):
    """x [B, T, heads, d] at positions 0 .. T-1."""
    inv, factor = rotary(rope, x.shape[-1])
    dim = 2 * inv.shape[0]
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    angles = jnp.concatenate([angles, angles], -1)[:, None]
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    turned, kept = x[..., :dim], x[..., dim:]
    half = dim // 2
    flipped = jnp.concatenate([-turned[..., half:], turned[..., :half]], axis=-1)
    return jnp.concatenate([turned * cos + flipped * sin, kept], axis=-1)


def attention(p, u, kind: str, nq: int, m: dict):
    bsz, t, _ = u.shape
    d, nkv = m["head_dim"], m["num_key_value_heads"]
    rope = m["rope_parameters"][kind]
    q = rotate((u @ p["wq"]).reshape(bsz, t, nq, d), rope)
    k = jnp.repeat(rotate((u @ p["wk"]).reshape(bsz, t, nkv, d), rope), nq // nkv, axis=2)
    v = jnp.repeat((u @ p["wv"]).reshape(bsz, t, nkv, d), nq // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    i, j = np.arange(t)[:, None], np.arange(t)[None]
    mask = j <= i
    if kind == "sliding_attention":
        mask = mask & (j > i - m["sliding_window"])
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v) * jax.nn.sigmoid(u @ p["wg"])[..., None]
    return out.reshape(bsz, t, nq * d) @ p["wo"]


def expert_layer(p, u, m, chosen=None):
    """The held experts' part of the layer: every expert held over every token, weighted by the
    token's weight for it (0 where it was not chosen), in one product a weight over the experts;
    then the shared expert's one ungated SwiGLU, which every share computes whole."""
    ids, w, own, margin = route(p, u, m, chosen)
    e0, n = m["experts_held"]
    weight = jnp.sum(jnp.where(ids[..., None] == e0 + jnp.arange(n), w[..., None], 0.0), axis=-2)  # [..., held]
    hidden = jax.nn.silu(jnp.einsum("...h,ehf->...ef", u, p["w1"])) * jnp.einsum("...h,ehf->...ef", u, p["w3"])
    out = jnp.einsum("...ef,efh,...e->...h", hidden, p["w2"], weight)
    shared = p["shared"]
    return out + swiglu(shared["w1"], shared["w3"], shared["w2"], u), {"own": own, "margin": margin}


def forward(params, m, tokens, chosen=None, remat=False):
    """tokens [B, T] -> logits [B, T, V], values [B, T], and per expert layer the reference's
    own choice `own` [B, T, expert layers, k] and the margin between its k-th and (k+1)-th
    score. `chosen` ([B, T, expert layers, k]) makes every expert layer follow those choices
    downstream. `remat` recomputes a layer in a backward pass: a memory device of this file,
    no part of the model."""

    def layer(p, x, ids, kinds):
        kind, ffn, nq = kinds
        u = rms_norm(x, p["op_norm"], m["norm_eps"])
        x = x + attention(p["op"], u, kind, nq, m)
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
