"""Plain reference of the Dreamer-V3 gradient step, for the comparison that decides
`correct`. Straightforward `jax.numpy`, float32, matmuls at `highest`; no kernels,
no flax, no optax, nothing imported from the program. It follows Hafner et al.
2023 (Dreamer-V3) as sheeprl v0.5.6 implements it, and reads its weights in the
program's published checkpoint layout (the names flax gives the modules), which is
the one thing the two sides have to share to be compared leaf by leaf.

Everything is a function of `m`, the `model` block of a configuration file under
`perfbench/configs/`. The weights are made here from a seed (`init_params`); the
harness hands the same tree to the program, so neither side takes weights from the
other. The step consumes PRNG keys in the order the published algorithm's JAX port
does (one split per use), so that both sides draw the same categorical samples.

Departures from the paper, all of them the source's: the continue head's mode is
`p > 0.5`; the moments use `jnp.quantile`; the critic's target network is updated
before the step, with tau 1 at step 0.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_TRUNC_STD = 0.87962566103423978  # std of a unit normal truncated at +-2


# ---------------------------------------------------------------------------------
# shapes and weights
# ---------------------------------------------------------------------------------
def derived(m):
    """Sizes that follow from the configuration's own."""
    stages = int(math.log2(m["screen_size"])) - 2
    spatial = m["screen_size"] // (2**stages)
    top = (2 ** (stages - 1)) * m["cnn_channels_multiplier"]
    stoch = m["stochastic_size"] * m["discrete_size"]
    cnn_out = spatial * spatial * top if m["cnn_keys"] else 0
    mlp_in = sum(m["mlp_keys"].values())
    embed = cnn_out + (m["dense_units"] if mlp_in else 0)
    return dict(
        stages=stages,
        spatial=spatial,
        top=top,
        stoch=stoch,
        latent=stoch + m["recurrent_state_size"],
        cnn_out=cnn_out,
        mlp_in=mlp_in,
        embed=embed,
        image_channels=sum(m["cnn_keys"].values()),
    )


def _stack(shapes, d_in, units, layers):
    for i in range(layers):
        shapes[f"Dense_{i}"] = {"kernel": ((d_in if i == 0 else units, units), "hafner")}
        shapes[f"LayerNorm_{i}"] = {"scale": ((units,), "ones"), "bias": ((units,), "zeros")}
    return shapes


def _head(d_in, units, layers, d_out, head_init):
    return {
        "DenseStack_0": _stack({}, d_in, units, layers),
        "Dense_0": {"kernel": ((units, d_out), head_init), "bias": ((d_out,), "zeros")},
    }


def param_shapes(m):
    """`{name: ... (shape, init)}` in the program's checkpoint layout."""
    d = derived(m)
    units, layers, mult = m["dense_units"], m["mlp_layers"], m["cnn_channels_multiplier"]
    R, hidden, A = m["recurrent_state_size"], m["hidden_size"], m["actions"]
    wm = {}
    enc = {}
    if m["cnn_keys"]:
        cnn = {}
        c_in = d["image_channels"]
        for i in range(d["stages"]):
            c_out = (2**i) * mult
            cnn[f"Conv_{i}"] = {"kernel": ((4, 4, c_in, c_out), "hafner")}
            cnn[f"LayerNorm_{i}"] = {"scale": ((c_out,), "ones"), "bias": ((c_out,), "zeros")}
            c_in = c_out
        enc["cnn_encoder"] = cnn
    if d["mlp_in"]:
        enc["mlp_encoder"] = {"DenseStack_0": _stack({}, d["mlp_in"], units, layers)}
    wm["encoder"] = enc
    wm["recurrent_model"] = {
        "DenseStack_0": _stack({}, d["stoch"] + A, units, 1),
        "LayerNormGRUCell_0": {
            "kernel": ((units + R, 3 * R), "hafner"),
            "ln_scale": ((3 * R,), "ones"),
            "ln_bias": ((3 * R,), "zeros"),
        },
    }
    wm["representation_model"] = _head(R + d["embed"], hidden, 1, d["stoch"], "uniform")
    wm["transition_model"] = _head(R, hidden, 1, d["stoch"], "uniform")
    obs = {}
    if m["cnn_keys"]:
        dec = {
            "Dense_0": {
                "kernel": ((d["latent"], d["top"] * d["spatial"] ** 2), "hafner"),
                "bias": ((d["top"] * d["spatial"] ** 2,), "zeros"),
            }
        }
        c_in = d["top"]
        for i in range(d["stages"] - 1):
            c_out = (2 ** (d["stages"] - 2 - i)) * mult
            dec[f"ConvTranspose_{i}"] = {"kernel": ((4, 4, c_in, c_out), "hafner")}
            dec[f"LayerNorm_{i}"] = {"scale": ((c_out,), "ones"), "bias": ((c_out,), "zeros")}
            c_in = c_out
        dec[f"ConvTranspose_{d['stages'] - 1}"] = {
            "kernel": ((4, 4, c_in, d["image_channels"]), "uniform"),
            "bias": ((d["image_channels"],), "zeros"),
        }
        obs["cnn_decoder"] = dec
    if m["mlp_decoder_keys"]:
        mlp = {"DenseStack_0": _stack({}, d["latent"], units, layers)}
        for i, k in enumerate(m["mlp_decoder_keys"]):
            dim = m["mlp_keys"][k]
            mlp[f"Dense_{i}"] = {"kernel": ((units, dim), "uniform"), "bias": ((dim,), "zeros")}
        obs["mlp_decoder"] = mlp
    wm["observation_model"] = obs
    wm["reward_model"] = _head(d["latent"], units, layers, m["bins"], "zeros")
    wm["continue_model"] = _head(d["latent"], units, layers, 1, "uniform")
    wm["initial_recurrent_state"] = ((R,), "zeros")
    critic = _head(d["latent"], units, layers, m["bins"], "zeros")
    return {
        "world_model": wm,
        "actor": _head(d["latent"], units, layers, A, "uniform"),
        "critic": critic,
        "target_critic": critic,
    }


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _make(key, shape, kind):
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    receptive = math.prod(shape[:-2]) if len(shape) > 2 else 1
    fan_avg = (shape[-2] * receptive + shape[-1] * receptive) / 2.0
    if kind == "hafner":  # truncated normal, variance 1 / fan_avg
        std = math.sqrt(1.0 / fan_avg) / _TRUNC_STD
        return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std
    if kind == "uniform":  # U(-l, l), l = sqrt(3 / fan_avg)
        limit = math.sqrt(3.0 / fan_avg)
        return jax.random.uniform(key, shape, jnp.float32, -limit, limit)
    raise ValueError(kind)


def init_params(m, seed):
    """All weights from the seed, in one traceable function (jit it once)."""
    shapes = param_shapes(m)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_spec)
    key = jax.random.PRNGKey(seed)
    out = [_make(jax.random.fold_in(key, i), shape, kind) for i, (shape, kind) in enumerate(leaves)]
    params = jax.tree_util.tree_unflatten(treedef, out)
    # the target critic starts as a copy of the critic (its own buffers)
    params["target_critic"] = jax.tree_util.tree_map(jnp.copy, params["critic"])
    return params


# ---------------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------------
def _ln(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def _dense_stack(p, x, layers, eps):
    for i in range(layers):
        x = x @ p[f"Dense_{i}"]["kernel"]
        x = jax.nn.silu(_ln(x, p[f"LayerNorm_{i}"]["scale"], p[f"LayerNorm_{i}"]["bias"], eps))
    return x


def _mlp_head(p, x, layers, eps):
    x = _dense_stack(p["DenseStack_0"], x, layers, eps)
    return x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"]


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * jnp.expm1(jnp.abs(x))


def encode(m, p, obs):
    """obs: images `[..., C, H, W]` in [-0.5, 0.5], vectors `[..., D]`."""
    d, eps = derived(m), m["layer_norm_eps"]
    outs = []
    if m["cnn_keys"]:
        x = jnp.concatenate([obs[k] for k in m["cnn_keys"]], axis=-3)
        lead = x.shape[:-3]
        x = jnp.moveaxis(x.reshape(-1, *x.shape[-3:]), -3, -1)
        for i in range(d["stages"]):
            x = lax.conv_general_dilated(
                x,
                p["cnn_encoder"][f"Conv_{i}"]["kernel"],
                (2, 2),
                ((1, 1), (1, 1)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            norm = p["cnn_encoder"][f"LayerNorm_{i}"]
            x = jax.nn.silu(_ln(x, norm["scale"], norm["bias"], eps))
        outs.append(x.reshape(*lead, -1))
    if d["mlp_in"]:
        x = jnp.concatenate([symlog(obs[k]) for k in m["mlp_keys"]], axis=-1)
        outs.append(_dense_stack(p["mlp_encoder"]["DenseStack_0"], x, m["mlp_layers"], eps))
    return jnp.concatenate(outs, axis=-1)


def decode(m, p, latent):
    d, eps = derived(m), m["layer_norm_eps"]
    out = {}
    if m["cnn_keys"]:
        q = p["cnn_decoder"]
        x = latent @ q["Dense_0"]["kernel"] + q["Dense_0"]["bias"]
        lead = x.shape[:-1]
        x = x.reshape(-1, d["spatial"], d["spatial"], d["top"])
        for i in range(d["stages"]):
            layer = q[f"ConvTranspose_{i}"]
            x = lax.conv_transpose(
                x, layer["kernel"], (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
            )
            if i < d["stages"] - 1:
                norm = q[f"LayerNorm_{i}"]
                x = jax.nn.silu(_ln(x, norm["scale"], norm["bias"], eps))
            else:
                x = x + layer["bias"]
        x = jnp.moveaxis(x, -1, -3)
        x = x.reshape(*lead, *x.shape[-3:])
        start = 0
        for k, c in m["cnn_keys"].items():
            out[k] = x[..., start : start + c, :, :]
            start += c
    if m["mlp_decoder_keys"]:
        q = p["mlp_decoder"]
        x = _dense_stack(q["DenseStack_0"], latent, m["mlp_layers"], eps)
        for i, k in enumerate(m["mlp_decoder_keys"]):
            out[k] = x @ q[f"Dense_{i}"]["kernel"] + q[f"Dense_{i}"]["bias"]
    return out


def unimix(m, logits, classes):
    shaped = logits.reshape(*logits.shape[:-1], -1, classes)
    if m["unimix"] > 0:
        probs = jax.nn.softmax(shaped, axis=-1)
        probs = (1 - m["unimix"]) * probs + m["unimix"] / classes
        shaped = jnp.log(probs)
    return shaped.reshape(*shaped.shape[:-2], -1)


def sample_onehot(logits, classes, key):
    """Straight-through one-hot sample of `[..., S*classes]` logits."""
    shaped = logits.reshape(*logits.shape[:-1], -1, classes)
    idx = jax.random.categorical(key, shaped, axis=-1)
    onehot = jax.nn.one_hot(idx, classes, dtype=shaped.dtype)
    probs = jax.nn.softmax(shaped, axis=-1)
    out = lax.stop_gradient(onehot) + probs - lax.stop_gradient(probs)
    return out.reshape(*out.shape[:-2], -1)


def recurrent(m, wm, z, a, h):
    """Dense -> LN -> SiLU, then the layer-norm GRU cell (norm over all 3R gates)."""
    eps, R = m["layer_norm_eps"], m["recurrent_state_size"]
    q = wm["recurrent_model"]
    feat = _dense_stack(q["DenseStack_0"], jnp.concatenate([z, a], axis=-1), 1, eps)
    cell = q["LayerNormGRUCell_0"]
    gates = jnp.concatenate([feat, h], axis=-1) @ cell["kernel"]
    gates = _ln(gates, cell["ln_scale"], cell["ln_bias"], eps)
    reset = jax.nn.sigmoid(gates[..., :R])
    cand = jnp.tanh(reset * gates[..., R : 2 * R])
    update = jax.nn.sigmoid(gates[..., 2 * R :] - 1.0)
    return update * cand + (1.0 - update) * h


def transition_logits(m, wm, h):
    return unimix(m, _mlp_head(wm["transition_model"], h, 1, m["layer_norm_eps"]), m["discrete_size"])


def initial_state(m, wm, batch):
    R, D = m["recurrent_state_size"], m["discrete_size"]
    h0 = jnp.broadcast_to(jnp.tanh(wm["initial_recurrent_state"]), (batch, R))
    logits = transition_logits(m, wm, h0)
    shaped = logits.reshape(batch, -1, D)
    z0 = jax.nn.one_hot(jnp.argmax(shaped, axis=-1), D, dtype=logits.dtype).reshape(batch, -1)
    return h0, z0


def observe(m, wm, embedded, actions, is_first, key):
    """Posterior/prior unroll over `[T, B, ...]`."""
    T, B = embedded.shape[:2]
    d, eps = derived(m), m["layer_norm_eps"]
    h0, z0 = initial_state(m, wm, B)
    keys = jax.random.split(key, T)

    def step(carry, inp):
        h, z = carry
        a, e, first, k = inp
        a = (1 - first) * a
        h = (1 - first) * h + first * h0
        z = (1 - first) * z + first * z0
        h = recurrent(m, wm, z, a, h)
        prior = transition_logits(m, wm, h)
        post = _mlp_head(wm["representation_model"], jnp.concatenate([h, e], axis=-1), 1, eps)
        post = unimix(m, post, m["discrete_size"])
        z = sample_onehot(post, m["discrete_size"], k)
        return (h, z), (h, z, post, prior)

    init = (jnp.zeros((B, m["recurrent_state_size"])), jnp.zeros((B, d["stoch"])))
    _, (hs, zs, post, prior) = lax.scan(step, init, (actions, embedded, is_first, keys))
    return hs, zs, post, prior


def twohot_logprob(m, logits, x):
    """log-prob of `x [..., 1]` under the symlog two-hot head; `[...]` out."""
    x = symlog(x)
    bins = jnp.linspace(-20.0, 20.0, m["bins"], dtype=logits.dtype)
    below = jnp.sum((bins <= x).astype(jnp.int32), axis=-1, keepdims=True) - 1
    above = jnp.minimum(below + 1, m["bins"] - 1)
    below = jnp.maximum(below, 0)
    equal = below == above
    to_below = jnp.where(equal, 1, jnp.abs(bins[below] - x))
    to_above = jnp.where(equal, 1, jnp.abs(bins[above] - x))
    total = to_below + to_above
    target = (
        jax.nn.one_hot(below, m["bins"], dtype=logits.dtype) * (to_above / total)[..., None]
        + jax.nn.one_hot(above, m["bins"], dtype=logits.dtype) * (to_below / total)[..., None]
    )[..., 0, :]
    log_pred = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    return jnp.sum(target * log_pred, axis=-1)


def twohot_mean(m, logits):
    bins = jnp.linspace(-20.0, 20.0, m["bins"], dtype=logits.dtype)
    return symexp(jnp.sum(jax.nn.softmax(logits, axis=-1) * bins, axis=-1, keepdims=True))


def categorical_kl(post, prior, classes):
    post = jax.nn.log_softmax(post.reshape(*post.shape[:-1], -1, classes), axis=-1)
    prior = jax.nn.log_softmax(prior.reshape(*prior.shape[:-1], -1, classes), axis=-1)
    return jnp.sum(jnp.exp(post) * (post - prior), axis=(-2, -1))


# ---------------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------------
def world_loss(m, wm, batch, key):
    eps, layers = m["layer_norm_eps"], m["mlp_layers"]
    key, _unused = jax.random.split(key)
    obs = {k: batch[k] / 255.0 - 0.5 for k in m["cnn_keys"]}
    obs.update({k: batch[k] for k in m["mlp_keys"]})
    is_first = batch["is_first"].at[0].set(1.0)
    actions = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], axis=0)
    embedded = encode(m, wm["encoder"], obs)
    hs, zs, post, prior = observe(m, wm, embedded, actions, is_first, key)
    latents = jnp.concatenate([zs, hs], axis=-1)
    recon = decode(m, wm["observation_model"], latents)
    observation_loss = 0.0
    for k in m["cnn_keys"]:
        observation_loss += jnp.sum(jnp.square(recon[k] - obs[k]), axis=(-3, -2, -1))
    for k in m["mlp_decoder_keys"]:
        distance = jnp.square(recon[k] - symlog(obs[k]))
        observation_loss += jnp.sum(jnp.where(distance < 1e-8, 0.0, distance), axis=-1)
    reward_loss = -twohot_logprob(m, _mlp_head(wm["reward_model"], latents, layers, eps), batch["rewards"])
    cont_logits = _mlp_head(wm["continue_model"], latents, layers, eps)
    target = 1.0 - batch["terminated"]
    continue_lp = -jnp.logaddexp(0.0, jnp.where(target > 0.5, -cont_logits, cont_logits)).sum(-1)
    continue_loss = m["continue_scale_factor"] * -continue_lp
    D = m["discrete_size"]
    dyn = m["kl_dynamic"] * jnp.maximum(
        categorical_kl(lax.stop_gradient(post), prior, D), m["kl_free_nats"]
    )
    rep = m["kl_representation"] * jnp.maximum(
        categorical_kl(post, lax.stop_gradient(prior), D), m["kl_free_nats"]
    )
    loss = (m["kl_regularizer"] * (dyn + rep) + observation_loss + reward_loss + continue_loss).mean()
    return loss, (zs, hs)


def actor_sample(m, logits, key):
    (k,) = jax.random.split(key, 1)  # one key per action head; these cells have one
    return sample_onehot(unimix(m, logits, m["actions"]), m["actions"], k)


def imagine(m, wm, actor, z0, h0, key):
    eps, layers = m["layer_norm_eps"], m["mlp_layers"]
    k0, kscan = jax.random.split(key)
    latent0 = jnp.concatenate([z0, h0], axis=-1)
    a0 = actor_sample(m, _mlp_head(actor, lax.stop_gradient(latent0), layers, eps), k0)

    def step(carry, k):
        z, h, a = carry
        h = recurrent(m, wm, z, a, h)
        z = sample_onehot(transition_logits(m, wm, h), m["discrete_size"], k)
        latent = jnp.concatenate([z, h], axis=-1)
        logits = _mlp_head(actor, lax.stop_gradient(latent), layers, eps)
        a = actor_sample(m, logits, jax.random.fold_in(k, 1))
        return (z, h, a), (latent, a)

    _, (latents, actions) = lax.scan(step, (z0, h0, a0), jax.random.split(kscan, m["horizon"]))
    return (
        jnp.concatenate([latent0[None], latents], axis=0),
        jnp.concatenate([a0[None], actions], axis=0),
    )


def lambda_values(rewards, values, continues, lmbda):
    interm = rewards + continues * values * (1 - lmbda)

    def step(ret, inp):
        interm_t, cont_t = inp
        ret = interm_t + cont_t * lmbda * ret
        return ret, ret

    _, rev = lax.scan(step, values[-1], (interm[::-1], continues[::-1]))
    return rev[::-1]


def actor_loss(m, actor, params, zs, hs, true_continue, moments, key):
    eps, layers, gamma = m["layer_norm_eps"], m["mlp_layers"], m["gamma"]
    d = derived(m)
    wm = params["world_model"]
    z0 = lax.stop_gradient(zs).reshape(-1, d["stoch"])
    h0 = lax.stop_gradient(hs).reshape(-1, m["recurrent_state_size"])
    latents, actions = imagine(m, wm, actor, z0, h0, key)
    values = twohot_mean(m, _mlp_head(params["critic"], latents, layers, eps))
    rewards = twohot_mean(m, _mlp_head(wm["reward_model"], latents, layers, eps))
    cont_logits = _mlp_head(wm["continue_model"], latents, layers, eps)
    continues = (jax.nn.sigmoid(cont_logits) > 0.5).astype(cont_logits.dtype)
    continues = jnp.concatenate([true_continue[None], continues[1:]], axis=0)
    lam = lambda_values(rewards[1:], values[1:], continues[1:] * gamma, m["lmbda"])
    discount = lax.stop_gradient(jnp.cumprod(continues * gamma, axis=0) / gamma)
    mo = m["moments"]
    flat = lax.stop_gradient(lam)
    low = mo["decay"] * moments["low"] + (1 - mo["decay"]) * jnp.quantile(flat, mo["low"])
    high = mo["decay"] * moments["high"] + (1 - mo["decay"]) * jnp.quantile(flat, mo["high"])
    invscale = jnp.maximum(1.0 / mo["max"], high - low)
    advantage = (lam - low) / invscale - (values[:-1] - low) / invscale
    logits = unimix(m, _mlp_head(actor, lax.stop_gradient(latents), layers, eps), m["actions"])
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.sum(logp_all * lax.stop_gradient(actions), axis=-1, keepdims=True)
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    objective = logp[:-1] * lax.stop_gradient(advantage)
    loss = -jnp.mean(discount[:-1] * (objective + m["ent_coef"] * entropy[..., None][:-1]))
    return loss, (latents, lam, discount, {"low": low, "high": high})


def critic_loss(m, critic, target, latents, lam, discount):
    eps, layers = m["layer_norm_eps"], m["mlp_layers"]
    logits = _mlp_head(critic, latents[:-1], layers, eps)
    target_values = twohot_mean(m, _mlp_head(target, latents[:-1], layers, eps))
    loss = -twohot_logprob(m, logits, lax.stop_gradient(lam))
    loss = loss - twohot_logprob(m, logits, lax.stop_gradient(target_values))
    return jnp.mean(loss * discount[:-1].squeeze(-1))


# ---------------------------------------------------------------------------------
# optimizer and step
# ---------------------------------------------------------------------------------
def init_opt(params):
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    return {
        g: {"count": jnp.zeros((), jnp.int32), "mu": zeros(params[g]), "nu": zeros(params[g])}
        for g in ("world_model", "actor", "critic")
    }


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree)))


def adam_update(o, grads, state, params):
    """Clip by global norm, then Adam (b1 0.9, b2 0.999, bias-corrected)."""
    norm = _global_norm(grads)
    grads = jax.tree_util.tree_map(
        lambda g: jnp.where(norm < o["clip"], g, g / norm * o["clip"]), grads
    )
    b1, b2 = 0.9, 0.999
    count = state["count"] + 1
    mu = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, state["nu"], grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - o["lr"] * (a / c1) / (jnp.sqrt(b / c2) + o["eps"]), params, mu, nu
    )
    return params, {"count": count, "mu": mu, "nu": nu}


def train_step(m, params, opt, moments, batch, cum, key):
    """One gradient step: target EMA, world model, actor, critic. Returns the new
    state and the three losses."""
    k_world, k_img = jax.random.split(key)
    tau = jnp.where(cum == 0, 1.0, m["tau"])
    do_ema = (cum % m["target_freq"]) == 0
    params = dict(params)
    params["target_critic"] = jax.tree_util.tree_map(
        lambda t, c: jnp.where(do_ema, tau * c + (1 - tau) * t, t),
        params["target_critic"],
        params["critic"],
    )
    (w_loss, (zs, hs)), w_grads = jax.value_and_grad(
        lambda wm: world_loss(m, wm, batch, k_world), has_aux=True
    )(params["world_model"])
    opt = dict(opt)
    params["world_model"], opt["world_model"] = adam_update(
        m["optim"]["world_model"], w_grads, opt["world_model"], params["world_model"]
    )
    true_continue = (1 - batch["terminated"]).reshape(-1, 1)
    (a_loss, (latents, lam, discount, moments)), a_grads = jax.value_and_grad(
        lambda actor: actor_loss(m, actor, params, zs, hs, true_continue, moments, k_img),
        has_aux=True,
    )(params["actor"])
    params["actor"], opt["actor"] = adam_update(
        m["optim"]["actor"], a_grads, opt["actor"], params["actor"]
    )
    latents = lax.stop_gradient(latents)
    c_loss, c_grads = jax.value_and_grad(
        lambda critic: critic_loss(m, critic, params["target_critic"], latents, lam, discount)
    )(params["critic"])
    params["critic"], opt["critic"] = adam_update(
        m["optim"]["critic"], c_grads, opt["critic"], params["critic"]
    )
    losses = {"world_model": w_loss, "actor": a_loss, "critic": c_loss}
    return params, opt, moments, losses


def act_step(m, params, obs, a, h, z, key):
    """One env step of the player: the new recurrent state, the sampled posterior and
    the sampled action. `obs` as `encode` takes it; the key is split three ways
    (carried on, posterior, action) as the published port's player does."""
    eps, layers = m["layer_norm_eps"], m["mlp_layers"]
    wm = params["world_model"]
    _, k_post, k_act = jax.random.split(key, 3)
    embedded = encode(m, wm["encoder"], obs)
    h = recurrent(m, wm, z, a, h)
    post = _mlp_head(wm["representation_model"], jnp.concatenate([h, embedded], axis=-1), 1, eps)
    z = sample_onehot(unimix(m, post, m["discrete_size"]), m["discrete_size"], k_post)
    logits = _mlp_head(params["actor"], jnp.concatenate([z, h], axis=-1), layers, eps)
    return h, z, actor_sample(m, logits, k_act)
