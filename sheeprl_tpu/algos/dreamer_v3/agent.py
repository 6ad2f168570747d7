"""Dreamer-V3 agent, Flax/JAX-native.

Capability parity with the reference agent (sheeprl/algos/dreamer_v3/agent.py:
CNNEncoder:42, MLPEncoder:103, CNNDecoder:154, MLPDecoder:231, RecurrentModel:285,
RSSM:344, PlayerDV3:596, Actor:694, build_agent:937) redesigned for the TPU:

- the RSSM is a set of small Flax modules plus *pure scan functions*
  (`dynamic_scan`, `imagination_scan`) so the whole sequence unroll is one
  ``lax.scan`` inside a jitted program — the reference pays a Python loop with a
  GRU-cell call per timestep (dreamer_v3.py:86-97);
- images flow NHWC inside the conv stacks (MXU-friendly) while the framework-facing
  arrays stay channel-first like the buffers;
- Hafner initialization (reference utils.py:143-180) maps exactly onto
  ``variance_scaling(1.0, "fan_avg", "truncated_normal")`` / ``(scale, "fan_avg",
  "uniform")`` initializers;
- the agent/player weight-tying dance (agent.py:1237-1260) disappears: one params
  pytree serves the jitted `player_step` and the jitted train program.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
from flax.traverse_util import flatten_dict, unflatten_dict
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models.models import LayerNormGRUCell, resolve_activation, tapped
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import symlog

# Hafner init: trunc-normal with variance 1/fan_avg and the 0.8796... correction —
# identical math to reference init_weights (dreamer_v3/utils.py:143-168)
hafner_init = nn.initializers.variance_scaling(1.0, "fan_avg", "truncated_normal")


def uniform_init(scale: float) -> Callable:
    """Reference uniform_init_weights (dreamer_v3/utils.py:170-180): U(-l, l) with
    l = sqrt(3 * scale / fan_avg); scale 0 → zeros."""
    if scale == 0.0:
        return nn.initializers.zeros
    return nn.initializers.variance_scaling(scale, "fan_avg", "uniform")


class DenseStack(nn.Module):
    """[Dense(no bias) → LayerNorm → act] × n — the Dreamer-V3 MLP block."""

    units: int
    n_layers: int
    activation: Any = "silu"
    eps: float = 1e-3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = resolve_activation(self.activation)
        x = x.astype(self.dtype)
        for i in range(self.n_layers):
            y = nn.Dense(self.units, use_bias=False, kernel_init=hafner_init, dtype=self.dtype)(x)
            x = tapped(self, f"tap_{i}", x, y)
            x = nn.LayerNorm(epsilon=self.eps, dtype=self.dtype)(x)
            x = act(x)
        return x


class MLPHead(nn.Module):
    """DenseStack + linear head — representation/transition/reward/continue/critic."""

    units: int
    n_layers: int
    output_dim: int
    activation: Any = "silu"
    eps: float = 1e-3
    head_init_scale: Optional[float] = None  # None → hafner trunc-normal
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = DenseStack(self.units, self.n_layers, self.activation, self.eps, self.dtype)(x)
        init = hafner_init if self.head_init_scale is None else uniform_init(self.head_init_scale)
        return tapped(self, "tap_head", x, nn.Dense(self.output_dim, kernel_init=init, dtype=self.dtype)(x))


class CNNEncoder(nn.Module):
    """4-stage stride-2 conv encoder, 64x64 → 4x4 (reference agent.py:42-100).
    Inputs are channel-first [..., C, H, W]; convs run NHWC."""

    keys: Sequence[str]
    channels_multiplier: int
    stages: int = 4
    activation: Any = "silu"
    eps: float = 1e-3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        act = resolve_activation(self.activation)
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-3)
        lead = x.shape[:-3]
        x = x.reshape(-1, *x.shape[-3:])
        x = jnp.moveaxis(x, -3, -1).astype(self.dtype)  # NCHW -> NHWC
        for i in range(self.stages):
            # pad 1, then VALID: nn.Conv's own padding would lower a padded
            # convolution, another program than the one the chip's numbers are of
            x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
            x = nn.Conv(
                (2**i) * self.channels_multiplier,
                (4, 4),
                strides=(2, 2),
                padding="VALID",
                use_bias=False,
                kernel_init=hafner_init,
                dtype=self.dtype,
                name=f"Conv_{i}",
            )(x)
            x = nn.LayerNorm(epsilon=self.eps, dtype=self.dtype)(x)
            x = act(x)
        return x.reshape(*lead, -1)


class MLPEncoder(nn.Module):
    """Vector encoder with optional symlog input squashing (reference agent.py:103-151)."""

    keys: Sequence[str]
    mlp_layers: int = 4
    dense_units: int = 512
    activation: Any = "silu"
    eps: float = 1e-3
    symlog_inputs: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate(
            [symlog(obs[k]) if self.symlog_inputs else obs[k] for k in self.keys], axis=-1
        )
        return DenseStack(self.dense_units, self.mlp_layers, self.activation, self.eps, self.dtype)(x)


class Encoder(nn.Module):
    """Fused cnn+mlp encoder over the obs dict (reference MultiEncoder usage)."""

    cnn_encoder: Optional[CNNEncoder]
    mlp_encoder: Optional[MLPEncoder]

    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        outs = []
        if self.cnn_encoder is not None:
            outs.append(self.cnn_encoder(obs))
        if self.mlp_encoder is not None:
            outs.append(self.mlp_encoder(obs))
        return jnp.concatenate(outs, axis=-1)


class ConvTransposeHead(nn.Module):
    """``nn.ConvTranspose(features, (4, 4), strides=(2, 2), padding="SAME")`` with its
    parameter tree, the bias added as the ``[features]`` vector it is kept as:
    ``nn.ConvTranspose`` reshapes it to ``[1, 1, 1, features]`` first, the same values
    from another program than the one the chip's numbers are of."""

    features: int
    kernel_init: Callable
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param("kernel", self.kernel_init, (4, 4, x.shape[-1], self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (self.features,), jnp.float32)
        y = jax.lax.conv_transpose(
            x.astype(self.dtype), kernel.astype(self.dtype), (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + bias.astype(self.dtype)


class CNNDecoder(nn.Module):
    """Inverse of CNNEncoder: latent → 4x4 → stride-2 deconv stages → channel-first
    images per key (reference agent.py:154-228)."""

    keys: Sequence[str]
    output_channels: Sequence[int]
    channels_multiplier: int
    image_size: Tuple[int, int]
    stages: int = 4
    activation: Any = "silu"
    eps: float = 1e-3
    hafner_heads: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        act = resolve_activation(self.activation)
        spatial = self.image_size[0] // (2**self.stages)
        top_channels = (2 ** (self.stages - 1)) * self.channels_multiplier
        x = nn.Dense(
            top_channels * spatial * spatial, kernel_init=hafner_init, dtype=self.dtype
        )(latent)
        lead = x.shape[:-1]
        x = x.reshape(-1, spatial, spatial, top_channels)
        for i in range(self.stages - 1):
            x = nn.ConvTranspose(
                (2 ** (self.stages - 2 - i)) * self.channels_multiplier,
                (4, 4),
                strides=(2, 2),
                padding="SAME",
                use_bias=False,
                kernel_init=hafner_init,
                dtype=self.dtype,
                name=f"ConvTranspose_{i}",
            )(x)
            x = nn.LayerNorm(epsilon=self.eps, dtype=self.dtype)(x)
            x = act(x)
        x = ConvTransposeHead(
            sum(self.output_channels),
            kernel_init=uniform_init(1.0) if self.hafner_heads else hafner_init,
            dtype=self.dtype,
            name=f"ConvTranspose_{self.stages - 1}",
        )(x)
        x = jnp.moveaxis(x, -1, -3)  # NHWC -> NCHW
        x = x.reshape(*lead, *x.shape[-3:])
        splits = np.cumsum(self.output_channels)[:-1].tolist()
        return {k: v for k, v in zip(self.keys, jnp.split(x, splits, axis=-3))}


class MLPDecoder(nn.Module):
    """Inverse of MLPEncoder: shared stack + one linear head per key
    (reference agent.py:231-282)."""

    keys: Sequence[str]
    output_dims: Sequence[int]
    mlp_layers: int = 4
    dense_units: int = 512
    activation: Any = "silu"
    eps: float = 1e-3
    hafner_heads: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        x = DenseStack(self.dense_units, self.mlp_layers, self.activation, self.eps, self.dtype)(latent)
        init = uniform_init(1.0) if self.hafner_heads else hafner_init
        return {
            k: nn.Dense(dim, kernel_init=init, dtype=self.dtype)(x)
            for k, dim in zip(self.keys, self.output_dims)
        }


class Decoder(nn.Module):
    cnn_decoder: Optional[CNNDecoder]
    mlp_decoder: Optional[MLPDecoder]

    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(latent))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(latent))
        return out


class RecurrentModel(nn.Module):
    """MLP input projection + layer-norm GRU cell (reference agent.py:285-341)."""

    recurrent_state_size: int
    dense_units: int
    activation: Any = "silu"
    eps: float = 1e-3
    fused_step: bool = False  # LayerNormGRUCell's: on where the programs run on one device
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, h: jax.Array) -> jax.Array:
        feat = DenseStack(self.dense_units, 1, self.activation, self.eps, self.dtype)(x)
        return LayerNormGRUCell(
            hidden_size=self.recurrent_state_size,
            bias=False,
            layer_norm=True,
            layer_norm_eps=self.eps,
            kernel_init=hafner_init,
            fused_step=self.fused_step,
            dtype=self.dtype,
        )(h, feat)


class Actor(nn.Module):
    """Dreamer-V3 policy head (reference agent.py:694-884): DenseStack backbone, one
    logits head per discrete action dim (unimix-smoothed), or a single
    mean/std head for continuous control. Returns the *raw head outputs*; sampling
    and distribution math live in pure functions below so they can take PRNG keys."""

    actions_dim: Sequence[int]
    is_continuous: bool
    dense_units: int = 1024
    mlp_layers: int = 5
    activation: Any = "silu"
    eps: float = 1e-3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, state: jax.Array) -> List[jax.Array]:
        x = DenseStack(self.dense_units, self.mlp_layers, self.activation, self.eps, self.dtype)(state)
        if self.is_continuous:
            return [nn.Dense(int(np.sum(self.actions_dim)) * 2, kernel_init=uniform_init(1.0), dtype=self.dtype)(x)]
        return [
            nn.Dense(dim, kernel_init=uniform_init(1.0), dtype=self.dtype)(x)
            for dim in self.actions_dim
        ]


class MinedojoActor(Actor):
    """Marker subclass selecting MineDojo action masking (reference agent.py:850-935).

    Parameters and forward pass are identical to ``Actor`` — the masking is
    sampling-time logic applied to the head logits (``apply_minedojo_masks`` below),
    driven by the ``mask_*`` observation keys, so it lives in the pure sampling path
    rather than the module."""


# MineDojo functional-action ids whose argument heads are conditionally masked
# (reference agent.py:908-925: 15=craft, 16/17=equip/place, 18=destroy)
_MINEDOJO_CRAFT_ACTION = 15
_MINEDOJO_EQUIP_PLACE_ACTIONS = (16, 17)
_MINEDOJO_DESTROY_ACTION = 18
MINEDOJO_MASK_KEYS = ("mask_action_type", "mask_craft_smelt", "mask_destroy", "mask_equip_place")


def mask_minedojo_head(
    head_idx: int,
    logits: jax.Array,
    mask: Dict[str, jax.Array],
    functional_action: Optional[jax.Array] = None,
) -> jax.Array:
    """Mask one MineDojo actor head's logits with the env-provided validity masks.

    Head 0 (action type) is masked unconditionally; head 1 (craft argument) only
    where the sampled functional action is craft; head 2 (equip/place/destroy
    argument) per the sampled functional action. The reference does the conditional
    part with a per-(t, b) python loop (agent.py:911-925); here it is a vectorized
    ``jnp.where`` over the whole batch. ``functional_action`` (int ids, shape [...])
    is the argmax of the freshly-sampled head-0 one-hot."""
    neg_inf = jnp.asarray(-1e9, logits.dtype)
    if head_idx == 0:
        return jnp.where(mask["mask_action_type"].astype(bool), logits, neg_inf)
    if functional_action is None:
        return logits
    if head_idx == 1 and "mask_craft_smelt" in mask:
        is_craft = (functional_action == _MINEDOJO_CRAFT_ACTION)[..., None]
        invalid = jnp.logical_not(mask["mask_craft_smelt"].astype(bool))
        return jnp.where(jnp.logical_and(is_craft, invalid), neg_inf, logits)
    if head_idx == 2 and "mask_equip_place" in mask and "mask_destroy" in mask:
        is_equip_place = jnp.isin(
            functional_action, jnp.asarray(_MINEDOJO_EQUIP_PLACE_ACTIONS)
        )[..., None]
        is_destroy = (functional_action == _MINEDOJO_DESTROY_ACTION)[..., None]
        invalid_ep = jnp.logical_not(mask["mask_equip_place"].astype(bool))
        invalid_d = jnp.logical_not(mask["mask_destroy"].astype(bool))
        logits = jnp.where(jnp.logical_and(is_equip_place, invalid_ep), neg_inf, logits)
        return jnp.where(jnp.logical_and(is_destroy, invalid_d), neg_inf, logits)
    return logits


# ---------------------------------------------------------------------------------
# pure stochastic-state math
# ---------------------------------------------------------------------------------
def unimix_logits(logits: jax.Array, discrete: int, unimix: float) -> jax.Array:
    """1% uniform mixing of categorical probs (reference RSSM._uniform_mix,
    agent.py:447-459). Takes and returns flat [..., S*D] logits."""
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    if unimix > 0.0:
        probs = jax.nn.softmax(logits, axis=-1)
        uniform = jnp.ones_like(probs) / discrete
        probs = (1 - unimix) * probs + unimix * uniform
        logits = jnp.log(probs)
    return logits.reshape(*logits.shape[:-2], -1)


def stochastic_state(
    logits: jax.Array, discrete: int, key: Optional[jax.Array] = None, sample: bool = True
) -> jax.Array:
    """Straight-through sample (or mode) of the [..., S, D] categorical stack
    (reference dreamer_v2/utils.py:44-61). Returns flat [..., S*D]."""
    shaped = logits.reshape(*logits.shape[:-1], -1, discrete)
    if sample:
        idx = jax.random.categorical(key, shaped, axis=-1)
        onehot = jax.nn.one_hot(idx, discrete, dtype=shaped.dtype)
        probs = jax.nn.softmax(shaped, axis=-1)
        out = jax.lax.stop_gradient(onehot) + probs - jax.lax.stop_gradient(probs)
    else:
        idx = jnp.argmax(shaped, axis=-1)
        out = jax.nn.one_hot(idx, discrete, dtype=shaped.dtype)
    return out.reshape(*out.shape[:-2], -1)


def categorical_kl(post_logits: jax.Array, prior_logits: jax.Array, discrete: int) -> jax.Array:
    """KL( Cat(post) || Cat(prior) ) summed over the stochastic-variable axis;
    flat [..., S*D] logits in, [...] out."""
    post = post_logits.reshape(*post_logits.shape[:-1], -1, discrete)
    prior = prior_logits.reshape(*prior_logits.shape[:-1], -1, discrete)
    post_lp = jax.nn.log_softmax(post, axis=-1)
    prior_lp = jax.nn.log_softmax(prior, axis=-1)
    kl = jnp.sum(jnp.exp(post_lp) * (post_lp - prior_lp), axis=-1)
    return kl.sum(axis=-1)


# ---------------------------------------------------------------------------------
# actor distribution math (pure)
# ---------------------------------------------------------------------------------
def actor_sample(
    agent: "DV3Agent",
    pre_dist: List[jax.Array],
    key: jax.Array,
    greedy: bool = False,
    mask: Optional[Dict[str, jax.Array]] = None,
) -> jax.Array:
    """Sample concatenated actions from the raw actor outputs (one-hot blocks for
    discrete dims, clipped tanh-mean scaled-normal for continuous — reference
    Actor.forward, agent.py:790-855). ``mask`` applies MineDojo per-head validity
    masking (reference MinedojoActor.forward, agent.py:884-935): head 0 sampled
    first, its functional action gating the argument heads."""
    cfg = agent.actor_cfg
    if agent.is_continuous:
        mean, std_raw = jnp.split(pre_dist[0], 2, axis=-1)
        mean = jnp.tanh(mean)
        std = (cfg["max_std"] - cfg["min_std"]) * jax.nn.sigmoid(std_raw + cfg["init_std"]) + cfg["min_std"]
        if greedy:
            actions = mean
        else:
            actions = mean + std * jax.random.normal(key, mean.shape, mean.dtype)
        clip = cfg.get("action_clip", 1.0)
        if clip and clip > 0:
            limit = jnp.full_like(actions, clip)
            scale = limit / jnp.maximum(limit, jnp.abs(actions))
            actions = actions * jax.lax.stop_gradient(scale)
        return actions
    keys = jax.random.split(key, len(pre_dist))
    outs = []
    functional_action = None
    for i, logits in enumerate(pre_dist):
        logits = unimix_logits(logits, logits.shape[-1], cfg.get("unimix", 0.01))
        if mask is not None:
            logits = mask_minedojo_head(i, logits, mask, functional_action)
        if greedy:
            idx = jnp.argmax(logits, axis=-1)
            outs.append(jax.nn.one_hot(idx, logits.shape[-1], dtype=logits.dtype))
        else:
            idx = jax.random.categorical(keys[i], logits, axis=-1)
            onehot = jax.nn.one_hot(idx, logits.shape[-1], dtype=logits.dtype)
            probs = jax.nn.softmax(logits, axis=-1)
            outs.append(jax.lax.stop_gradient(onehot) + probs - jax.lax.stop_gradient(probs))
        if functional_action is None:
            functional_action = jnp.argmax(outs[0], axis=-1)
    return jnp.concatenate(outs, axis=-1)


def actor_logprob_entropy(
    agent: "DV3Agent", pre_dist: List[jax.Array], actions: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """log-prob of concatenated ``actions`` under the actor heads + total entropy
    (used by the imagination REINFORCE objective). Shapes [..., 1] / [...]."""
    cfg = agent.actor_cfg
    if agent.is_continuous:
        mean, std_raw = jnp.split(pre_dist[0], 2, axis=-1)
        mean = jnp.tanh(mean)
        std = (cfg["max_std"] - cfg["min_std"]) * jax.nn.sigmoid(std_raw + cfg["init_std"]) + cfg["min_std"]
        var = jnp.square(std)
        lp = (-jnp.square(actions - mean) / (2 * var) - jnp.log(std) - 0.5 * jnp.log(2 * jnp.pi)).sum(
            axis=-1, keepdims=True
        )
        ent = (0.5 + 0.5 * jnp.log(2 * jnp.pi) + jnp.log(std)).sum(axis=-1)
        return lp, ent
    splits = np.cumsum(agent.actions_dim)[:-1].tolist()
    blocks = jnp.split(actions, splits, axis=-1)
    lps, ents = [], []
    for logits, act in zip(pre_dist, blocks):
        logits = unimix_logits(logits, logits.shape[-1], cfg.get("unimix", 0.01))
        lp_all = jax.nn.log_softmax(logits, axis=-1)
        lps.append(jnp.sum(lp_all * act, axis=-1))
        ents.append(-jnp.sum(jnp.exp(lp_all) * lp_all, axis=-1))
    return jnp.stack(lps, axis=-1).sum(axis=-1, keepdims=True), jnp.stack(ents, axis=-1).sum(axis=-1)


# ---------------------------------------------------------------------------------
# agent container + scan programs
# ---------------------------------------------------------------------------------
# the kernels the posterior step multiplies by: name -> (the kernel's path under
# ``wm_params``, the path of the tap its module takes on that product (models.tapped)
# under the model's ``taps`` and ``tap_inputs`` collections)
_STEP_KERNELS = {
    "recurrent_in": (
        ("recurrent_model", "DenseStack_0", "Dense_0", "kernel"),
        ("recurrent_model", "DenseStack_0", "tap_0"),
    ),
    "gru": (
        ("recurrent_model", "LayerNormGRUCell_0", "kernel"),
        ("recurrent_model", "LayerNormGRUCell_0", "gates"),
    ),
    "representation_in": (
        ("representation_model", "DenseStack_0", "Dense_0", "kernel"),
        ("representation_model", "DenseStack_0", "tap_0"),
    ),
    "representation_head": (
        ("representation_model", "Dense_0", "kernel"),
        ("representation_model", "tap_head"),
    ),
}


def _scan_hoisting_kernel_grads(run: Callable, kernels: Dict[str, jax.Array], rest: Dict, keys):
    """``run(kernels, rest, keys)[0]``, differentiable in ``kernels`` and ``rest``.

    ``run`` is a ``lax.scan`` that multiplies by ``kernels`` at every step; it returns
    ``(outs, tap_inputs)`` and takes in ``rest["xs"]["taps"]`` one tap a kernel and
    step, added to that step's product (models.tapped). The backward pass differentiates
    the scan with the kernels held constant, so its loop carries no kernel-sized sum,
    and forms each kernel's gradient once: the stacked inputs against the taps'
    stacked cotangents. Whatever else the step depends on is in ``rest``: a
    differentiable value it closed over instead would lose its gradient in silence.
    """

    dtypes = {name: kernel.dtype for name, kernel in kernels.items()}

    @jax.custom_vjp
    def scan(kernels, rest, keys):
        return run(kernels, rest, keys)[0]

    def forward(kernels, rest, keys):
        outs, vjp, tap_inputs = jax.vjp(lambda rest: run(kernels, rest, keys), rest, has_aux=True)
        return outs, (vjp, tap_inputs)

    def backward(residuals, cotangents):
        vjp, tap_inputs = residuals
        (d_rest,) = vjp(cotangents)
        d_kernels = {
            name: jnp.einsum(
                "tbk,tbn->kn", tap_inputs[name], d_rest["xs"]["taps"][name], preferred_element_type=jnp.float32
            ).astype(dtype)
            for name, dtype in dtypes.items()
        }
        return d_kernels, d_rest, None

    scan.defvjp(forward, backward)
    return scan(kernels, rest, keys)


@dataclass
class DV3Agent:
    """All Flax modules plus the pure-scan RSSM programs. ``params`` pytrees are
    threaded explicitly; layout:

    ``{"world_model": {"encoder", "recurrent_model", "representation_model",
    "transition_model", "observation_model", "reward_model", "continue_model",
    "initial_recurrent_state"}, "actor", "critic", "target_critic"}``
    """

    encoder: Encoder
    recurrent_model: RecurrentModel
    representation_model: MLPHead
    transition_model: MLPHead
    observation_model: Decoder
    reward_model: MLPHead
    continue_model: MLPHead
    actor: Actor
    critic: MLPHead
    actions_dim: Sequence[int]
    is_continuous: bool
    stochastic_size: int
    discrete_size: int
    recurrent_state_size: int
    unimix: float
    actor_cfg: Dict[str, Any] = field(default_factory=dict)
    learnable_initial_recurrent_state: bool = True
    decoupled_rssm: bool = False

    @property
    def stoch_state_size(self) -> int:
        return self.stochastic_size * self.discrete_size

    @property
    def is_minedojo(self) -> bool:
        return isinstance(self.actor, MinedojoActor)

    @property
    def latent_state_size(self) -> int:
        return self.stoch_state_size + self.recurrent_state_size

    # -- rssm primitives -------------------------------------------------------------

    def initial_state(self, wm_params: Dict, batch_shape: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
        """tanh(learnable w) expanded + transition-mode posterior (reference
        RSSM.get_initial_states, agent.py:406-409)."""
        w = wm_params["initial_recurrent_state"]
        if not self.learnable_initial_recurrent_state:
            w = jax.lax.stop_gradient(w)
        h0 = jnp.broadcast_to(jnp.tanh(w), (*batch_shape, self.recurrent_state_size))
        z0 = stochastic_state(self._prior_logits(wm_params, h0), self.discrete_size, sample=False)
        return h0, z0

    def _representation(self, wm_params: Dict, h: jax.Array, embedded: jax.Array, key: jax.Array):
        if self.decoupled_rssm:
            # DecoupledRSSM (reference agent.py:501-596): the posterior depends on
            # the embedded observation ALONE — no recurrent-state input
            rep_in = embedded
        else:
            rep_in = jnp.concatenate([h, embedded], axis=-1)
        logits = self.representation_model.apply(
            {"params": wm_params["representation_model"]}, rep_in
        )
        logits = unimix_logits(logits, self.discrete_size, self.unimix)
        return logits, stochastic_state(logits, self.discrete_size, key)

    def _prior_logits(self, wm_params: Dict, h: jax.Array) -> jax.Array:
        logits = self.transition_model.apply({"params": wm_params["transition_model"]}, h)
        return unimix_logits(logits, self.discrete_size, self.unimix)

    def _transition(self, wm_params: Dict, h: jax.Array, key: jax.Array):
        logits = self._prior_logits(wm_params, h)
        return logits, stochastic_state(logits, self.discrete_size, key)

    def _recurrent(self, wm_params: Dict, z: jax.Array, a: jax.Array, h: jax.Array) -> jax.Array:
        return self.recurrent_model.apply(
            {"params": wm_params["recurrent_model"]}, jnp.concatenate([z, a], axis=-1), h
        )

    def dynamic_scan(
        self,
        wm_params: Dict,
        embedded: jax.Array,  # [T, B, E]
        actions: jax.Array,  # [T, B, A]
        is_first: jax.Array,  # [T, B, 1]
        key: jax.Array,
    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Posterior/prior unroll over the sequence — ONE lax.scan replacing the
        reference's per-timestep Python loop (dreamer_v3.py:86-97).

        The loop does only what its carry ``(h, z)`` needs. The prior comes from the
        stacked recurrent states after it, ``embedded``'s share of the posterior's
        first product before it, and the gradients of the kernels the step does
        multiply by are formed after the backward loop, one product a kernel
        (``_scan_hoisting_kernel_grads``): left to ``lax.scan``'s transpose, each
        would be a kernel-sized sum read and written at every time step to add a
        product of B rows (howto/performance.md).

        Returns (recurrent_states, posteriors, posterior_logits, prior_logits), all
        time-major with flattened stochastic states.
        """
        kernels, rest, keys = self._posterior_pieces(wm_params, embedded, actions, is_first, key)
        T, B = embedded.shape[:2]
        for name, kernel in kernels.items():
            # the GRU's gates are float32 whatever the compute dtype (ops/gru.py)
            dtype = jnp.float32 if name == "gru" else embedded.dtype
            rest["xs"]["taps"].setdefault(name, jnp.zeros((T, B, kernel.shape[-1]), dtype))
        self._count_weight_grad_bytes(wm_params, in_scan={})

        def run(kernels, rest, keys):
            return self._posterior_scan(kernels, rest, keys, jax.lax.scan, keep_inputs=True)

        hs, zs, post_logits = _scan_hoisting_kernel_grads(run, kernels, rest, keys)
        return hs, zs, post_logits, self._prior_logits(wm_params, hs)

    def dynamic_scan_sp(
        self,
        wm_params: Dict,
        embedded: jax.Array,  # [T, B, E], T sharded over the mesh seq axis
        actions: jax.Array,
        is_first: jax.Array,
        key: jax.Array,
        mesh,
        axis: str = "seq",
    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Sequence-parallel posterior/prior unroll: the long-context variant of
        ``dynamic_scan`` — the TIME axis is sharded over the mesh ``axis`` and the
        carry hops along a ppermute ring, so each device holds only T/S steps of
        inputs and activations (SURVEY §5.7's extension hook; no reference
        counterpart). Numerically identical to ``dynamic_scan`` (parity-tested);
        both run the SAME step body from ``_posterior_scan``. The step's kernels
        keep plain autodiff here, which sums their gradients inside the loop."""
        from sheeprl_tpu.parallel.sequence import ring_sequence_scan

        kernels, rest, keys = self._posterior_pieces(wm_params, embedded, actions, is_first, key)
        self._count_weight_grad_bytes(wm_params, in_scan=kernels)

        def ring(step, init, xs):
            return ring_sequence_scan(step, init, xs, mesh, axis)

        (hs, zs, post_logits), _ = self._posterior_scan(kernels, rest, keys, ring, keep_inputs=False)
        return hs, zs, post_logits, self._prior_logits(wm_params, hs)

    def _count_weight_grad_bytes(self, wm_params: Dict, in_scan: Dict[str, jax.Array]) -> None:
        """Counted once a trace: the bytes of the RSSM's kernels whose gradient is
        formed outside the posterior loop, and of those (``in_scan``, the step's own)
        still summed inside it."""
        total = sum(
            leaf.nbytes
            for model in ("recurrent_model", "representation_model", "transition_model")
            for path, leaf in jax.tree_util.tree_leaves_with_path(wm_params[model])
            if path[-1].key == "kernel"
        )
        inside = sum(kernel.nbytes for kernel in in_scan.values())
        timer.count("rssm/weight_grad_bytes_hoisted", total - inside)
        timer.count("rssm/weight_grad_bytes_in_scan", inside)

    def _posterior_pieces(self, wm_params, embedded, actions, is_first, key):
        """What the posterior loop consumes, computed outside it: ``kernels``, the
        kernels its step multiplies by (``_STEP_KERNELS``); ``rest``, everything else
        that can carry a gradient (the two models' other leaves by path, the initial
        state, the per-step inputs ``xs``); and the per-step sampling keys."""
        T = embedded.shape[0]
        H = self.recurrent_state_size
        dtype = embedded.dtype
        h0, z0 = self.initial_state(wm_params, (embedded.shape[1],))
        keys = jax.random.split(key, T)
        # the carry must keep the compute dtype through the whole scan: fp32
        # actions/is_first would promote the bf16 body output back to fp32 and break
        # the carry-type invariant under precision=bf16-*
        xs = {"a": actions.astype(dtype), "first": is_first.astype(dtype), "taps": {}}
        names = ["recurrent_in", "gru"]
        if self.decoupled_rssm:
            # the posterior is non-recurrent, so the WHOLE sequence's posteriors come
            # from one batched feedforward pass (reference DecoupledRSSM samples the
            # posterior outside the time loop); only the recurrent chain stays
            # sequential
            xs["post_logits"], xs["z"] = jax.vmap(
                lambda e, k: self._representation(wm_params, h0, e, k)
            )(embedded, keys)
            keys = None
        else:
            names += ["representation_in", "representation_head"]
        # the two models' leaves by path, the step's kernels taken out
        models = flatten_dict({model: wm_params[model] for model in {_STEP_KERNELS[name][0][0] for name in names}})
        kernels = {name: models.pop(_STEP_KERNELS[name][0]) for name in names}
        if not self.decoupled_rssm:
            # concat([h, embedded]) @ W = h @ W[:H] + embedded @ W[H:] (the Dense has no
            # bias): the step multiplies by W[:H] alone, and embedded's share, all
            # T x B rows in one product, reaches the same pre-activation as a tap
            w = kernels["representation_in"]
            kernels["representation_in"] = w[:H]
            xs["taps"]["representation_in"] = nn.Dense(
                w.shape[-1], use_bias=False, dtype=self.representation_model.dtype
            ).apply({"params": {"kernel": w[H:]}}, embedded)
        rest = {"models": models, "h0": h0.astype(dtype), "z0": z0.astype(dtype), "xs": xs}
        return kernels, rest, keys

    def _posterior_scan(self, kernels, rest, keys, scan, keep_inputs: bool):
        """``scan`` over the RSSM step shared by the plain and the sequence-parallel
        unrolls. Returns ((hs, zs, post_logits), tap_inputs): with ``keep_inputs`` the
        input of each of ``kernels`` at every step, stacked over time, else {}."""
        h0, z0 = rest["h0"], rest["z0"]
        models = unflatten_dict({**rest["models"], **{_STEP_KERNELS[name][0]: kernel for name, kernel in kernels.items()}})

        def apply(module, model, taps, *args):
            """``module`` on ``args`` with the step's taps, and the inputs it kept."""
            paths = {name: path[1:] for name, (_, path) in _STEP_KERNELS.items() if path[0] == model and name in taps}
            variables = {"params": models[model], "taps": unflatten_dict({path: taps[name] for name, path in paths.items()})}
            if not keep_inputs:
                return module.apply(variables, *args), {}
            out, kept = module.apply(variables, *args, mutable=["tap_inputs"])
            kept = flatten_dict(kept["tap_inputs"])
            return out, {name: kept[path] for name, path in paths.items()}

        def step(carry, inp):
            h, z = carry
            a, first = inp["a"], inp["first"]
            # reset masking, then the recurrent update
            a = (1 - first) * a
            h = (1 - first) * h + first * h0
            z = (1 - first) * z + first * z0
            h, inputs = apply(self.recurrent_model, "recurrent_model", inp["taps"], jnp.concatenate([z, a], axis=-1), h)
            if self.decoupled_rssm:
                z, post_logits = inp["z"], inp["post_logits"]
            else:
                logits, rep_inputs = apply(self.representation_model, "representation_model", inp["taps"], h)
                inputs.update(rep_inputs)
                post_logits = unimix_logits(logits, self.discrete_size, self.unimix)
                z = stochastic_state(post_logits, self.discrete_size, inp["key"])
            return (h, z), ((h, z, post_logits), inputs)

        xs = rest["xs"] if keys is None else {**rest["xs"], "key": keys}
        init = (jnp.zeros_like(h0), jnp.zeros_like(z0))
        _, outs = scan(step, init, xs)
        return outs

    def imagination_scan(
        self,
        wm_params: Dict,
        actor_params: Dict,
        z0: jax.Array,  # [N, S*D] flattened start posteriors (stop-gradient'ed)
        h0: jax.Array,  # [N, H]
        key: jax.Array,
        horizon: int,
    ) -> Tuple[jax.Array, jax.Array]:
        """Latent imagination (reference behaviour_learning, dreamer_v3.py:104-158):
        actor acts on stop-gradient latents, dynamics keep gradients flowing so the
        continuous-control pathwise objective works. Returns
        (latents [H+1, N, L], actions [H+1, N, A])."""
        k0, kscan = jax.random.split(key)
        latent0 = jnp.concatenate([z0, h0], axis=-1)
        pre = self.actor.apply({"params": actor_params}, jax.lax.stop_gradient(latent0))
        a0 = actor_sample(self, pre, k0)

        def step(carry, k):
            z, h, a = carry
            h = self._recurrent(wm_params, z, a, h)
            _, z = self._transition(wm_params, h, k)
            latent = jnp.concatenate([z, h], axis=-1)
            k_act = jax.random.fold_in(k, 1)
            pre = self.actor.apply({"params": actor_params}, jax.lax.stop_gradient(latent))
            a = actor_sample(self, pre, k_act)
            return (z, h, a), (latent, a)

        keys = jax.random.split(kscan, horizon)
        _, (latents, actions) = jax.lax.scan(step, (z0, h0, a0), keys)
        latents = jnp.concatenate([latent0[None], latents], axis=0)
        actions = jnp.concatenate([a0[None], actions], axis=0)
        return latents, actions


def build_agent(
    fabric,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg,
    obs_space,
    key: jax.Array,
    agent_state: Optional[Dict[str, Any]] = None,
) -> Tuple[DV3Agent, Dict[str, Any]]:
    """Create the DV3Agent container + initialized params pytree (role of reference
    build_agent, agent.py:937-1260, minus the Fabric/compile/weight-tying dance)."""
    wm_cfg = cfg.algo.world_model
    actor_cfg = cfg.algo.actor
    critic_cfg = cfg.algo.critic
    dtype = fabric.compute_dtype

    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_dec_keys = tuple(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = tuple(cfg.algo.mlp_keys.decoder)
    cnn_stages = int(np.log2(cfg.env.screen_size) - np.log2(4))
    eps = 1e-3

    cnn_encoder = (
        CNNEncoder(
            keys=cnn_keys,
            channels_multiplier=wm_cfg.encoder.cnn_channels_multiplier,
            stages=cnn_stages,
            activation=cfg.algo.cnn_act,
            eps=eps,
            dtype=dtype,
        )
        if len(cnn_keys) > 0
        else None
    )
    mlp_encoder = (
        MLPEncoder(
            keys=mlp_keys,
            mlp_layers=wm_cfg.encoder.mlp_layers,
            dense_units=wm_cfg.encoder.dense_units,
            activation=cfg.algo.dense_act,
            eps=eps,
            dtype=dtype,
        )
        if len(mlp_keys) > 0
        else None
    )
    encoder = Encoder(cnn_encoder, mlp_encoder)

    stochastic_size = wm_cfg.stochastic_size
    discrete_size = wm_cfg.discrete_size
    stoch_state_size = stochastic_size * discrete_size
    recurrent_state_size = wm_cfg.recurrent_model.recurrent_state_size
    latent_state_size = stoch_state_size + recurrent_state_size

    recurrent_model = RecurrentModel(
        recurrent_state_size=recurrent_state_size,
        dense_units=wm_cfg.recurrent_model.dense_units,
        activation=cfg.algo.dense_act,
        eps=eps,
        fused_step=fabric.num_devices == 1,
        dtype=dtype,
    )
    representation_model = MLPHead(
        units=wm_cfg.representation_model.hidden_size,
        n_layers=1,
        output_dim=stoch_state_size,
        activation=wm_cfg.representation_model.dense_act,
        eps=eps,
        head_init_scale=1.0 if cfg.algo.hafner_initialization else None,
        dtype=dtype,
    )
    transition_model = MLPHead(
        units=wm_cfg.transition_model.hidden_size,
        n_layers=1,
        output_dim=stoch_state_size,
        activation=wm_cfg.transition_model.dense_act,
        eps=eps,
        head_init_scale=1.0 if cfg.algo.hafner_initialization else None,
        dtype=dtype,
    )
    cnn_decoder = (
        CNNDecoder(
            keys=cnn_dec_keys,
            output_channels=[int(np.prod(obs_space[k].shape[:-2])) for k in cnn_dec_keys],
            channels_multiplier=wm_cfg.observation_model.cnn_channels_multiplier,
            image_size=tuple(obs_space[cnn_dec_keys[0]].shape[-2:]),
            stages=cnn_stages,
            activation=cfg.algo.cnn_act,
            eps=eps,
            hafner_heads=cfg.algo.hafner_initialization,
            dtype=dtype,
        )
        if len(cnn_dec_keys) > 0
        else None
    )
    mlp_decoder = (
        MLPDecoder(
            keys=mlp_dec_keys,
            output_dims=[obs_space[k].shape[0] for k in mlp_dec_keys],
            mlp_layers=wm_cfg.observation_model.mlp_layers,
            dense_units=wm_cfg.observation_model.dense_units,
            activation=cfg.algo.dense_act,
            eps=eps,
            hafner_heads=cfg.algo.hafner_initialization,
            dtype=dtype,
        )
        if len(mlp_dec_keys) > 0
        else None
    )
    observation_model = Decoder(cnn_decoder, mlp_decoder)
    reward_model = MLPHead(
        units=wm_cfg.reward_model.dense_units,
        n_layers=wm_cfg.reward_model.mlp_layers,
        output_dim=wm_cfg.reward_model.bins,
        activation=cfg.algo.dense_act,
        eps=eps,
        head_init_scale=0.0 if cfg.algo.hafner_initialization else None,
        dtype=dtype,
    )
    continue_model = MLPHead(
        units=wm_cfg.discount_model.dense_units,
        n_layers=wm_cfg.discount_model.mlp_layers,
        output_dim=1,
        activation=cfg.algo.dense_act,
        eps=eps,
        head_init_scale=1.0 if cfg.algo.hafner_initialization else None,
        dtype=dtype,
    )
    cls_path = str(actor_cfg.get("cls") or "")
    if cls_path:
        from sheeprl_tpu.config.instantiate import locate

        actor_cls = locate(cls_path)
    else:
        actor_cls = Actor
    actor = actor_cls(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        dense_units=actor_cfg.dense_units,
        mlp_layers=actor_cfg.mlp_layers,
        activation=actor_cfg.dense_act,
        eps=eps,
        dtype=dtype,
    )
    critic = MLPHead(
        units=critic_cfg.dense_units,
        n_layers=critic_cfg.mlp_layers,
        output_dim=critic_cfg.bins,
        activation=critic_cfg.dense_act,
        eps=eps,
        head_init_scale=0.0 if cfg.algo.hafner_initialization else None,
        dtype=dtype,
    )

    agent = DV3Agent(
        encoder=encoder,
        recurrent_model=recurrent_model,
        representation_model=representation_model,
        transition_model=transition_model,
        observation_model=observation_model,
        reward_model=reward_model,
        continue_model=continue_model,
        actor=actor,
        critic=critic,
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        stochastic_size=stochastic_size,
        discrete_size=discrete_size,
        recurrent_state_size=recurrent_state_size,
        unimix=cfg.algo.unimix,
        actor_cfg={
            "init_std": actor_cfg.init_std,
            "min_std": actor_cfg.min_std,
            "max_std": actor_cfg.get("max_std", 1.0),
            "unimix": actor_cfg.get("unimix", cfg.algo.unimix),
            "action_clip": actor_cfg.get("action_clip", 1.0),
        },
        learnable_initial_recurrent_state=wm_cfg.learnable_initial_recurrent_state,
        decoupled_rssm=bool(wm_cfg.get("decoupled_rssm", False)),
    )

    # -- init params -------------------------------------------------------------
    # The whole init is ONE jitted program: eager flax `.init` calls dispatch hundreds
    # of tiny ops, each paying a device round-trip (multi-second setup on a remote
    # TPU); a single traced program pays one compile + one execution.
    act_dim = int(np.sum(actions_dim))

    def _init_all(key):
        keys = jax.random.split(key, 10)
        dummy_obs = {}
        for k in cnn_keys:
            dummy_obs[k] = jnp.zeros((1, *obs_space[k].shape), jnp.float32)
        for k in mlp_keys:
            dummy_obs[k] = jnp.zeros((1, *obs_space[k].shape), jnp.float32)
        embed_dim_probe = encoder.init(keys[0], dummy_obs)
        embedded = encoder.apply(embed_dim_probe, dummy_obs)
        h = jnp.zeros((1, recurrent_state_size), jnp.float32)
        z = jnp.zeros((1, stoch_state_size), jnp.float32)
        latent = jnp.zeros((1, latent_state_size), jnp.float32)

        wm_params = {
            "encoder": embed_dim_probe["params"],
            "recurrent_model": recurrent_model.init(
                keys[1], jnp.concatenate([z, jnp.zeros((1, act_dim), jnp.float32)], axis=-1), h
            )["params"],
            "representation_model": representation_model.init(
                keys[2],
                # decoupled RSSM: the posterior head consumes the embedding alone
                embedded
                if wm_cfg.get("decoupled_rssm", False)
                else jnp.concatenate([h, embedded], axis=-1),
            )["params"],
            "transition_model": transition_model.init(keys[3], h)["params"],
            "observation_model": observation_model.init(keys[4], latent)["params"],
            "reward_model": reward_model.init(keys[5], latent)["params"],
            "continue_model": continue_model.init(keys[6], latent)["params"],
            "initial_recurrent_state": jnp.zeros((recurrent_state_size,), jnp.float32),
        }
        actor_params = actor.init(keys[7], latent)["params"]
        critic_params = critic.init(keys[8], latent)["params"]
        return {
            "world_model": wm_params,
            "actor": actor_params,
            "critic": critic_params,
            # explicit copy so critic/target_critic never alias one buffer — the
            # donated train program rejects f(donate(a), donate(a))
            "target_critic": jax.tree_util.tree_map(jnp.copy, critic_params),
        }

    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
        if getattr(fabric, "model_parallel", False):
            # restored trees land in the same rule-derived shardings a fresh init
            # would get, so the train program compiles identically across resume
            params = fabric.shard_params(params)
    elif getattr(fabric, "model_parallel", False):
        # jit with out_shardings (parallel/sharding.py): every kernel lands
        # directly in its model-axis shard — the full replicated tree never
        # materializes, so a model larger than one chip's HBM still initializes
        from sheeprl_tpu.parallel.sharding import init_sharded

        params = init_sharded(fabric.mesh, _init_all, key)
    else:
        params = jax.jit(_init_all)(key)
    return agent, params


class PlayerDV3:
    """Stateful env-interaction wrapper (reference PlayerDV3, agent.py:596-694): holds
    the per-env carry (previous action, recurrent + stochastic state) and steps all
    envs through one jitted encoder→RSSM→actor program."""

    def __init__(self, agent: DV3Agent, num_envs: int, cnn_keys: Sequence[str], mlp_keys: Sequence[str]):
        self.agent = agent
        self.num_envs = num_envs
        self.cnn_keys = tuple(cnn_keys)
        self.mlp_keys = tuple(mlp_keys)
        self.actions: Optional[jax.Array] = None
        self.recurrent_state: Optional[jax.Array] = None
        self.stochastic_state: Optional[jax.Array] = None

        agent_ref = self.agent

        def _step(params, obs: Dict[str, jax.Array], a, h, z, key, greedy: bool):
            # the PRNG chain advances inside the jitted program: an un-jitted
            # per-step jax.random.split costs ~0.5 ms of host dispatch
            key, k_repr, k_act = jax.random.split(key, 3)
            wm = params["world_model"]
            embedded = agent_ref.encoder.apply({"params": wm["encoder"]}, obs)
            h = agent_ref._recurrent(wm, z, a, h)
            _, z = agent_ref._representation(wm, h, embedded, k_repr)
            latent = jnp.concatenate([z, h], axis=-1)
            pre = agent_ref.actor.apply({"params": params["actor"]}, latent)
            mask = None
            if agent_ref.is_minedojo and "mask_action_type" in obs:
                mask = {k: obs[k] for k in MINEDOJO_MASK_KEYS if k in obs}
            actions = actor_sample(agent_ref, pre, k_act, greedy=greedy, mask=mask)
            return actions, h, z, key

        self._step = jax.jit(_step, static_argnames=("greedy",))

        def _full_init(params, n):
            h0, z0 = agent_ref.initial_state(params["world_model"], (n,))
            act_dim = int(np.sum(agent_ref.actions_dim))
            return jnp.zeros((n, act_dim), jnp.float32), h0, z0

        self._full_init = jax.jit(_full_init, static_argnames=("n",))

        def _masked_reset(params, a, h, z, mask):
            # one fixed-shape program per num_envs: resets are a `where` over a host
            # mask, not per-index eager scatters (each of which pays a dispatch and,
            # for every new index pattern, a fresh compile)
            h0, z0 = agent_ref.initial_state(params["world_model"], (a.shape[0],))
            m = mask[:, None]
            return a * (1.0 - m), jnp.where(m > 0, h0, h), jnp.where(m > 0, z0, z)

        self._masked_reset = jax.jit(_masked_reset)

    def init_states(self, params: Dict, reset_envs: Optional[Sequence[int]] = None) -> None:
        if reset_envs is None or len(reset_envs) == 0 or self.actions is None:
            self.actions, self.recurrent_state, self.stochastic_state = self._full_init(
                params, self.num_envs
            )
        else:
            mask = np.zeros((self.num_envs,), np.float32)
            mask[np.asarray(reset_envs)] = 1.0
            self.actions, self.recurrent_state, self.stochastic_state = self._masked_reset(
                params, self.actions, self.recurrent_state, self.stochastic_state, mask
            )

    def get_actions(self, params: Dict, obs: Dict[str, jax.Array], key: jax.Array, greedy: bool = False):
        """Returns ``(actions, key)`` — the advanced PRNG chain key."""
        actions, self.recurrent_state, self.stochastic_state, key = self._step(
            params, obs, self.actions, self.recurrent_state, self.stochastic_state, key, greedy
        )
        self.actions = actions
        return actions, key
