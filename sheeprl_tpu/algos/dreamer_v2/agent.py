"""Dreamer-V2 agent, Flax/JAX-native.

Capability parity with the reference agent (sheeprl/algos/dreamer_v2/agent.py:
CNNEncoder:31, MLPEncoder:84, CNNDecoder:129, MLPDecoder:191, RecurrentModel:240,
RSSM:287, PlayerDV2:735, Actor:416, build_agent:884) in the same pure-scan style as
the Dreamer-V3 module: discrete-latent RSSM without unimix, zero initial states,
ELU activations, optional layer norm, TruncatedNormal continuous policy with
exploration-noise support."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models.models import LayerNormGRUCell, resolve_activation
from sheeprl_tpu.utils.distribution import TruncatedNormal


class DenseStack(nn.Module):
    """[Dense → (LayerNorm) → act] × n — the Dreamer-V1/V2 MLP block (bias kept when
    no norm; reference MLP usage with norm_layer optional)."""

    units: int
    n_layers: int
    activation: Any = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = resolve_activation(self.activation)
        x = x.astype(self.dtype)
        for _ in range(self.n_layers):
            x = nn.Dense(self.units, use_bias=not self.layer_norm, dtype=self.dtype)(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=1e-3, dtype=self.dtype)(x)
            x = act(x)
        return x


class MLPHead(nn.Module):
    units: int
    n_layers: int
    output_dim: int
    activation: Any = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = DenseStack(self.units, self.n_layers, self.activation, self.layer_norm, self.dtype)(x)
        return nn.Dense(self.output_dim, dtype=self.dtype)(x)


class CNNEncoder(nn.Module):
    """4 k4-s2 VALID convs, channels [1,2,4,8]×multiplier (reference agent.py:31-81);
    64×64 → 2×2, flattened."""

    keys: Sequence[str]
    channels_multiplier: int
    activation: Any = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        act = resolve_activation(self.activation)
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-3)
        lead = x.shape[:-3]
        x = x.reshape(-1, *x.shape[-3:])
        x = jnp.moveaxis(x, -3, -1).astype(self.dtype)
        for i, mult in enumerate((1, 2, 4, 8)):
            x = nn.Conv(
                mult * self.channels_multiplier,
                (4, 4),
                strides=(2, 2),
                padding="VALID",
                use_bias=not self.layer_norm,
                dtype=self.dtype,
                name=f"Conv_{i}",
            )(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=1e-3, dtype=self.dtype)(x)
            x = act(x)
        return x.reshape(*lead, -1)


class MLPEncoder(nn.Module):
    keys: Sequence[str]
    mlp_layers: int = 4
    dense_units: int = 400
    activation: Any = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        return DenseStack(self.dense_units, self.mlp_layers, self.activation, self.layer_norm, self.dtype)(x)


class Encoder(nn.Module):
    cnn_encoder: Optional[CNNEncoder]
    mlp_encoder: Optional[MLPEncoder]

    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        outs = []
        if self.cnn_encoder is not None:
            outs.append(self.cnn_encoder(obs))
        if self.mlp_encoder is not None:
            outs.append(self.mlp_encoder(obs))
        return jnp.concatenate(outs, axis=-1)


class CNNDecoder(nn.Module):
    """latent → Dense(enc_out) → 1×1 spatial → deconvs k5,k5,k6,k6 s2 VALID → 64×64
    (reference agent.py:129-188)."""

    keys: Sequence[str]
    output_channels: Sequence[int]
    channels_multiplier: int
    cnn_encoder_output_dim: int
    activation: Any = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        act = resolve_activation(self.activation)
        lead = latent.shape[:-1]
        x = nn.Dense(self.cnn_encoder_output_dim, dtype=self.dtype)(latent)
        x = x.reshape(-1, 1, 1, self.cnn_encoder_output_dim)
        specs = [
            (4 * self.channels_multiplier, 5),
            (2 * self.channels_multiplier, 5),
            (1 * self.channels_multiplier, 6),
        ]
        for i, (ch, k) in enumerate(specs):
            x = nn.ConvTranspose(
                ch,
                (k, k),
                strides=(2, 2),
                padding="VALID",
                use_bias=not self.layer_norm,
                dtype=self.dtype,
                name=f"ConvTranspose_{i}",
            )(x)
            if self.layer_norm:
                x = nn.LayerNorm(epsilon=1e-3, dtype=self.dtype)(x)
            x = act(x)
        x = nn.ConvTranspose(
            sum(self.output_channels),
            (6, 6),
            strides=(2, 2),
            padding="VALID",
            dtype=self.dtype,
            name=f"ConvTranspose_{len(specs)}",
        )(x)
        x = jnp.moveaxis(x, -1, -3)
        x = x.reshape(*lead, *x.shape[-3:])
        splits = np.cumsum(self.output_channels)[:-1].tolist()
        return {k: v for k, v in zip(self.keys, jnp.split(x, splits, axis=-3))}


class MLPDecoder(nn.Module):
    keys: Sequence[str]
    output_dims: Sequence[int]
    mlp_layers: int = 4
    dense_units: int = 400
    activation: Any = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: jax.Array) -> Dict[str, jax.Array]:
        x = DenseStack(self.dense_units, self.mlp_layers, self.activation, self.layer_norm, self.dtype)(latent)
        return {k: nn.Dense(dim, dtype=self.dtype)(x) for k, dim in zip(self.keys, self.output_dims)}


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
    """MLP projection + (layer-norm) GRU cell (reference agent.py:240-284)."""

    recurrent_state_size: int
    dense_units: int
    activation: Any = "elu"
    layer_norm: bool = True
    fused_step: bool = False  # LayerNormGRUCell's: on where the programs run on one device
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, h: jax.Array) -> jax.Array:
        feat = DenseStack(self.dense_units, 1, self.activation, False, self.dtype)(x)
        return LayerNormGRUCell(
            hidden_size=self.recurrent_state_size,
            bias=True,
            layer_norm=self.layer_norm,
            fused_step=self.fused_step,
            dtype=self.dtype,
        )(h, feat)


class Actor(nn.Module):
    """Backbone + heads; continuous default is a tanh-mean TruncatedNormal
    (reference agent.py:416-574). Returns raw head outputs."""

    actions_dim: Sequence[int]
    is_continuous: bool
    dense_units: int = 400
    mlp_layers: int = 4
    activation: Any = "elu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, state: jax.Array) -> List[jax.Array]:
        x = DenseStack(self.dense_units, self.mlp_layers, self.activation, self.layer_norm, self.dtype)(state)
        if self.is_continuous:
            return [nn.Dense(int(np.sum(self.actions_dim)) * 2, dtype=self.dtype)(x)]
        return [nn.Dense(dim, dtype=self.dtype)(x) for dim in self.actions_dim]


def st_onehot_sample(logits: jax.Array, key: Optional[jax.Array], sample: bool = True) -> jax.Array:
    """Straight-through one-hot sample (or mode) over the last axis."""
    if sample:
        idx = jax.random.categorical(key, logits, axis=-1)
        onehot = jax.nn.one_hot(idx, logits.shape[-1], dtype=logits.dtype)
        probs = jax.nn.softmax(logits, axis=-1)
        return jax.lax.stop_gradient(onehot) + probs - jax.lax.stop_gradient(probs)
    idx = jnp.argmax(logits, axis=-1)
    return jax.nn.one_hot(idx, logits.shape[-1], dtype=logits.dtype)


def stochastic_state(logits: jax.Array, discrete: int, key: Optional[jax.Array] = None, sample: bool = True) -> jax.Array:
    """ST sample of the [..., S, D] categorical stack, flat in/out."""
    shaped = logits.reshape(*logits.shape[:-1], -1, discrete)
    out = st_onehot_sample(shaped, key, sample)
    return out.reshape(*out.shape[:-2], -1)


def actor_sample(
    agent: "DV2Agent", pre_dist: List[jax.Array], key: jax.Array, greedy: bool = False
) -> jax.Array:
    """Sample concatenated actions (reference Actor.forward:505-556)."""
    cfg = agent.actor_cfg
    if agent.is_continuous:
        mean, std_raw = jnp.split(pre_dist[0], 2, axis=-1)
        mean = jnp.tanh(mean)
        std = 2 * jax.nn.sigmoid((std_raw + cfg["init_std"]) / 2) + cfg["min_std"]
        dist = TruncatedNormal(mean, std, -1.0, 1.0)
        return dist.mode if greedy else dist.rsample(key)
    keys = jax.random.split(key, len(pre_dist))
    outs = []
    for i, logits in enumerate(pre_dist):
        if greedy:
            outs.append(jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1], dtype=logits.dtype))
        else:
            outs.append(st_onehot_sample(logits, keys[i]))
    return jnp.concatenate(outs, axis=-1)


def actor_logprob_entropy(
    agent: "DV2Agent", pre_dist: List[jax.Array], actions: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """(log-prob [..., 1], entropy [...]); continuous TruncatedNormal reports zero
    entropy like the reference's NotImplementedError fallback (dreamer_v2.py:334)."""
    cfg = agent.actor_cfg
    if agent.is_continuous:
        mean, std_raw = jnp.split(pre_dist[0], 2, axis=-1)
        mean = jnp.tanh(mean)
        std = 2 * jax.nn.sigmoid((std_raw + cfg["init_std"]) / 2) + cfg["min_std"]
        dist = TruncatedNormal(mean, std, -1.0, 1.0)
        lp = dist.log_prob(actions).sum(axis=-1, keepdims=True)
        return lp, jnp.zeros(lp.shape[:-1], lp.dtype)
    splits = np.cumsum(agent.actions_dim)[:-1].tolist()
    blocks = jnp.split(actions, splits, axis=-1)
    lps, ents = [], []
    for logits, act in zip(pre_dist, blocks):
        lp_all = jax.nn.log_softmax(logits, axis=-1)
        lps.append(jnp.sum(lp_all * act, axis=-1))
        ents.append(-jnp.sum(jnp.exp(lp_all) * lp_all, axis=-1))
    return jnp.stack(lps, axis=-1).sum(axis=-1, keepdims=True), jnp.stack(ents, axis=-1).sum(axis=-1)


@dataclass
class DV2Agent:
    """Params layout: {"world_model": {"encoder", "recurrent_model",
    "representation_model", "transition_model", "observation_model", "reward_model",
    "continue_model"?}, "actor", "critic", "target_critic"}."""

    encoder: Encoder
    recurrent_model: RecurrentModel
    representation_model: MLPHead
    transition_model: MLPHead
    observation_model: Decoder
    reward_model: MLPHead
    continue_model: Optional[MLPHead]
    actor: Actor
    critic: MLPHead
    actions_dim: Sequence[int]
    is_continuous: bool
    stochastic_size: int
    discrete_size: int
    recurrent_state_size: int
    actor_cfg: Dict[str, Any] = field(default_factory=dict)

    @property
    def stoch_state_size(self) -> int:
        return self.stochastic_size * self.discrete_size

    @property
    def latent_state_size(self) -> int:
        return self.stoch_state_size + self.recurrent_state_size

    def _representation(self, wm, h, embedded, key):
        logits = self.representation_model.apply(
            {"params": wm["representation_model"]}, jnp.concatenate([h, embedded], axis=-1)
        )
        return logits, stochastic_state(logits, self.discrete_size, key)

    def _transition(self, wm, h, key):
        logits = self.transition_model.apply({"params": wm["transition_model"]}, h)
        return logits, stochastic_state(logits, self.discrete_size, key)

    def _recurrent(self, wm, z, a, h):
        return self.recurrent_model.apply(
            {"params": wm["recurrent_model"]}, jnp.concatenate([z, a], axis=-1), h
        )

    def dynamic_scan(self, wm, embedded, actions, is_first, key):
        """Posterior/prior unroll; zeros initial states, is_first masks
        (reference RSSM.dynamic:333-368)."""
        T, B = embedded.shape[:2]
        keys = jax.random.split(key, T)

        def step(carry, inp):
            h, z = carry
            a, e, first, k = inp
            a = (1 - first) * a
            h = (1 - first) * h
            z = (1 - first) * z
            h = self._recurrent(wm, z, a, h)
            prior_logits, _ = self._transition(wm, h, jax.random.fold_in(k, 0))
            post_logits, z = self._representation(wm, h, e, k)
            return (h, z), (h, z, post_logits, prior_logits)

        init = (
            jnp.zeros((B, self.recurrent_state_size), embedded.dtype),
            jnp.zeros((B, self.stoch_state_size), embedded.dtype),
        )
        _, (hs, zs, post_logits, prior_logits) = jax.lax.scan(
            step, init, (actions, embedded, is_first, keys)
        )
        return hs, zs, post_logits, prior_logits

    def imagination_scan(self, wm, actor_params, z0, h0, key, horizon, act_dim):
        """DV2 imagination (reference dreamer_v2.py:218-266): action[0] is zero, the
        actor acts before each imagination step. Returns (latents [H+1, N, L],
        actions [H+1, N, A])."""
        latent0 = jnp.concatenate([z0, h0], axis=-1)

        def step(carry, k):
            z, h, latent = carry
            pre = self.actor.apply({"params": actor_params}, jax.lax.stop_gradient(latent))
            a = actor_sample(self, pre, jax.random.fold_in(k, 1))
            h = self._recurrent(wm, z, a, h)
            _, z = self._transition(wm, h, k)
            latent = jnp.concatenate([z, h], axis=-1)
            return (z, h, latent), (latent, a)

        keys = jax.random.split(key, horizon)
        _, (latents, actions) = jax.lax.scan(step, (z0, h0, latent0), keys)
        latents = jnp.concatenate([latent0[None], latents], axis=0)
        a0 = jnp.zeros((1, z0.shape[0], act_dim), latents.dtype)
        actions = jnp.concatenate([a0, actions], axis=0)
        return latents, actions


def build_agent(
    fabric,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg,
    obs_space,
    key: jax.Array,
    agent_state: Optional[Dict[str, Any]] = None,
) -> Tuple[DV2Agent, Dict[str, Any]]:
    wm_cfg = cfg.algo.world_model
    actor_cfg = cfg.algo.actor
    critic_cfg = cfg.algo.critic
    dtype = fabric.compute_dtype

    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_dec_keys = tuple(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = tuple(cfg.algo.mlp_keys.decoder)

    cnn_encoder = (
        CNNEncoder(
            keys=cnn_keys,
            channels_multiplier=wm_cfg.encoder.cnn_channels_multiplier,
            activation=cfg.algo.cnn_act,
            layer_norm=wm_cfg.encoder.get("layer_norm", cfg.algo.layer_norm),
            dtype=dtype,
        )
        if cnn_keys
        else None
    )
    mlp_encoder = (
        MLPEncoder(
            keys=mlp_keys,
            mlp_layers=wm_cfg.encoder.mlp_layers,
            dense_units=wm_cfg.encoder.dense_units,
            activation=cfg.algo.dense_act,
            layer_norm=wm_cfg.encoder.get("layer_norm", cfg.algo.layer_norm),
            dtype=dtype,
        )
        if mlp_keys
        else None
    )
    encoder = Encoder(cnn_encoder, mlp_encoder)

    stochastic_size = wm_cfg.stochastic_size
    discrete_size = wm_cfg.get("discrete_size", 1)
    stoch_state_size = stochastic_size * discrete_size
    recurrent_state_size = wm_cfg.recurrent_model.recurrent_state_size
    latent_state_size = stoch_state_size + recurrent_state_size

    recurrent_model = RecurrentModel(
        recurrent_state_size=recurrent_state_size,
        dense_units=wm_cfg.recurrent_model.dense_units,
        activation=cfg.algo.dense_act,
        layer_norm=wm_cfg.recurrent_model.get("layer_norm", True),
        fused_step=fabric.num_devices == 1,
        dtype=dtype,
    )
    representation_model = MLPHead(
        units=wm_cfg.representation_model.hidden_size,
        n_layers=1,
        output_dim=stoch_state_size,
        activation=wm_cfg.representation_model.dense_act,
        layer_norm=wm_cfg.representation_model.get("layer_norm", cfg.algo.layer_norm),
        dtype=dtype,
    )
    transition_model = MLPHead(
        units=wm_cfg.transition_model.hidden_size,
        n_layers=1,
        output_dim=stoch_state_size,
        activation=wm_cfg.transition_model.dense_act,
        layer_norm=wm_cfg.transition_model.get("layer_norm", cfg.algo.layer_norm),
        dtype=dtype,
    )
    dummy_obs = {}
    for k in cnn_keys:
        dummy_obs[k] = jnp.zeros((1, *obs_space[k].shape), jnp.float32)
    for k in mlp_keys:
        dummy_obs[k] = jnp.zeros((1, *obs_space[k].shape), jnp.float32)
    keys = jax.random.split(key, 10)
    enc_vars = encoder.init(keys[0], dummy_obs)
    embedded = encoder.apply(enc_vars, dummy_obs)
    cnn_encoder_output_dim = (
        int(np.asarray(cnn_encoder.apply({"params": enc_vars["params"]["cnn_encoder"]}, dummy_obs)).shape[-1])
        if cnn_encoder is not None
        else 0
    )

    cnn_decoder = (
        CNNDecoder(
            keys=cnn_dec_keys,
            output_channels=[int(np.prod(obs_space[k].shape[:-2])) for k in cnn_dec_keys],
            channels_multiplier=wm_cfg.observation_model.cnn_channels_multiplier,
            cnn_encoder_output_dim=cnn_encoder_output_dim,
            activation=cfg.algo.cnn_act,
            layer_norm=wm_cfg.observation_model.get("layer_norm", cfg.algo.layer_norm),
            dtype=dtype,
        )
        if cnn_dec_keys
        else None
    )
    mlp_decoder = (
        MLPDecoder(
            keys=mlp_dec_keys,
            output_dims=[obs_space[k].shape[0] for k in mlp_dec_keys],
            mlp_layers=wm_cfg.observation_model.mlp_layers,
            dense_units=wm_cfg.observation_model.dense_units,
            activation=cfg.algo.dense_act,
            layer_norm=wm_cfg.observation_model.get("layer_norm", cfg.algo.layer_norm),
            dtype=dtype,
        )
        if mlp_dec_keys
        else None
    )
    observation_model = Decoder(cnn_decoder, mlp_decoder)
    reward_model = MLPHead(
        units=wm_cfg.reward_model.dense_units,
        n_layers=wm_cfg.reward_model.mlp_layers,
        output_dim=1,
        activation=cfg.algo.dense_act,
        layer_norm=wm_cfg.reward_model.get("layer_norm", cfg.algo.layer_norm),
        dtype=dtype,
    )
    continue_model = (
        MLPHead(
            units=wm_cfg.discount_model.dense_units,
            n_layers=wm_cfg.discount_model.mlp_layers,
            output_dim=1,
            activation=cfg.algo.dense_act,
            layer_norm=wm_cfg.discount_model.get("layer_norm", cfg.algo.layer_norm),
            dtype=dtype,
        )
        if wm_cfg.use_continues
        else None
    )
    actor = Actor(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        dense_units=actor_cfg.dense_units,
        mlp_layers=actor_cfg.mlp_layers,
        activation=actor_cfg.dense_act,
        layer_norm=actor_cfg.get("layer_norm", cfg.algo.layer_norm),
        dtype=dtype,
    )
    critic = MLPHead(
        units=critic_cfg.dense_units,
        n_layers=critic_cfg.mlp_layers,
        output_dim=1,
        activation=critic_cfg.dense_act,
        layer_norm=critic_cfg.get("layer_norm", cfg.algo.layer_norm),
        dtype=dtype,
    )

    agent = DV2Agent(
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
        actor_cfg={
            "init_std": actor_cfg.init_std,
            "min_std": actor_cfg.min_std,
            "expl_amount": actor_cfg.get("expl_amount", 0.0),
            "expl_decay": actor_cfg.get("expl_decay", 0.0),
            "expl_min": actor_cfg.get("expl_min", 0.0),
        },
    )

    act_dim = int(np.sum(actions_dim))
    h = jnp.zeros((1, recurrent_state_size), jnp.float32)
    z = jnp.zeros((1, stoch_state_size), jnp.float32)
    latent = jnp.zeros((1, latent_state_size), jnp.float32)
    wm_params = {
        "encoder": enc_vars["params"],
        "recurrent_model": recurrent_model.init(
            keys[1], jnp.concatenate([z, jnp.zeros((1, act_dim), jnp.float32)], axis=-1), h
        )["params"],
        "representation_model": representation_model.init(
            keys[2], jnp.concatenate([h, embedded], axis=-1)
        )["params"],
        "transition_model": transition_model.init(keys[3], h)["params"],
        "observation_model": observation_model.init(keys[4], latent)["params"],
        "reward_model": reward_model.init(keys[5], latent)["params"],
    }
    if continue_model is not None:
        wm_params["continue_model"] = continue_model.init(keys[6], latent)["params"]
    critic_params = critic.init(keys[8], latent)["params"]
    params = {
        "world_model": wm_params,
        "actor": actor.init(keys[7], latent)["params"],
        "critic": critic_params,
        "target_critic": jax.tree_util.tree_map(jnp.copy, critic_params),
    }
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    if getattr(fabric, "model_parallel", False):
        # data x model mesh: land every kernel in its rule-derived model-axis
        # shard (parallel/sharding.py); a 1-D mesh leaves this a no-op
        params = fabric.shard_params(params)
    return agent, params


class PlayerDV2:
    """Stateful env-interaction wrapper (reference PlayerDV2, agent.py:735-884)."""

    def __init__(self, agent: DV2Agent, num_envs: int, cnn_keys: Sequence[str], mlp_keys: Sequence[str]):
        self.agent = agent
        self.num_envs = num_envs
        self.cnn_keys = tuple(cnn_keys)
        self.mlp_keys = tuple(mlp_keys)
        self.actions: Optional[jax.Array] = None
        self.recurrent_state: Optional[jax.Array] = None
        self.stochastic_state: Optional[jax.Array] = None

        agent_ref = self.agent

        def _step(params, obs, a, h, z, key, greedy: bool, expl_amount):
            wm = params["world_model"]
            embedded = agent_ref.encoder.apply({"params": wm["encoder"]}, obs)
            h = agent_ref._recurrent(wm, z, a, h)
            # chain key advanced in-program (saves ~0.5 ms/step of host dispatch)
            key, k_repr, k_act, k_expl = jax.random.split(key, 4)
            _, z = agent_ref._representation(wm, h, embedded, k_repr)
            latent = jnp.concatenate([z, h], axis=-1)
            pre = agent_ref.actor.apply({"params": params["actor"]}, latent)
            actions = actor_sample(agent_ref, pre, k_act, greedy=greedy)
            # expl_amount is a traced scalar: 0 makes the noise a no-op, so the
            # anneal schedule never triggers a recompile
            actions = add_exploration_noise(agent_ref, actions, k_expl, expl_amount)
            return actions, h, z, key

        self._step = jax.jit(_step, static_argnames=("greedy",))

    def init_states(self, params: Dict = None, reset_envs: Optional[Sequence[int]] = None) -> None:
        act_dim = int(np.sum(self.agent.actions_dim))
        if reset_envs is None or len(reset_envs) == 0:
            self.actions = jnp.zeros((self.num_envs, act_dim), jnp.float32)
            self.recurrent_state = jnp.zeros((self.num_envs, self.agent.recurrent_state_size), jnp.float32)
            self.stochastic_state = jnp.zeros((self.num_envs, self.agent.stoch_state_size), jnp.float32)
        else:
            idx = np.asarray(reset_envs)
            self.actions = self.actions.at[idx].set(0.0)
            self.recurrent_state = self.recurrent_state.at[idx].set(0.0)
            self.stochastic_state = self.stochastic_state.at[idx].set(0.0)

    def get_actions(
        self, params: Dict, obs: Dict[str, jax.Array], key: jax.Array, greedy: bool = False, expl_amount: float = 0.0
    ):
        """Returns ``(actions, key)`` — the advanced PRNG chain key."""
        actions, self.recurrent_state, self.stochastic_state, key = self._step(
            params, obs, self.actions, self.recurrent_state, self.stochastic_state, key, greedy,
            jnp.asarray(expl_amount, jnp.float32),
        )
        self.actions = actions
        return actions, key


def add_exploration_noise(agent: DV2Agent, actions: jax.Array, key: jax.Array, expl_amount: float) -> jax.Array:
    """Gaussian noise (clipped to [-1,1]) for continuous actions; epsilon-uniform
    resampling per discrete head (reference Actor.add_exploration_noise:558-574)."""
    if agent.is_continuous:
        noise = jax.random.normal(key, actions.shape, actions.dtype) * expl_amount
        return jnp.clip(actions + noise, -1.0, 1.0)
    splits = np.cumsum(agent.actions_dim)[:-1].tolist()
    blocks = jnp.split(actions, splits, axis=-1)
    outs = []
    for i, act in enumerate(blocks):
        k_sample, k_mask = jax.random.split(jax.random.fold_in(key, i))
        idx = jax.random.randint(k_sample, act.shape[:-1], 0, act.shape[-1])
        sample = jax.nn.one_hot(idx, act.shape[-1], dtype=act.dtype)
        mask = jax.random.uniform(k_mask, act.shape[:1]) < expl_amount
        outs.append(jnp.where(mask[..., None], sample, act))
    return jnp.concatenate(outs, axis=-1)
