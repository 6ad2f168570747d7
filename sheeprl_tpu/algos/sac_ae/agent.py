"""SAC-AE agent, Flax/JAX-native (pixel SAC with an autoencoder, arXiv:1910.01741).

Capability parity with the reference (sheeprl/algos/sac_ae/agent.py: CNNEncoder:26,
MLPEncoder:91, MLPDecoder:122, CNNDecoder:155, SACAEQFunction:207, SACAECritic:225,
SACAEContinuousActor:239, SACAEAgent:323, build_agent:430):

- one shared conv trunk feeds both actor and critic; each side owns its projection
  head (the reference ties ``.model`` between two encoder instances — here the
  sharing is explicit in the params pytree: ``conv`` + ``mlp_enc`` are shared,
  ``critic_cnn_fc`` / ``actor_cnn_fc`` are per-side);
- "detach encoder features" becomes ``stop_gradient`` on the trunk outputs in the
  actor path;
- the twin critics are a vmapped ensemble (stacked params, one apply);
- the decoder reconstructs all obs keys from the critic-side features.
"""

from __future__ import annotations

from dataclasses import dataclass
from math import prod
from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.sac.agent import CriticEnsemble
from sheeprl_tpu.models.models import MLP

LOG_STD_MAX = 2.0
LOG_STD_MIN = -10.0


class ConvTrunk(nn.Module):
    """The SAC-AE conv stack: 4 k3 convs (stride 2,1,1,1), ReLU, flattened output."""

    keys: Sequence[str]
    channels_multiplier: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-3)
        lead = x.shape[:-3]
        x = x.reshape(-1, *x.shape[-3:])
        x = jnp.moveaxis(x, -3, -1).astype(self.dtype)  # NCHW -> NHWC
        for stride in (2, 1, 1, 1):
            x = nn.Conv(32 * self.channels_multiplier, (3, 3), strides=(stride, stride), padding="VALID", dtype=self.dtype)(x)
            x = jax.nn.relu(x)
        return x.reshape(*lead, -1)


class EncoderFC(nn.Module):
    """Per-side projection: Dense → LayerNorm → tanh (reference CNNEncoder.fc)."""

    features_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = nn.Dense(self.features_dim, dtype=self.dtype)(x)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        return jnp.tanh(x)


class VectorEncoder(nn.Module):
    keys: Sequence[str]
    dense_units: int
    mlp_layers: int
    dense_act: Any = "relu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        return MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(x)


class CNNDecoderAE(nn.Module):
    """features → fc → conv-shape → 3 k3 s1 deconvs → k4 s2 deconv to screen_size
    (reference CNNDecoder:155-204; the final stage is k4 s2 VALID, the shape-exact
    inverse of the k3 s2 encoder stage without torch's output_padding trick)."""

    keys: Sequence[str]
    output_channels: Sequence[int]
    conv_shape: Tuple[int, int, int]  # (H, W, C) of the encoder trunk output
    channels_multiplier: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, feat: jax.Array) -> Dict[str, jax.Array]:
        lead = feat.shape[:-1]
        x = nn.Dense(int(np.prod(self.conv_shape)), dtype=self.dtype)(feat)
        x = x.reshape(-1, *self.conv_shape)
        for _ in range(3):
            x = nn.ConvTranspose(32 * self.channels_multiplier, (3, 3), strides=(1, 1), padding="VALID", dtype=self.dtype)(x)
            x = jax.nn.relu(x)
        x = nn.ConvTranspose(
            sum(self.output_channels), (4, 4), strides=(2, 2), padding="VALID", dtype=self.dtype, name="ConvTranspose_3"
        )(x)
        x = jnp.moveaxis(x, -1, -3)  # NHWC -> NCHW
        x = x.reshape(*lead, *x.shape[-3:])
        splits = np.cumsum(self.output_channels)[:-1].tolist()
        return {k: v for k, v in zip(self.keys, jnp.split(x, splits, axis=-3))}


class MLPDecoderAE(nn.Module):
    keys: Sequence[str]
    output_dims: Sequence[int]
    dense_units: int
    mlp_layers: int
    dense_act: Any = "relu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, feat: jax.Array) -> Dict[str, jax.Array]:
        x = MLP(
            hidden_sizes=(self.dense_units,) * self.mlp_layers,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(feat)
        return {
            k: nn.Dense(dim, dtype=self.dtype)(x) for k, dim in zip(self.keys, self.output_dims)
        }


class SACAEActorHead(nn.Module):
    """MLP(hidden, hidden) → mean / tanh-bounded log-std heads (reference
    SACAEContinuousActor:239-284)."""

    action_dim: int
    hidden_size: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, feat: jax.Array) -> Tuple[jax.Array, jax.Array]:
        x = MLP(hidden_sizes=(self.hidden_size, self.hidden_size), activation="relu", dtype=self.dtype)(feat)
        mean = nn.Dense(self.action_dim, dtype=self.dtype)(x)
        log_std = nn.Dense(self.action_dim, dtype=self.dtype)(x)
        log_std = jnp.tanh(log_std)
        log_std = LOG_STD_MIN + 0.5 * (LOG_STD_MAX - LOG_STD_MIN) * (log_std + 1)
        return mean, jnp.exp(log_std)


@dataclass
class SACAEAgent:
    """Module container + pure feature functions. Params layout:
    {"conv", "mlp_enc", "critic_cnn_fc", "actor_cnn_fc", "qfs", "actor",
    "log_alpha", "decoder": {"cnn", "mlp"},
    "target": {"conv", "mlp_enc", "critic_cnn_fc", "qfs"}}."""

    conv: Optional[ConvTrunk]
    mlp_enc: Optional[VectorEncoder]
    cnn_fc: Optional[EncoderFC]
    qfs: CriticEnsemble
    actor: SACAEActorHead
    cnn_decoder: Optional[CNNDecoderAE]
    mlp_decoder: Optional[MLPDecoderAE]
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    action_scale: Any = 1.0
    action_bias: Any = 0.0

    def features(
        self,
        params: Dict,
        obs: Dict[str, jax.Array],
        side: str = "critic",
        detach_encoder_features: bool = False,
        target: bool = False,
    ) -> jax.Array:
        """Concatenated encoder features. ``detach_encoder_features`` stops gradients
        at the shared trunks (the per-side cnn fc keeps training, mirroring the
        reference's detach point inside CNNEncoder.forward:77-87)."""
        src = params["target"] if target else params
        outs = []
        if self.conv is not None:
            conv_out = self.conv.apply({"params": src["conv"]}, obs)
            if detach_encoder_features:
                conv_out = jax.lax.stop_gradient(conv_out)
            fc_key = "critic_cnn_fc" if (side == "critic" or target) else "actor_cnn_fc"
            fc_params = src["critic_cnn_fc"] if target else params[fc_key]
            outs.append(self.cnn_fc.apply({"params": fc_params}, conv_out))
        if self.mlp_enc is not None:
            mlp_out = self.mlp_enc.apply({"params": src["mlp_enc"]}, obs)
            if detach_encoder_features:
                mlp_out = jax.lax.stop_gradient(mlp_out)
            outs.append(mlp_out)
        return jnp.concatenate(outs, axis=-1)

    def reconstruct(self, params: Dict, feat: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder.apply({"params": params["decoder"]["cnn"]}, feat))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder.apply({"params": params["decoder"]["mlp"]}, feat))
        return out


def build_agent(
    fabric,
    cfg,
    observation_space,
    action_space,
    key: jax.Array,
    state: Optional[Dict[str, Any]] = None,
) -> Tuple[SACAEAgent, Dict[str, Any]]:
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_dec_keys = tuple(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = tuple(cfg.algo.mlp_keys.decoder)
    dtype = fabric.compute_dtype
    act_dim = int(prod(action_space.shape))
    cm = int(cfg.algo.cnn_channels_multiplier)
    screen = int(cfg.env.screen_size)

    conv = ConvTrunk(keys=cnn_keys, channels_multiplier=cm, dtype=dtype) if cnn_keys else None
    cnn_fc = EncoderFC(features_dim=cfg.algo.encoder.features_dim, dtype=dtype) if cnn_keys else None
    mlp_enc = (
        VectorEncoder(
            keys=mlp_keys,
            dense_units=cfg.algo.encoder.dense_units,
            mlp_layers=cfg.algo.encoder.mlp_layers,
            dense_act=cfg.algo.encoder.dense_act,
            layer_norm=cfg.algo.encoder.layer_norm,
            dtype=dtype,
        )
        if mlp_keys
        else None
    )
    qfs = CriticEnsemble(n=cfg.algo.critic.n, hidden_size=cfg.algo.hidden_size, dtype=dtype)
    actor = SACAEActorHead(action_dim=act_dim, hidden_size=cfg.algo.hidden_size, dtype=dtype)

    # encoder trunk output spatial shape: k3 s2 then 3× k3 s1 on screen×screen;
    # the decoder's k4-s2 final stage inverts this exactly only for even sizes
    if screen % 2 != 0:
        raise ValueError(f"SAC-AE requires an even env.screen_size, got {screen}")
    s = (screen - 3) // 2 + 1
    s = s - 2 * 3  # three stride-1 k3 convs each remove 2
    conv_shape = (s, s, 32 * cm)

    cnn_decoder = (
        CNNDecoderAE(
            keys=cnn_dec_keys,
            output_channels=[int(np.prod(observation_space[k].shape[:-2])) for k in cnn_dec_keys],
            conv_shape=conv_shape,
            channels_multiplier=cm,
            dtype=dtype,
        )
        if cnn_dec_keys
        else None
    )
    mlp_decoder = (
        MLPDecoderAE(
            keys=mlp_dec_keys,
            output_dims=[observation_space[k].shape[0] for k in mlp_dec_keys],
            dense_units=cfg.algo.decoder.dense_units,
            mlp_layers=cfg.algo.decoder.mlp_layers,
            dense_act=cfg.algo.decoder.dense_act,
            layer_norm=cfg.algo.decoder.layer_norm,
            dtype=dtype,
        )
        if mlp_dec_keys
        else None
    )

    agent = SACAEAgent(
        conv=conv,
        mlp_enc=mlp_enc,
        cnn_fc=cnn_fc,
        qfs=qfs,
        actor=actor,
        cnn_decoder=cnn_decoder,
        mlp_decoder=mlp_decoder,
        cnn_keys=cnn_keys,
        mlp_keys=mlp_keys,
        action_scale=jnp.asarray((np.asarray(action_space.high) - np.asarray(action_space.low)) / 2.0, jnp.float32),
        action_bias=jnp.asarray((np.asarray(action_space.high) + np.asarray(action_space.low)) / 2.0, jnp.float32),
    )

    keys = jax.random.split(key, 8)
    dummy_obs = {}
    for k in cnn_keys:
        shape = observation_space[k].shape
        # frame-stack dims fold into channels (runtime prepare_obs does the same)
        dummy_obs[k] = jnp.zeros((1, int(np.prod(shape[:-2])), *shape[-2:]), jnp.float32)
    for k in mlp_keys:
        dummy_obs[k] = jnp.zeros((1, *observation_space[k].shape), jnp.float32)
    dummy_act = jnp.zeros((1, act_dim), jnp.float32)

    params: Dict[str, Any] = {"log_alpha": jnp.log(jnp.asarray([cfg.algo.alpha.alpha], jnp.float32))}
    feat_parts = []
    if conv is not None:
        params["conv"] = conv.init(keys[0], dummy_obs)["params"]
        conv_out = conv.apply({"params": params["conv"]}, dummy_obs)
        params["critic_cnn_fc"] = cnn_fc.init(keys[1], conv_out)["params"]
        params["actor_cnn_fc"] = cnn_fc.init(keys[2], conv_out)["params"]
        feat_parts.append(cnn_fc.apply({"params": params["critic_cnn_fc"]}, conv_out))
    if mlp_enc is not None:
        params["mlp_enc"] = mlp_enc.init(keys[3], dummy_obs)["params"]
        feat_parts.append(mlp_enc.apply({"params": params["mlp_enc"]}, dummy_obs))
    feat = jnp.concatenate(feat_parts, axis=-1)
    params["qfs"] = qfs.init(keys[4], feat, dummy_act)["params"]
    params["actor"] = actor.init(keys[5], feat)["params"]
    params["decoder"] = {}
    if cnn_decoder is not None:
        params["decoder"]["cnn"] = cnn_decoder.init(keys[6], feat)["params"]
    if mlp_decoder is not None:
        params["decoder"]["mlp"] = mlp_decoder.init(keys[7], feat)["params"]
    params["target"] = {
        k: jax.tree_util.tree_map(jnp.copy, params[k])
        for k in ("conv", "mlp_enc", "critic_cnn_fc", "qfs")
        if k in params
    }
    if state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, state)
    return agent, params
