"""Offline Dreamer agent: Dreamer-V3 plus a Concept-Bottleneck World Model.

Capability parity with reference sheeprl/algos/offline_dreamer/agent.py: the ``CEM``
concept-embedding module (reference agent.py:943-1026) maps the RSSM latent into
``sum(concept_bins)`` concept probabilities + per-concept embeddings + one residual
(non-concept) embedding; every head (decoder/reward/continue/actor/critic) then
consumes this concept latent instead of the raw one (reference agent.py:1101-1299,
CBWM at agent.py:1030). With ``use_cbm: False`` the agent degenerates to Dreamer-V3.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import (
    Actor,
    CNNDecoder,
    CNNEncoder,
    Decoder,
    DV3Agent,
    Encoder,
    MLPDecoder,
    MLPEncoder,
    MLPHead,
    RecurrentModel,
    actor_sample,
)


class CEM(nn.Module):
    """Concept Embedding Module (reference CEM, offline_dreamer/agent.py:943-1026).

    For each concept ``c`` a context head produces ``concept_bins[c]`` candidate
    embeddings of size ``emb_size``; a prob head scores the bins; the concept
    embedding is the prob-weighted sum of the candidates. One extra context head
    produces the residual (non-concept) embedding. Output latent =
    ``concat(all bin probs, all concept embeddings, residual)`` of size
    ``sum(concept_bins) + (n_concepts + 1) * emb_size``.
    """

    n_concepts: int
    concept_bins: Tuple[int, ...]
    emb_size: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        probs_blocks = []
        logits_blocks = []
        emb_blocks = []
        for c in range(self.n_concepts):
            bins = self.concept_bins[c]
            context = nn.Dense(bins * self.emb_size, dtype=self.dtype, name=f"context_{c}")(latent)
            logits = nn.Dense(bins, dtype=self.dtype, name=f"prob_{c}")(context)
            probs = jax.nn.softmax(logits, axis=-1)
            # prob-weighted mixture of the per-bin candidate embeddings
            candidates = context.reshape(*context.shape[:-1], bins, self.emb_size)
            emb = jnp.sum(candidates * probs[..., None], axis=-2)
            probs_blocks.append(probs)
            logits_blocks.append(logits)
            emb_blocks.append(emb)
        residual = nn.Dense(self.emb_size, dtype=self.dtype, name=f"context_{self.n_concepts}")(latent)
        all_probs = jnp.concatenate(probs_blocks, axis=-1)
        all_logits = jnp.concatenate(logits_blocks, axis=-1)
        concept_emb = jnp.concatenate(emb_blocks, axis=-1)
        cem_latent = jnp.concatenate([all_probs, concept_emb, residual], axis=-1)
        return cem_latent, all_logits, concept_emb, residual


def cem_latent_size(cfg) -> int:
    cbm = cfg.algo.world_model.cbm_model
    return int(sum(cbm.concept_bins) + (cbm.n_concepts + 1) * cbm.emb_size)


@dataclass
class ODV3Agent(DV3Agent):
    """DV3Agent + optional CEM bottleneck. When ``use_cbm`` the heads read the CEM
    latent and ``wm_params["cem"]`` holds the bottleneck parameters."""

    cem: Optional[CEM] = None
    use_cbm: bool = False

    @property
    def head_latent_size(self) -> int:
        if self.use_cbm:
            return int(
                sum(self.cem.concept_bins) + (self.cem.n_concepts + 1) * self.cem.emb_size
            )
        return self.latent_state_size

    def apply_cem(self, wm_params: Dict, latent: jax.Array):
        """Returns (head_latent, concept_logits, concept_emb, residual); identity
        (with empty aux) when the bottleneck is disabled."""
        if not self.use_cbm:
            return latent, None, None, None
        return self.cem.apply({"params": wm_params["cem"]}, latent)

    def imagination_scan(
        self,
        wm_params: Dict,
        actor_params: Dict,
        z0: jax.Array,
        h0: jax.Array,
        key: jax.Array,
        horizon: int,
    ) -> Tuple[jax.Array, jax.Array]:
        """Latent imagination with the CEM applied at every step (reference
        behaviour_learning, offline_dreamer.py:110-172): the recorded trajectory and
        the actor inputs are CEM latents; the RSSM dynamics still evolve (z, h)."""
        if not self.use_cbm:
            return super().imagination_scan(wm_params, actor_params, z0, h0, key, horizon)

        k0, kscan = jax.random.split(key)
        latent0, _, _, _ = self.apply_cem(wm_params, jnp.concatenate([z0, h0], axis=-1))
        pre = self.actor.apply({"params": actor_params}, jax.lax.stop_gradient(latent0))
        a0 = actor_sample(self, pre, k0)

        def step(carry, k):
            z, h, a = carry
            h = self._recurrent(wm_params, z, a, h)
            _, z = self._transition(wm_params, h, k)
            latent, _, _, _ = self.apply_cem(wm_params, jnp.concatenate([z, h], axis=-1))
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
) -> Tuple[ODV3Agent, Dict[str, Any]]:
    """Role of reference offline_dreamer build_agent (agent.py:1055-1360): identical
    to the Dreamer-V3 build except every head's input is the CEM latent size."""
    wm_cfg = cfg.algo.world_model
    actor_cfg = cfg.algo.actor
    critic_cfg = cfg.algo.critic
    cbm_cfg = wm_cfg.cbm_model
    use_cbm = bool(cbm_cfg.use_cbm)
    dtype = fabric.compute_dtype
    if wm_cfg.get("decoupled_rssm", False):
        raise NotImplementedError(
            "decoupled_rssm is not implemented yet; set algo.world_model.decoupled_rssm=False"
        )

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
    cem = (
        CEM(
            n_concepts=int(cbm_cfg.n_concepts),
            concept_bins=tuple(int(b) for b in cbm_cfg.concept_bins),
            emb_size=int(cbm_cfg.emb_size),
            dtype=dtype,
        )
        if use_cbm
        else None
    )
    head_latent_size = (
        int(sum(cbm_cfg.concept_bins) + (cbm_cfg.n_concepts + 1) * cbm_cfg.emb_size)
        if use_cbm
        else latent_state_size
    )

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
    actor = Actor(
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

    agent = ODV3Agent(
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
        cem=cem,
        use_cbm=use_cbm,
    )

    # -- init params -------------------------------------------------------------
    keys = jax.random.split(key, 11)
    dummy_obs = {}
    for k in cnn_keys:
        dummy_obs[k] = jnp.zeros((1, *obs_space[k].shape), jnp.float32)
    for k in mlp_keys:
        dummy_obs[k] = jnp.zeros((1, *obs_space[k].shape), jnp.float32)
    embed_dim_probe = encoder.init(keys[0], dummy_obs)
    embedded = encoder.apply(embed_dim_probe, dummy_obs)
    act_dim = int(np.sum(actions_dim))
    h = jnp.zeros((1, recurrent_state_size), jnp.float32)
    z = jnp.zeros((1, stoch_state_size), jnp.float32)
    latent = jnp.zeros((1, latent_state_size), jnp.float32)
    head_latent = jnp.zeros((1, head_latent_size), jnp.float32)

    wm_params = {
        "encoder": embed_dim_probe["params"],
        "recurrent_model": recurrent_model.init(
            keys[1], jnp.concatenate([z, jnp.zeros((1, act_dim), jnp.float32)], axis=-1), h
        )["params"],
        "representation_model": representation_model.init(
            keys[2], jnp.concatenate([h, embedded], axis=-1)
        )["params"],
        "transition_model": transition_model.init(keys[3], h)["params"],
        "observation_model": observation_model.init(keys[4], head_latent)["params"],
        "reward_model": reward_model.init(keys[5], head_latent)["params"],
        "continue_model": continue_model.init(keys[6], head_latent)["params"],
        "initial_recurrent_state": jnp.zeros((recurrent_state_size,), jnp.float32),
    }
    if use_cbm:
        wm_params["cem"] = cem.init(keys[9], latent)["params"]
    actor_params = actor.init(keys[7], head_latent)["params"]
    critic_params = critic.init(keys[8], head_latent)["params"]
    params = {
        "world_model": wm_params,
        "actor": actor_params,
        "critic": critic_params,
        # a REAL copy: the donated train program must never see the same buffer in
        # two leaves (XLA rejects f(donate(a), donate(a)))
        "target_critic": jax.tree_util.tree_map(jnp.copy, critic_params),
    }
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    if getattr(fabric, "model_parallel", False):
        # data x model mesh: land every kernel in its rule-derived model-axis
        # shard (parallel/sharding.py); a 1-D mesh leaves this a no-op
        params = fabric.shard_params(params)
    return agent, params


class PlayerODV3:
    """Stateful env-interaction wrapper (reference PlayerODV3, agent.py:597-694):
    PlayerDV3 with the CEM applied to the latent before the actor (agent.py:693-694)."""

    def __init__(self, agent: ODV3Agent, num_envs: int, cnn_keys: Sequence[str], mlp_keys: Sequence[str]):
        self.agent = agent
        self.num_envs = num_envs
        self.cnn_keys = tuple(cnn_keys)
        self.mlp_keys = tuple(mlp_keys)
        self.actions: Optional[jax.Array] = None
        self.recurrent_state: Optional[jax.Array] = None
        self.stochastic_state: Optional[jax.Array] = None

        agent_ref = self.agent

        def _step(params, obs: Dict[str, jax.Array], a, h, z, key, greedy: bool):
            key, k_repr, k_act = jax.random.split(key, 3)
            wm = params["world_model"]
            embedded = agent_ref.encoder.apply({"params": wm["encoder"]}, obs)
            h = agent_ref._recurrent(wm, z, a, h)
            _, z = agent_ref._representation(wm, h, embedded, k_repr)
            latent = jnp.concatenate([z, h], axis=-1)
            latent, _, _, _ = agent_ref.apply_cem(wm, latent)
            pre = agent_ref.actor.apply({"params": params["actor"]}, latent)
            actions = actor_sample(agent_ref, pre, k_act, greedy=greedy)
            return actions, h, z, key

        self._step = jax.jit(_step, static_argnames=("greedy",))

    def init_states(self, params: Dict, reset_envs: Optional[Sequence[int]] = None) -> None:
        act_dim = int(np.sum(self.agent.actions_dim))
        if reset_envs is None or len(reset_envs) == 0:
            h0, z0 = self.agent.initial_state(params["world_model"], (self.num_envs,))
            self.actions = jnp.zeros((self.num_envs, act_dim), jnp.float32)
            self.recurrent_state = h0
            self.stochastic_state = z0
        else:
            idx = np.asarray(reset_envs)
            h0, z0 = self.agent.initial_state(params["world_model"], (len(idx),))
            self.actions = self.actions.at[idx].set(0.0)
            self.recurrent_state = self.recurrent_state.at[idx].set(h0)
            self.stochastic_state = self.stochastic_state.at[idx].set(z0)

    def get_actions(self, params: Dict, obs: Dict[str, jax.Array], key: jax.Array, greedy: bool = False):
        """Returns ``(actions, key)`` — the advanced PRNG chain key."""
        actions, self.recurrent_state, self.stochastic_state, key = self._step(
            params, obs, self.actions, self.recurrent_state, self.stochastic_state, key, greedy
        )
        self.actions = actions
        return actions, key
