"""A token env with a reward the device can check: copy the prompt back.

An episode is exactly ``episode_steps`` steps. The first ``P`` of them (``P`` uniform on
``[prompt_min, prompt_max]``, drawn from the key) feed a prompt of ids drawn from the
vocabulary, one a step, and the action is ignored: ``info["action_mask"]`` is 0 there.
From step ``P`` on the observation is the agent's last action, and the reward is 1 where
the action equals the prompt's token at ``(t - P) mod P``, else 0.

The observation is ONE token id (int32 scalar): the policy is a sequence model that
keeps its own state over the episode (``algos/ppo/anakin.py``, the sequence flavour).
Pure functions of ``(state, action)``, as ``base.py`` asks.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.envs.jax.base import ActionSpec, EnvSpec, JaxEnv


class TokenCopyState(NamedTuple):
    t: jax.Array  # int32 step inside the episode
    prompt_len: jax.Array  # int32 P
    prompt: jax.Array  # [prompt_max] int32, the first P in use


class TokenCopy(JaxEnv):
    def __init__(self, vocab_size: int, episode_steps: int, prompt_min: int = 32, prompt_max: int = 96):
        if not 1 <= prompt_min <= prompt_max < episode_steps:
            raise ValueError(
                f"need 1 <= prompt_min ({prompt_min}) <= prompt_max ({prompt_max}) < episode_steps ({episode_steps})"
            )
        self.vocab_size, self.episode_steps = int(vocab_size), int(episode_steps)
        self.prompt_min, self.prompt_max = int(prompt_min), int(prompt_max)
        self.spec = EnvSpec(
            obs_shape=(),
            action=ActionSpec(kind="discrete", num_actions=self.vocab_size),
            obs_dtype=np.int32,
            obs_low=0,
            obs_high=self.vocab_size - 1,
            episode_steps=self.episode_steps,
        )

    def reset(self, key: jax.Array) -> Tuple[TokenCopyState, jax.Array]:
        k_len, k_prompt = jax.random.split(key)
        prompt_len = jax.random.randint(k_len, (), self.prompt_min, self.prompt_max + 1, jnp.int32)
        prompt = jax.random.randint(k_prompt, (self.prompt_max,), 0, self.vocab_size, jnp.int32)
        return TokenCopyState(jnp.int32(0), prompt_len, prompt), prompt[0]

    def step(
        self, state: TokenCopyState, action: jax.Array
    ) -> Tuple[TokenCopyState, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        t, p = state.t, state.prompt_len
        action = action.astype(jnp.int32)
        counts = t >= p  # the action at this step is the agent's to answer for
        target = state.prompt[jnp.mod(t - p, p)]
        reward = jnp.where(counts & (action == target), 1.0, 0.0).astype(jnp.float32)
        nxt = t + 1
        obs = jnp.where(nxt < p, state.prompt[jnp.minimum(nxt, self.prompt_max - 1)], action)
        done = nxt >= self.episode_steps
        return TokenCopyState(nxt, p, state.prompt), obs, reward, done, {"action_mask": counts.astype(jnp.float32)}
