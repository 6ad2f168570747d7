"""Pure-JAX env wrappers: the autoreset contract and vmap batching.

``AutoReset`` reproduces the host plane's SAME_STEP autoreset semantics
(gym.vector.AutoresetMode.SAME_STEP, see ``algos/ppo/ppo.py``): the step that
ends an episode returns the *fresh reset observation* as the next observation,
the terminal observation rides in ``info["terminal_observation"]``, and
truncation (step-budget exhaustion) is reported separately from termination so
the rollout can bootstrap truncated episodes exactly like the host loops.
Episode return/length accumulate in carried state and surface in ``info`` on
the done step — the role of ``RecordEpisodeStatistics``.

``VmapEnv`` lifts a single-instance env to a ``num_envs`` leading axis with
``jax.vmap``; composition order is ``VmapEnv(AutoReset(env))`` so every
instance resets independently inside one fused program.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.envs.jax.base import EnvSpec, JaxEnv


class AutoResetState(NamedTuple):
    inner: Any  # wrapped env's state
    key: jax.Array  # PRNG chain for in-step resets
    episode_return: jax.Array  # float32 running return of the CURRENT episode
    episode_length: jax.Array  # int32 running length of the CURRENT episode


class AutoReset(JaxEnv):
    """done -> fresh reset inside ``step`` (branchless: the reset is computed
    every step and selected by the done mask — classic-control/gridworld resets
    are a handful of ops, so this stays cheaper than any ``lax.cond`` under
    vmap, where both branches execute anyway)."""

    def __init__(self, env: JaxEnv, max_episode_steps: int | None = None):
        self.env = env
        self.max_episode_steps = int(max_episode_steps) if max_episode_steps else None
        self.spec = EnvSpec(
            obs_shape=env.spec.obs_shape,
            action=env.spec.action,
            obs_dtype=env.spec.obs_dtype,
            obs_low=env.spec.obs_low,
            obs_high=env.spec.obs_high,
            max_episode_steps=self.max_episode_steps,
            episode_steps=env.spec.episode_steps,
        )

    def reset(self, key: jax.Array) -> Tuple[AutoResetState, jax.Array]:
        key, reset_key = jax.random.split(key)
        inner, obs = self.env.reset(reset_key)
        state = AutoResetState(
            inner=inner,
            key=key,
            episode_return=jnp.float32(0.0),
            episode_length=jnp.int32(0),
        )
        return state, obs

    def step(
        self, state: AutoResetState, action: jax.Array
    ) -> Tuple[AutoResetState, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        inner, obs, reward, terminated, inner_info = self.env.step(state.inner, action)
        episode_return = state.episode_return + reward
        episode_length = state.episode_length + 1
        if self.max_episode_steps is not None:
            truncated = (episode_length >= self.max_episode_steps) & ~terminated
        else:
            truncated = jnp.bool_(False)
        done = terminated | truncated

        key, reset_key = jax.random.split(state.key)
        reset_inner, reset_obs = self.env.reset(reset_key)
        new_inner = jax.tree_util.tree_map(
            lambda r, s: jnp.where(done, r, s), reset_inner, inner
        )
        new_obs = jnp.where(done, reset_obs, obs)
        new_state = AutoResetState(
            inner=new_inner,
            key=key,
            episode_return=jnp.where(done, 0.0, episode_return).astype(jnp.float32),
            episode_length=jnp.where(done, 0, episode_length).astype(jnp.int32),
        )
        info = {
            **inner_info,  # the env's own fixed-shape arrays (e.g. the token env's action mask)
            # the pre-reset observation of THIS step (the host plane's
            # infos["final_obs"]); valid only where done
            "terminal_observation": obs,
            "terminated": terminated,
            "truncated": truncated,
            # episode stats of the episode that ENDED this step; valid where done
            "episode_return": episode_return,
            "episode_length": episode_length,
        }
        return new_state, new_obs, reward, done, info


class VmapEnv(JaxEnv):
    """Batch a single-instance env over a ``num_envs`` leading axis. ``reset``
    takes ONE key and fans it out; ``step`` maps state/action elementwise."""

    def __init__(self, env: JaxEnv, num_envs: int):
        if num_envs < 1:
            raise ValueError(f"num_envs must be >= 1, got {num_envs}")
        self.env = env
        self.num_envs = int(num_envs)
        self.spec = env.spec
        self._reset = jax.vmap(env.reset)
        self._step = jax.vmap(env.step)

    def reset(self, key: jax.Array) -> Tuple[Any, jax.Array]:
        return self._reset(jax.random.split(key, self.num_envs))

    def step(
        self, state: Any, action: jax.Array
    ) -> Tuple[Any, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        return self._step(state, action)
