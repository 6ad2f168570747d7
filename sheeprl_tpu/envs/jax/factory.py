"""Factories for the on-device env plane + the gymnasium compatibility adapter.

Two consumers, one id namespace (:data:`JAX_ENV_IDS`):

- :func:`make_jax_env` — the pure plane: resolve ``cfg.env.id``, apply the
  :class:`AutoReset` contract and vmap-batch over ``num_envs``. This is what
  the Anakin topology fuses into its jitted program.
- :class:`JaxToGymEnv` — a ``gym.Env`` stepping the same pure functions on the
  host CPU backend, so ``env.backend=jax`` slots behind the existing
  ``make_env`` factory and every host-env loop/wrapper/test keeps working.

Gridworld ids take an optional size suffix: ``gridworld_four_rooms-16`` is the
16x16 four-rooms member.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import gymnasium as gym
import jax
import numpy as np

from sheeprl_tpu.envs.jax.base import JaxEnv
from sheeprl_tpu.envs.jax.classic import CartPole, Pendulum
from sheeprl_tpu.envs.jax.gridworld import GridWorld
from sheeprl_tpu.envs.jax.tokens import TokenCopy
from sheeprl_tpu.envs.jax.wrappers import AutoReset, VmapEnv
from sheeprl_tpu.utils.utils import host_cpu_device

# id -> (constructor, default max_episode_steps — gymnasium's registered
# TimeLimit for the classics, a 4*N*N step budget for gridworlds)
JAX_ENV_IDS = ("CartPole-v1", "Pendulum-v1", "gridworld_empty", "gridworld_four_rooms", "token_copy")


def resolve_jax_env(env_id: str, tokens: Optional[Any] = None) -> Tuple[JaxEnv, Optional[int]]:
    """Build the bare single-instance env for ``env_id`` and return it with the
    id's default episode step budget. ``tokens`` is the ``env.tokens`` group the
    token env takes its sizes from (it ends its own episodes: no step budget)."""
    if env_id == "token_copy":
        if tokens is None:
            raise ValueError("token_copy needs env.tokens (vocab_size, episode_steps, prompt_min, prompt_max)")
        return TokenCopy(int(tokens.vocab_size), int(tokens.episode_steps), int(tokens.prompt_min),
                         int(tokens.prompt_max)), None
    if env_id == "CartPole-v1":
        return CartPole(), 500
    if env_id == "Pendulum-v1":
        return Pendulum(), 200
    if env_id.startswith("gridworld_"):
        base, _, size_suffix = env_id.partition("-")
        layout = base[len("gridworld_"):]
        size = int(size_suffix) if size_suffix else 8
        return GridWorld(size=size, layout=layout), 4 * size * size
    raise ValueError(
        f"unknown jax env id {env_id!r}; the on-device plane provides {JAX_ENV_IDS} "
        "(see howto/jax_envs.md to add one)"
    )


def make_jax_env(cfg: Any, num_envs: int) -> VmapEnv:
    """The pure plane entry point: ``cfg.env.id`` resolved, AutoReset applied
    (``cfg.env.max_episode_steps`` overrides the id default; <= 0 disables
    truncation entirely), batched over ``num_envs``."""
    env, default_limit = resolve_jax_env(str(cfg.env.id), cfg.env.get("tokens", None))
    limit = cfg.env.get("max_episode_steps", None)
    limit = default_limit if limit is None else (int(limit) if int(limit) > 0 else None)
    return VmapEnv(AutoReset(env, max_episode_steps=limit), num_envs)


class JaxToGymEnv(gym.Env):
    """gymnasium adapter over a pure :class:`JaxEnv` (``env.backend=jax`` behind
    ``make_env``). Steps run through jitted functions pinned to the host CPU
    backend — the host plane's loops treat this exactly like any other gym env,
    including TimeLimit/RecordEpisodeStatistics stacking on top."""

    metadata = {"render_modes": []}
    render_mode = None

    def __init__(
        self,
        id: str,
        seed: int = 0,
        max_episode_steps: Optional[int] = None,
        apply_default_time_limit: bool = True,
    ):
        self._env, default_limit = resolve_jax_env(id)
        self.id = id
        if max_episode_steps is None and apply_default_time_limit:
            max_episode_steps = default_limit
        self._max_episode_steps = max_episode_steps
        self.observation_space = self._env.spec.to_gym_obs_space()
        self.action_space = self._env.spec.action.to_gym_space()
        # pin the step/reset programs to the host CPU backend by committing the
        # PRNG chain there: committed inputs drive jit placement, and the env
        # state stays committed across steps (jit's deprecated backend= kwarg
        # is avoided). Same placement as the act program it feeds (ActPlacement).
        self._cpu = host_cpu_device()
        self._reset_fn = jax.jit(self._env.reset)
        self._step_fn = jax.jit(self._env.step)
        self._key = jax.device_put(jax.random.PRNGKey(seed), self._cpu)
        self._state: Any = None
        self._elapsed = 0
        # gym.Env duck compatibility without inheriting (gym.Env is pure protocol)
        self.spec = gym.envs.registration.EnvSpec(id=f"jax/{id}")

    def reset(self, *, seed: Optional[int] = None, options: Optional[Dict] = None):
        if seed is not None:
            self._key = jax.device_put(jax.random.PRNGKey(seed), self._cpu)
        self._key, reset_key = jax.random.split(self._key)
        self._state, obs = self._reset_fn(reset_key)
        self._elapsed = 0
        return np.asarray(obs), {}

    def step(self, action):
        if self._env.spec.action.kind == "discrete":
            action = np.int32(action)
        else:
            action = np.asarray(action, np.float32)
        self._state, obs, reward, done, _ = self._step_fn(self._state, action)
        self._elapsed += 1
        terminated = bool(done)
        truncated = bool(
            self._max_episode_steps is not None
            and self._elapsed >= self._max_episode_steps
            and not terminated
        )
        return np.asarray(obs), float(reward), terminated, truncated, {}

    def render(self):
        return None

    def close(self):
        pass

    @property
    def unwrapped(self):
        return self
