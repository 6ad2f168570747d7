"""The ``JaxEnv`` protocol: environments as pure functions over pytrees.

An on-device environment is two pure functions plus a static spec:

- ``reset(key) -> (state, obs)`` — build a fresh episode state from a PRNG key;
- ``step(state, action) -> (state, obs, reward, done, info)`` — advance one
  step. ``info`` is a dict of fixed-shape arrays (it must be scan-able), with
  the keys produced by the :class:`~sheeprl_tpu.envs.jax.wrappers.AutoReset`
  wrapper contract documented in ``howto/jax_envs.md``.

``state`` is an arbitrary pytree; both functions must be jit/vmap/scan-safe
(no Python control flow on traced values, no host callbacks). Batching over a
``num_envs`` leading axis is the wrapper's job
(:class:`~sheeprl_tpu.envs.jax.wrappers.VmapEnv`), not the environment's:
every env here is written single-instance.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np


@dataclass(frozen=True)
class ActionSpec:
    """Static action-space descriptor.

    ``kind='discrete'``: ``num_actions`` categorical actions, taken as an int32
    scalar. ``kind='continuous'``: a float vector of ``shape`` bounded by
    ``low``/``high`` (broadcastable scalars kept static for jit closure).
    """

    kind: str  # "discrete" | "continuous"
    num_actions: int = 0
    shape: Tuple[int, ...] = ()
    low: float = -1.0
    high: float = 1.0

    def __post_init__(self) -> None:
        if self.kind not in ("discrete", "continuous"):
            raise ValueError(f"unknown action kind {self.kind!r}")

    @property
    def actions_dim(self) -> Tuple[int, ...]:
        """The per-head action dims in the agents' convention (one categorical
        head of ``num_actions`` logits, or one continuous head of ``shape``)."""
        if self.kind == "discrete":
            return (int(self.num_actions),)
        return tuple(int(s) for s in self.shape)

    def to_gym_space(self):
        """The equivalent gymnasium space (adapter + agent-building path)."""
        import gymnasium as gym

        if self.kind == "discrete":
            return gym.spaces.Discrete(int(self.num_actions))
        return gym.spaces.Box(self.low, self.high, self.shape, np.float32)


@dataclass(frozen=True)
class EnvSpec:
    """Static environment descriptor: observation shape/dtype + action spec."""

    obs_shape: Tuple[int, ...]
    action: ActionSpec
    obs_dtype: Any = np.float32
    # bounds are informational (the adapter's observation_space); pure-plane
    # consumers never clip observations
    obs_low: float = -np.inf
    obs_high: float = np.inf
    # populated by wrappers/envs that truncate episodes at a step budget; the
    # Anakin rollout uses it to decide statically whether to pay the
    # truncation-bootstrap value pass
    max_episode_steps: Optional[int] = None
    # set by envs whose every episode lasts exactly this many steps (the token env):
    # the sequence flavour of the Anakin loop makes one rollout one episode
    episode_steps: Optional[int] = None

    def to_gym_obs_space(self):
        import gymnasium as gym

        return gym.spaces.Box(self.obs_low, self.obs_high, self.obs_shape, self.obs_dtype)


class JaxEnv:
    """Base class for on-device environments (duck-typed protocol: anything with
    ``spec``/``reset``/``step`` of the right signatures works)."""

    spec: EnvSpec

    def reset(self, key: jax.Array) -> Tuple[Any, jax.Array]:
        raise NotImplementedError

    def step(
        self, state: Any, action: jax.Array
    ) -> Tuple[Any, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        raise NotImplementedError
