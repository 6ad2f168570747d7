"""The benchmark's own environment: seeded pixels, a seeded vector, seeded rewards.

Crafter, ALE and DIAMBRA are not installed and there is no network, so no cell can
step the game its configuration was published for. What the training loop needs from
an env is its shapes and its pace: frames of the configured size and type, one
discrete action, rewards that are not constant and episodes that end. This env gives
those from a seed and costs microseconds a step, so a cell's time is the program's.
Every frame is fresh noise (a constant frame would let XLA or a cache learn nothing,
and the replay rows of a batch would not all differ); rewards are sparse like
Crafter's achievements; episode lengths are uniform on `[episode_min, episode_max]`.
Each env's FIRST episode is short (uniform on `[16, first_episode_max]`): the player's
reset program (`PlayerDV3._masked_reset`) compiles the first time an episode ends, and
that has to happen during prefill, not some hundreds of steps later inside the window.
"""

from __future__ import annotations

from typing import Optional

import gymnasium as gym
import numpy as np


class SeededPixelEnv(gym.Env):
    metadata = {"render_modes": []}

    def __init__(
        self,
        seed: int = 0,
        screen_size: int = 64,
        channels: int = 3,
        image_key: str = "rgb",
        vector_key: Optional[str] = None,
        vector_dim: int = 0,
        actions: int = 17,
        episode_min: int = 200,
        episode_max: int = 600,
        first_episode_max: int = 64,
        reward_probability: float = 0.05,
        **_ignored,
    ):
        self._image_key, self._vector_key = image_key, vector_key
        self._shape = (int(channels), int(screen_size), int(screen_size))
        self._vector_dim = int(vector_dim)
        self._episode = (int(episode_min), int(episode_max))
        self._first_episode_max = int(first_episode_max)
        self._reward_probability = float(reward_probability)
        spaces = {image_key: gym.spaces.Box(0, 255, self._shape, np.uint8)}
        if vector_key:
            spaces[vector_key] = gym.spaces.Box(-np.inf, np.inf, (self._vector_dim,), np.float32)
        self.observation_space = gym.spaces.Dict(spaces)
        self.action_space = gym.spaces.Discrete(int(actions))
        self.reward_range = (0.0, 1.0)
        self._rng = np.random.default_rng(int(seed))
        self._left = 0

    def _obs(self):
        obs = {self._image_key: self._rng.integers(0, 256, self._shape, dtype=np.uint8)}
        if self._vector_key:
            obs[self._vector_key] = self._rng.standard_normal(self._vector_dim, dtype=np.float32)
        return obs

    def reset(self, *, seed=None, options=None):
        # the stream is fixed at construction: the loop reseeds every env of a vector
        # env with the same number, and rows of a batch have to differ
        low, high = self._episode
        if self._first_episode_max:  # see the module's docstring
            low, high, self._first_episode_max = 16, self._first_episode_max, 0
        self._left = int(self._rng.integers(low, high + 1))
        return self._obs(), {}

    def step(self, action):
        self._left -= 1
        reward = float(self._rng.random() < self._reward_probability)
        return self._obs(), reward, self._left <= 0, False, {}
