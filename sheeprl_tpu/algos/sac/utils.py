"""SAC helpers: metric whitelist, obs preparation, greedy test rollout
(reference: sheeprl/algos/sac/utils.py)."""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.utils.utils import host_cpu_device

AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/value_loss",
    "Loss/policy_loss",
    "Loss/alpha_loss",
}
MODELS_TO_REGISTER = {"agent"}


def prepare_obs(
    fabric, obs: Dict[str, np.ndarray], *, mlp_keys: Sequence[str] = (), num_envs: int = 1, **_: Any
) -> jax.Array:
    """Concatenate the mlp-key observations into one flat float array
    [num_envs, obs_dim] (reference utils.py:prepare_obs)."""
    with jax.default_device(host_cpu_device()):
        return jnp.concatenate(
            [np.asarray(obs[k], dtype=np.float32).reshape(num_envs, -1) for k in mlp_keys], axis=-1
        )


def test(actor_apply, params, fabric, cfg, log_dir: str) -> None:
    """Greedy (mean-action) single-env rollout logging Test/cumulative_reward
    (reference utils.py:test)."""
    from sheeprl_tpu.algos.sac.agent import greedy_action
    from sheeprl_tpu.utils.env import make_env

    env = make_env(cfg, None, 0, log_dir, "test", vector_env_idx=0)()
    action_scale = (env.action_space.high - env.action_space.low) / 2.0
    action_bias = (env.action_space.high + env.action_space.low) / 2.0
    done = False
    cumulative_rew = 0.0
    obs = env.reset(seed=cfg.seed)[0]
    while not done:
        jobs = prepare_obs(fabric, obs, mlp_keys=cfg.algo.mlp_keys.encoder)
        mean, _ = actor_apply({"params": params}, jobs)
        actions = np.asarray(greedy_action(mean, action_scale, action_bias))
        obs, reward, terminated, truncated, _ = env.step(actions.reshape(env.action_space.shape))
        done = bool(terminated) or bool(truncated)
        cumulative_rew += float(reward)
        if cfg.dry_run:
            done = True
    fabric.print("Test - Reward:", cumulative_rew)
    if cfg.metric.log_level > 0 and getattr(fabric, "logger", None):
        fabric.logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, 0)
    env.close()
