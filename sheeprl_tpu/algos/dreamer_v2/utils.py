"""Dreamer-V2 support (reference: sheeprl/algos/dreamer_v2/utils.py)."""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_HALF_LOG_2PI = 0.5 * float(np.log(2.0 * np.pi))


def normal1_logprob(pred: jax.Array, target: jax.Array, event_dims: int) -> jax.Array:
    """log N(target | pred, 1) summed over the rightmost ``event_dims`` dims."""
    lp = -0.5 * jnp.square(target - pred) - _HALF_LOG_2PI
    return lp.sum(axis=tuple(range(-event_dims, 0)))


def bernoulli_logprob(logits: jax.Array, target: jax.Array, event_dims: int) -> jax.Array:
    """Soft-target Bernoulli log-prob (torch's BCE-with-logits form): the continue
    targets are (1 - terminated) * gamma, not hard 0/1."""
    lp = target * jax.nn.log_sigmoid(logits) + (1.0 - target) * jax.nn.log_sigmoid(-logits)
    return lp.sum(axis=tuple(range(-event_dims, 0)))


AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/world_model_loss",
    "Loss/value_loss",
    "Loss/policy_loss",
    "Loss/observation_loss",
    "Loss/reward_loss",
    "Loss/state_loss",
    "Loss/continue_loss",
    "State/post_entropy",
    "State/prior_entropy",
    "State/kl",
    "Grads/world_model",
    "Grads/actor",
    "Grads/critic",
}
MODELS_TO_REGISTER = {"world_model", "actor", "critic", "target_critic"}


def compute_lambda_values(
    rewards: jax.Array,
    values: jax.Array,
    continues: jax.Array,
    bootstrap: jax.Array,
    lmbda: float = 0.95,
) -> jax.Array:
    """DV2 lambda-return recursion with explicit bootstrap (reference
    dreamer_v2/utils.py:85-102), as a reversed lax.scan.

    Accumulates in float32 regardless of compute precision (see the shared
    compute_lambda_values note in utils/utils.py): mixed bf16/fp32 inputs would
    otherwise break the scan carry-type invariant."""
    rewards = rewards.astype(jnp.float32)
    values = values.astype(jnp.float32)
    continues = continues.astype(jnp.float32)
    bootstrap = bootstrap.astype(jnp.float32)
    next_values = jnp.concatenate([values[1:], bootstrap], axis=0)
    inputs = rewards + continues * next_values * (1 - lmbda)

    def step(agg, inp):
        inp_t, cont_t = inp
        agg = inp_t + cont_t * lmbda * agg
        return agg, agg

    _, lv_rev = jax.lax.scan(step, bootstrap[0], (inputs[::-1], continues[::-1]))
    return lv_rev[::-1]


def prepare_obs(
    fabric, obs: Dict[str, np.ndarray], *, cnn_keys: Sequence[str] = (), mlp_keys: Sequence[str] = (), num_envs: int = 1
) -> Dict[str, np.ndarray]:
    # host arrays: the act program runs where the player's params live
    # (utils.ActPlacement), and jit moves the frame there. On the host CPU backend
    # (every loop but the coupled Dreamer-V3 one) that is no transfer; in the
    # coupled Dreamer-V3 loop on one attached chip it is one host-to-device copy of
    # the frame per env step, inside the `act` span (dreamer_v3.settle_act_placement;
    # `act_steady_ms`, ledger, PR 28)
    out: Dict[str, np.ndarray] = {}
    for k in cnn_keys:
        v = np.asarray(obs[k], dtype=np.float32)
        out[k] = v.reshape(num_envs, -1, *v.shape[-2:]) / 255.0 - 0.5
    for k in mlp_keys:
        v = np.asarray(obs[k], dtype=np.float32)
        out[k] = v.reshape(num_envs, -1)
    return out


def test(player, params, fabric, cfg: Dict[str, Any], log_dir: str, test_name: str = "", greedy: bool = True):
    """Play one episode with the frozen params (reference utils.py test)."""
    from sheeprl_tpu.utils.env import make_env

    env = make_env(cfg, cfg.seed, 0, log_dir, "test" + (f"_{test_name}" if test_name else ""))()
    done = False
    cumulative_rew = 0.0
    obs = env.reset(seed=cfg.seed)[0]
    player.num_envs = 1
    player.init_states(params)
    key = jax.random.PRNGKey(cfg.seed)
    actions_dim = player.agent.actions_dim
    while not done:
        jobs = prepare_obs(
            fabric, obs, cnn_keys=cfg.algo.cnn_keys.encoder, mlp_keys=cfg.algo.mlp_keys.encoder, num_envs=1
        )
        actions, key = player.get_actions(params, jobs, key, greedy=greedy)
        actions = np.asarray(actions)
        if player.agent.is_continuous:
            real_actions = actions[0]
        else:
            splits = np.cumsum(actions_dim)[:-1]
            real_actions = np.stack([b.argmax(-1) for b in np.split(actions[0], splits, axis=-1)], axis=-1)
        obs, reward, terminated, truncated, _ = env.step(real_actions.reshape(env.action_space.shape))
        done = bool(terminated or truncated or cfg.dry_run)
        cumulative_rew += float(np.asarray(reward))
    fabric.print("Test - Reward:", cumulative_rew)
    if cfg.metric.log_level > 0 and getattr(fabric, "logger", None) is not None:
        fabric.logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, 0)
    env.close()
