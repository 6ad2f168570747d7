"""Anakin topology: rollout + GAE + optimization fused into ONE jitted program.

The Podracer "Anakin" architecture (PAPERS.md, arxiv 2104.06272) applied to the
on-policy family: environments live on-device (``sheeprl_tpu/envs/jax``), so an
entire training iteration — ``rollout_steps`` vectorized env steps with the
acting policy, GAE, and the full ``update_epochs x minibatches`` optimization
phase — compiles into a single donated XLA program over the mesh.

Steady-state host traffic is ZERO data transfers: the host dispatches the fused
program in a loop, carries only opaque device references (params, opt state,
env state, obs, PRNG key, stats accumulators), and pulls a handful of SCALARS
(episode stats, losses) at the telemetry/logging cadence. Compare
``algos/ppo/ppo.py``, which pays a host<->device round trip per vector env step
— the structural bound once train programs are fast.

Two flavors share the driver (the host loops ``ppo.py``/``a2c.py`` define the
reference semantics):

- ``ppo`` — clipped-surrogate PPO: ``update_epochs`` x shuffled minibatches
  per rollout (``algos/ppo/loss.py``);
- ``a2c`` — one full-rollout gradient step per iteration, no ratio clipping
  (``algos/a2c/loss.py``).

A third shape of the same step is the SEQUENCE policy (``algo.policy=sequence``,
``algos/ppo/sequence_policy.py``): a sequence model that reads one token a step and
carries its own state. It enters through one seam, :func:`_make_sequence_program`: a
carry through the rollout's scan, minibatches of whole sequences, a teacher-forced
forward in the loss and a mask for the steps whose action the env ignores. The MLP
flavours' program is untouched by it.

Phase attribution: a fused program has no host-visible env/train boundary, so
the loop splits each call's wall time between the ``rollout`` phase (fused
env+act, new in the schema) and ``train`` by a one-shot MEASURED wall time of
the rollout-only sub-program (:func:`_measure_rollout_seconds`; a static XLA
cost-model split was rejected — ``cost_analysis`` counts a ``lax.scan`` body
once, not ``length`` times). If the measurement fails the whole call is
attributed to ``rollout`` — documented in howto/jax_envs.md.

Distribution: ``num_envs * world_size`` env instances are sharded over the
mesh's ``data`` axis (params replicated); XLA inserts the gradient psum exactly
like the host PPO's dp path. This is the substrate ROADMAP item 4 (many Anakin
actors feeding one learner) builds on.
"""

from __future__ import annotations

import os
import time
import warnings
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.a2c.loss import policy_loss as a2c_policy_loss
from sheeprl_tpu.algos.a2c.loss import value_loss as a2c_value_loss
from sheeprl_tpu.algos.ppo.agent import build_agent, make_dists, policy_output
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.sequence_policy import SequencePolicy, build_sequence_policy
from sheeprl_tpu.algos.ppo.utils import test
from sheeprl_tpu.analysis.programs import register_fused_program
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.envs.jax import make_jax_env
from sheeprl_tpu.obs import build_telemetry
from sheeprl_tpu.resilience import apply_armed_learn_fault, build_resilience
from sheeprl_tpu.utils import learn_stats
from sheeprl_tpu.utils.checkpoint import wait_for_checkpoint
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import (
    BenchWindow,
    epoch_permutation,
    gae,
    normalize_tensor,
    packed_device_get,
    polynomial_decay,
    save_configs,
)

# the Feistel minibatch shuffle now lives in utils/prp.py (shared with the
# device replay ring); re-exported here so existing import sites keep working
from sheeprl_tpu.utils.prp import prp_permutation  # noqa: E402, F401

# stats accumulator keys carried device-side across iterations (pulled + zeroed
# at the logging cadence; ``losses`` is overwritten each call, not accumulated)
_STATS_ACC = ("ep_return_sum", "ep_length_sum", "ep_count")


def sparse_truncation_bootstrap(values_fn, traj, gamma, num_steps, num_envs, max_truncations):
    """r += gamma * V(terminal_obs) on truncated rows — the host loops'
    semantics (``ppo.py``) — computed SPARSELY: truncations are rare (at most
    ``max_truncations`` of T*E rows, e.g. 0.4% at CartPole's 500-step budget),
    so evaluating the critic on every terminal observation would be the single
    largest waste in the fused program. ``jnp.nonzero`` with a static ``size``
    gathers exactly the truncated rows inside jit; overflow beyond
    ``max_truncations`` cannot happen when the bound is derived from the step
    budget (an env truncates at most ``1 + T // limit`` times per rollout)."""
    rewards = traj["rewards"]  # [T, E, 1]
    trunc = traj["truncated"].reshape(-1)  # [T*E]
    rows = num_steps * num_envs
    idx = jnp.nonzero(trunc, size=max_truncations, fill_value=rows)[0]
    safe_idx = jnp.minimum(idx, rows - 1)
    term_obs = jnp.take(traj["terminal_observation"].reshape(rows, -1), safe_idx, axis=0)
    term_v = values_fn(term_obs).squeeze(-1) * (idx < rows)
    flat_bonus = jnp.zeros((rows,), jnp.float32).at[safe_idx].add(gamma * term_v)
    return rewards + flat_bonus.reshape(num_steps, num_envs, 1)


def _episode_stats(info, done_f):
    """[return sum, length sum, count] of the episodes that ended this step."""
    return jnp.stack(
        [
            jnp.sum(info["episode_return"] * done_f),
            jnp.sum(info["episode_length"].astype(jnp.float32) * done_f),
            jnp.sum(done_f),
        ]
    )


def _flavor(cfg) -> str:
    name = str(cfg.algo.name)
    if name.startswith("a2c"):
        return "a2c"
    if name.startswith("ppo"):
        return "ppo"
    raise ValueError(f"anakin driver supports ppo/a2c flavors, got algo.name={name!r}")


def _minibatch_plan(cfg, world_size: int, total_num_envs: int):
    """(global_bs, num_minibatches, update_epochs) of one fused iteration —
    ONE derivation shared by the program builder and the lr-schedule sizing so
    the two can never drift. a2c: one accumulated full-rollout gradient step."""
    num_rows = int(cfg.algo.rollout_steps) * total_num_envs
    if _flavor(cfg) == "ppo":
        global_bs = min(int(cfg.algo.per_rank_batch_size * world_size), num_rows)
        num_minibatches = -(-num_rows // global_bs)  # ceil: partial minibatches pad-wrap
        return global_bs, num_minibatches, int(cfg.algo.get("update_epochs", 1))
    return num_rows, 1, 1


def make_anakin_program(
    agent, env, cfg, fabric, tx, actions_dim, is_continuous, mlp_key, total_num_envs
):
    """Build (anakin_step, rollout_only, updates_per_iter).

    ``anakin_step(params, opt_state, env_state, obs, key, stats, clip_coef,
    ent_coef) -> (params, opt_state, env_state, obs, key, stats)`` is the fused
    per-iteration program, jitted with params/opt-state/env-state/obs/key
    donated. ``rollout_only`` is a jit of just the acting half; the loop runs
    it a couple of times one-shot to MEASURE the rollout share of the fused
    call's wall time (:func:`_measure_rollout_seconds`).

    Module-level (like ``ppo.make_train_phase``) so the AOT lowering tests
    exercise exactly the program main() ships.
    """
    if isinstance(agent, SequencePolicy):
        return _make_sequence_program(agent, env, cfg, fabric, tx, total_num_envs)
    flavor = _flavor(cfg)
    world_size = fabric.world_size
    T = int(cfg.algo.rollout_steps)
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)
    loss_reduction = cfg.algo.loss_reduction
    vf_coef = float(cfg.algo.get("vf_coef", 1.0))
    clip_vloss = bool(cfg.algo.get("clip_vloss", False))
    normalize_advantages = bool(cfg.algo.get("normalize_advantages", False))
    share_data = bool(cfg.buffer.share_data)
    # static clip threshold for the learn-stats post-clip norms (_build_optimizer
    # chains clip_by_global_norm with exactly this value)
    max_grad_norm = float(cfg.algo.get("max_grad_norm", 0.0) or 0) or None
    # compile the Learn/* stats only when the telemetry learning plane is on
    learn_on = learn_stats.enabled(cfg)
    # episodes can only truncate when the autoreset wrapper carries a step
    # budget; without one the truncation-bootstrap value pass is dead code and
    # is statically skipped
    truncates = env.spec.max_episode_steps is not None

    num_rows = T * total_num_envs
    global_bs, num_minibatches, update_epochs = _minibatch_plan(cfg, world_size, total_num_envs)
    updates_per_iter = update_epochs * num_minibatches

    data_sharding = fabric.sharding("data") if world_size > 1 else None

    def _values(params, obs):
        # critic-only apply: the truncation-bootstrap and last-step value passes
        # need no actor forward — skipping it saves ~40% of those passes' FLOPs
        def critic_only(module, o):
            return module.critic(module.feature_extractor(o))

        return agent.apply(
            {"params": params}, {mlp_key: obs.astype(jnp.float32)}, method=critic_only
        )

    # static upper bound on truncations in one rollout: an env can only hit the
    # step budget once per `limit` steps (plus the episode it starts inside)
    limit = env.spec.max_episode_steps or 0
    max_truncations = (
        min(total_num_envs * (1 + T // limit), T * total_num_envs) if truncates else 0
    )

    def _sample_actions(actor_outs, key):
        """Act-path sampling: actions + logprob only (``policy_output`` also
        computes per-step entropy, which only the train loss needs)."""
        dists = make_dists(actor_outs, is_continuous)
        if is_continuous:
            act = dists[0].sample(key)
            return act, dists[0].log_prob(act)[..., None]
        keys = jax.random.split(key, len(dists))
        sampled = [d.sample(k) for d, k in zip(dists, keys)]
        logprob = jnp.stack(
            [d.log_prob(a) for d, a in zip(dists, sampled)], axis=-1
        ).sum(axis=-1, keepdims=True)
        return jnp.concatenate(sampled, axis=-1), logprob

    def rollout_phase(params, env_state, obs, key):
        """T fused env+act steps; returns the new env carry, the [T, E, ...]
        trajectory and the summed episode stats of episodes that ended."""

        def body(carry, _):
            env_state, obs, key = carry
            key, step_key = jax.random.split(key)
            fobs = obs.astype(jnp.float32)
            actor_outs, values = agent.apply({"params": params}, {mlp_key: fobs})
            actions, logprob = _sample_actions(actor_outs, step_key)
            if is_continuous:
                env_actions = actions
            else:
                # single categorical head (the jax env plane's discrete spaces)
                env_actions = jnp.argmax(actions, axis=-1).astype(jnp.int32)
            env_state, next_obs, reward, done, info = env.step(env_state, env_actions)
            done_f = done.astype(jnp.float32)
            transition = {
                mlp_key: fobs,
                "actions": actions,
                "logprobs": logprob,
                "values": values,
                "rewards": reward[:, None].astype(jnp.float32),
                "dones": done_f[:, None],
            }
            if truncates:
                # the truncation bootstrap (r += gamma * V(terminal_obs)) is
                # applied SPARSELY in the train phase — carrying the terminal
                # observation out of the scan is far cheaper than running the
                # critic over every step for a ~0.2%-nonzero mask
                transition["terminal_observation"] = info["terminal_observation"]
                transition["truncated"] = info["truncated"]
            step_stats = _episode_stats(info, done_f)
            return (env_state, next_obs, key), (transition, step_stats)

        (env_state, obs, key), (traj, step_stats) = jax.lax.scan(
            body, (env_state, obs, key), None, length=T
        )
        return env_state, obs, key, traj, step_stats.sum(axis=0)

    def ppo_loss_fn(params, batch, clip_coef, ent_coef):
        actor_outs, new_values = agent.apply({"params": params}, {mlp_key: batch[mlp_key]})
        out = policy_output(
            actor_outs,
            new_values,
            jax.random.PRNGKey(0),
            actions_dim,
            is_continuous,
            actions=batch["actions"],
        )
        advantages = batch["advantages"]
        if normalize_advantages:
            advantages = normalize_tensor(advantages)
        pg_loss = policy_loss(out["logprob"], batch["logprobs"], advantages, clip_coef, loss_reduction)
        v_loss = value_loss(
            out["values"], batch["values"], batch["returns"], clip_coef, clip_vloss, loss_reduction
        )
        ent_loss = entropy_loss(out["entropy"], loss_reduction)
        loss = pg_loss + vf_coef * v_loss + ent_coef * ent_loss
        return loss, (pg_loss, v_loss, ent_loss, _loss_stats(out, batch))

    def _loss_stats(out, batch):
        # learn-stats aux (scalars only): value statistics, value residual vs
        # the GAE return, policy entropy (utils/learn_stats.py)
        return learn_stats.maybe(learn_on, lambda: {
            **learn_stats.value_stats(jax.lax.stop_gradient(out["values"])),
            **learn_stats.td_quantiles(jax.lax.stop_gradient(batch["returns"] - out["values"])),
            **learn_stats.entropy_stats(jax.lax.stop_gradient(out["entropy"])),
        })

    def a2c_loss_fn(params, batch, clip_coef, ent_coef):
        actor_outs, new_values = agent.apply({"params": params}, {mlp_key: batch[mlp_key]})
        out = policy_output(
            actor_outs,
            new_values,
            jax.random.PRNGKey(0),
            actions_dim,
            is_continuous,
            actions=batch["actions"],
        )
        pg_loss = a2c_policy_loss(out["logprob"], batch["advantages"], loss_reduction)
        v_loss = a2c_value_loss(out["values"], batch["returns"], loss_reduction)
        ent_loss = entropy_loss(out["entropy"], loss_reduction)
        return pg_loss + v_loss + ent_coef * ent_loss, (pg_loss, v_loss, ent_loss, _loss_stats(out, batch))

    loss_fn = ppo_loss_fn if flavor == "ppo" else a2c_loss_fn

    def train_phase(params, opt_state, traj, next_values, train_key, clip_coef, ent_coef):
        if truncates:
            traj = dict(traj)
            traj["rewards"] = sparse_truncation_bootstrap(
                lambda o: _values(params, o), traj, gamma, T, total_num_envs, max_truncations
            )
            del traj["truncated"]
            del traj["terminal_observation"]
        returns, advantages = gae(
            traj["rewards"], traj["values"], traj["dones"], next_values, T, gamma, gae_lambda
        )
        if world_size > 1:
            # env-major flatten keeps each device's rows one contiguous block
            # (the layout epoch_permutation's device-local minibatching assumes)
            def _flatten(v):
                return jnp.swapaxes(v, 0, 1).reshape(-1, *v.shape[2:])
        else:
            # single device: a [T, E] -> [T*E] reshape of contiguous data is
            # free, and the minibatch shuffle makes the row order irrelevant —
            # the env-major transpose would only copy ~250 MB per iteration
            def _flatten(v):
                return v.reshape(-1, *v.shape[2:])

        flat = {k: _flatten(v) for k, v in traj.items()}
        flat["returns"] = _flatten(returns)
        flat["advantages"] = _flatten(advantages)
        if data_sharding is not None:
            flat = jax.lax.with_sharding_constraint(flat, data_sharding)

        def grad_step(params, opt_state, batch):
            grads, (pg, vl, ent, stats) = jax.grad(loss_fn, has_aux=True)(
                params, batch, clip_coef, ent_coef
            )
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            learn = learn_stats.maybe(learn_on, lambda: {
                **stats,
                **learn_stats.group_stats(
                    "policy",
                    grads=grads,
                    updates=updates,
                    params=params,
                    opt_state=opt_state,
                    clip=max_grad_norm,
                ),
                "Learn/loss/policy": pg,
                "Learn/loss/value": vl,
                "Learn/loss/entropy": ent,
            })
            return params, opt_state, (jnp.stack([pg, vl, ent]), learn)

        # single full-batch update (the a2c flavor, or ppo with one epoch over
        # one minibatch): any permutation is the identity up to reduction order,
        # so the shuffle + gather are statically elided
        single_full_batch = update_epochs == 1 and num_minibatches == 1
        # power-of-two row counts on a 1-device mesh take the O(n) Feistel
        # shuffle; the sharded/general path keeps epoch_permutation's
        # device-local block layout
        use_prp = world_size == 1 and num_rows >= 2 and (num_rows & (num_rows - 1)) == 0

        def epoch_body(carry, epoch_key):
            params, opt_state = carry
            if single_full_batch:
                params, opt_state, (losses, learn) = grad_step(params, opt_state, flat)
                return (params, opt_state), (losses, learn)
            if use_prp:
                perm = prp_permutation(epoch_key, num_rows)
            else:
                perm = epoch_permutation(epoch_key, num_rows, world_size, share_data, global_bs)
            pad = num_minibatches * global_bs - num_rows
            if pad > 0:
                perm = jnp.concatenate([perm, perm[:pad]])
            mb_idx = perm[: num_minibatches * global_bs].reshape(num_minibatches, global_bs)

            def mb_body(carry, idx):
                params, opt_state = carry
                batch = {k: jnp.take(v, idx, axis=0) for k, v in flat.items()}
                params, opt_state, out = grad_step(params, opt_state, batch)
                return (params, opt_state), out

            # learn stays [minibatches]-stacked: reduce_stacked takes the true
            # max over every fused step, so a one-minibatch gradient spike is
            # not averaged below the explosion detector's threshold
            (params, opt_state), (losses, learn) = jax.lax.scan(mb_body, (params, opt_state), mb_idx)
            return (params, opt_state), (losses.mean(axis=0), learn)

        epoch_keys = jax.random.split(train_key, update_epochs)
        (params, opt_state), (losses, learn) = jax.lax.scan(epoch_body, (params, opt_state), epoch_keys)
        return params, opt_state, losses.mean(axis=0), learn_stats.reduce_stacked(learn)

    def anakin_step(params, opt_state, env_state, obs, key, stats, clip_coef, ent_coef):
        if data_sharding is not None:
            env_state = jax.lax.with_sharding_constraint(env_state, data_sharding)
            obs = jax.lax.with_sharding_constraint(obs, data_sharding)
        key, train_key = jax.random.split(key)
        env_state, obs, key, traj, ep_stats = rollout_phase(params, env_state, obs, key)
        next_values = _values(params, obs)
        params, opt_state, losses, learn = train_phase(
            params, opt_state, traj, next_values, train_key, clip_coef, ent_coef
        )
        new_stats = {
            "ep_return_sum": stats["ep_return_sum"] + ep_stats[0],
            "ep_length_sum": stats["ep_length_sum"] + ep_stats[1],
            "ep_count": stats["ep_count"] + ep_stats[2],
            "losses": losses,
        }
        # the Learn/* block is a SEPARATE output (not folded into the carried
        # stats dict): the input stats template stays shape-stable across
        # calls, and telemetry holds only these fresh scalar buffers
        return params, opt_state, env_state, obs, key, new_stats, learn

    # stats (argnum 5) is NOT donated: telemetry holds the losses reference for
    # its window-cadence health sync, and a donated buffer would be deleted
    # under it by the next call
    fused = jax.jit(anakin_step, donate_argnums=(0, 1, 2, 3, 4))
    rollout_only = jax.jit(rollout_phase)
    return fused, rollout_only, updates_per_iter


def _masked(x, mask, reduction: str):
    """``algos/ppo/loss.py``'s reductions over the steps that count (``mask`` 1)."""
    total = jnp.sum(x * mask)
    if reduction == "mean":
        return total / jnp.maximum(mask.sum(), 1.0)
    if reduction == "sum":
        return total
    raise ValueError(f"the sequence flavour reduces its loss by mean or sum, not {reduction!r}")


def _mean_counters(stacked):
    """Scan-stacked counters -> scalars: the mean a decode step, or a gradient step."""
    return {k: v.mean() for k, v in stacked.items()} if stacked else {}


def _make_sequence_program(policy: SequencePolicy, env, cfg, fabric, tx, total_num_envs):
    """The fused program for a sequence policy: (anakin_step, rollout_only, updates_per_iter)
    as :func:`make_anakin_program` gives them, with one more output at the end of
    ``anakin_step``'s: ``{"counters", "record"}``, the expert layers' counters as scalars
    and what the iteration's own steps produced (the trajectory, the experts chosen in the
    rollout and in each gradient step, each gradient step's loss parts and sequences).

    One rollout is one episode: the env ends every episode after exactly ``rollout_steps``
    steps, so the policy's carry starts empty in every call and no cache outlives one.
    A minibatch is ``per_rank_batch_size`` whole sequences; the loss and the advantage
    statistics leave out the steps whose action the env ignored (``info["action_mask"]``).
    The parts carry ``jax.named_scope`` names (``rollout``, ``update``, ``gae``, ``ppo_loss``,
    ``optimizer``, and the trunk's own): metadata, which changes no program."""
    if _flavor(cfg) != "ppo":
        raise ValueError("a sequence policy is trained by the ppo flavour")
    if fabric.world_size > 1:
        raise ValueError("the sequence policy runs on one device: there is no expert or data axis for it yet")
    T, E = int(cfg.algo.rollout_steps), total_num_envs
    if env.spec.episode_steps != T:
        raise ValueError(
            f"a sequence policy needs episodes of exactly algo.rollout_steps={T} steps "
            f"(the env's are {env.spec.episode_steps}): every rollout begins at a reset"
        )
    gamma, gae_lambda = float(cfg.algo.gamma), float(cfg.algo.gae_lambda)
    loss_reduction = cfg.algo.loss_reduction
    vf_coef = float(cfg.algo.get("vf_coef", 1.0))
    clip_vloss = bool(cfg.algo.get("clip_vloss", False))
    normalize_advantages = bool(cfg.algo.get("normalize_advantages", False))
    max_grad_norm = float(cfg.algo.get("max_grad_norm", 0.0) or 0) or None
    learn_on = learn_stats.enabled(cfg)
    batch_sequences = min(int(cfg.algo.per_rank_batch_size), E)
    if E % batch_sequences:
        raise ValueError(f"algo.per_rank_batch_size={batch_sequences} sequences must divide env.num_envs={E}")
    num_minibatches = E // batch_sequences
    update_epochs = int(cfg.algo.get("update_epochs", 1))
    updates_per_iter = update_epochs * num_minibatches

    def rollout_phase(params, env_state, obs, key):
        """T decode steps of batch E through the policy's caches, from an empty carry."""

        def body(carry, _):
            env_state, obs, key, state = carry
            key, step_key = jax.random.split(key)
            logits, values, state, aux = policy.step(params, state, obs)
            actions = jax.random.categorical(step_key, logits).astype(jnp.int32)
            logprob = jnp.take_along_axis(jax.nn.log_softmax(logits), actions[:, None], axis=-1)[:, 0]
            env_state, next_obs, reward, done, info = env.step(env_state, actions)
            done_f = done.astype(jnp.float32)
            transition = {
                "tokens": obs,
                "actions": actions,
                "logprobs": logprob,
                "values": values,
                "rewards": reward.astype(jnp.float32),
                "dones": done_f,
                "mask": info["action_mask"].astype(jnp.float32),
            }
            if aux:
                transition["route_ids"] = aux["route_ids"]
            step_stats = _episode_stats(info, done_f)
            return (env_state, next_obs, key, state), (transition, step_stats, aux.get("counters", {}))

        with jax.named_scope("rollout"):
            (env_state, obs, key, _), (traj, step_stats, counters) = jax.lax.scan(
                body, (env_state, obs, key, policy.initial_carry(E)), None, length=T
            )
        return env_state, obs, key, traj, step_stats.sum(axis=0), _mean_counters(counters)

    def loss_fn(params, batch, clip_coef, ent_coef):
        logits, new_values, aux = policy.forward(params, batch["tokens"])
        with jax.named_scope("ppo_loss"):
            mask = batch["mask"]
            logp_all = jax.nn.log_softmax(logits, axis=-1)
            logprob = jnp.take_along_axis(logp_all, batch["actions"][..., None], axis=-1)[..., 0]
            entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
            advantages = batch["advantages"]
            if normalize_advantages:
                mean = _masked(advantages, mask, "mean")
                std = jnp.sqrt(_masked(jnp.square(advantages - mean), mask, "mean"))
                advantages = (advantages - mean) / (std + 1e-8)
            pg_loss = _masked(policy_loss(logprob, batch["logprobs"], advantages, clip_coef, "none"), mask, loss_reduction)
            v_loss = _masked(
                value_loss(new_values, batch["values"], batch["returns"], clip_coef, clip_vloss, "none"),
                mask, loss_reduction,
            )
            ent_loss = _masked(entropy_loss(entropy, "none"), mask, loss_reduction)
            loss = pg_loss + vf_coef * v_loss + ent_coef * ent_loss
        return loss, (jnp.stack([pg_loss, v_loss, ent_loss]), aux)

    def train_phase(params, opt_state, traj, train_key, clip_coef, ent_coef):
        with jax.named_scope("gae"):
            # every episode ends with the rollout: the value after the last step is masked out
            returns, advantages = gae(
                traj["rewards"], traj["values"], traj["dones"], jnp.zeros((E,), jnp.float32), T, gamma, gae_lambda
            )
        keys = ("tokens", "actions", "logprobs", "values", "mask")
        sequences = {k: jnp.swapaxes(traj[k], 0, 1) for k in keys}  # [E, T]: a row is a sequence
        sequences["returns"] = jnp.swapaxes(returns, 0, 1)
        sequences["advantages"] = jnp.swapaxes(advantages, 0, 1)

        def grad_step(carry, idx):
            params, opt_state = carry
            batch = {k: jnp.take(v, idx, axis=0) for k, v in sequences.items()}
            grads, (parts, aux) = jax.grad(loss_fn, has_aux=True)(params, batch, clip_coef, ent_coef)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            learn = learn_stats.maybe(learn_on, lambda: {
                **learn_stats.group_stats(
                    "policy", grads=grads, updates=updates, params=params, opt_state=opt_state, clip=max_grad_norm
                ),
                "Learn/loss/policy": parts[0],
                "Learn/loss/value": parts[1],
                "Learn/loss/entropy": parts[2],
            })
            out = {"losses": parts, "sequences": idx, "learn": learn}
            if aux:
                out["route_ids"], out["counters"] = aux["route_ids"], aux["counters"]
            return (params, opt_state), out

        def epoch_body(carry, epoch_key):
            order = jax.random.permutation(epoch_key, E).reshape(num_minibatches, batch_sequences)
            return jax.lax.scan(grad_step, carry, order)

        (params, opt_state), out = jax.lax.scan(
            epoch_body, (params, opt_state), jax.random.split(train_key, update_epochs)
        )
        # [epochs, minibatches, ...] -> [gradient steps, ...]
        out = jax.tree_util.tree_map(lambda x: x.reshape(updates_per_iter, *x.shape[2:]), out)
        return params, opt_state, out

    def anakin_step(params, opt_state, env_state, obs, key, stats, clip_coef, ent_coef):
        key, train_key = jax.random.split(key)
        env_state, obs, key, traj, ep_stats, rollout_counters = rollout_phase(params, env_state, obs, key)
        with jax.named_scope("update"):
            params, opt_state, out = train_phase(params, opt_state, traj, train_key, clip_coef, ent_coef)
        new_stats = {
            "ep_return_sum": stats["ep_return_sum"] + ep_stats[0],
            "ep_length_sum": stats["ep_length_sum"] + ep_stats[1],
            "ep_count": stats["ep_count"] + ep_stats[2],
            "losses": out["losses"].mean(axis=0),
        }
        counters = {f"rollout_{k}": v for k, v in rollout_counters.items()}
        counters.update({f"update_{k}": v for k, v in _mean_counters(out.get("counters")).items()})
        record = {"traj": traj, "losses": out["losses"], "sequences": out["sequences"]}
        if "route_ids" in out:
            record["update_route_ids"] = out["route_ids"]
        learn = learn_stats.reduce_stacked(out["learn"])
        return params, opt_state, env_state, obs, key, new_stats, learn, {"counters": counters, "record": record}

    fused = jax.jit(anakin_step, donate_argnums=(0, 1, 2, 3, 4))
    rollout_only = jax.jit(rollout_phase)
    return fused, rollout_only, updates_per_iter


@register_fused_program(
    "ppo.anakin_step",
    min_donated=10,
    expect_collectives=("all-reduce",),
    compile_on_cpu=True,
    devices=8,
    doc="Anakin fused rollout+train PPO step on the 8-device dp mesh",
)
def _aot_anakin_program():
    """The fused Anakin program on the 8-device CPU mesh — the TPU-readiness
    build the hand-written AOT test used, now shared through the registry:
    donation must survive (params/opt-state/env-state/obs/key), the steady-state
    program must carry NO host callbacks/outfeeds (zero per-step host<->device
    traffic by construction), and the dp gradient psum must appear as an
    all-reduce in the optimized HLO."""
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.jax import make_jax_env
    from sheeprl_tpu.parallel.fabric import Fabric

    devices = 8
    cfg = compose(
        [
            "exp=ppo_anakin_benchmarks",
            "fabric.accelerator=cpu",
            f"fabric.devices={devices}",
            "fabric.strategy=dp",
            "env.num_envs=16",
            "algo.rollout_steps=8",
            "algo.per_rank_batch_size=32",
            # lower the GROWN program (Learn/* stats compile in under telemetry)
            "metric.telemetry.enabled=true",
        ]
    )
    fabric = Fabric(devices=devices, accelerator="cpu", strategy="dp")
    fabric._setup()
    total_envs = 16 * devices
    env = make_jax_env(cfg, total_envs)
    spec = env.spec
    obs_space = gym.spaces.Dict({"state": spec.to_gym_obs_space()})
    agent, params = build_agent(
        fabric, spec.action.actions_dim, False, cfg, obs_space, jax.random.PRNGKey(0)
    )
    tx = _build_optimizer(cfg, 10, 1)
    opt_state = tx.init(params)
    fused, rollout_only, _ = make_anakin_program(
        agent, env, cfg, fabric, tx, spec.action.actions_dim, False, "state", total_envs
    )
    env_state, obs = jax.jit(env.reset)(jax.random.PRNGKey(1))
    stats = {
        "ep_return_sum": jnp.float32(0),
        "ep_length_sum": jnp.float32(0),
        "ep_count": jnp.float32(0),
        "losses": jnp.zeros((3,), jnp.float32),
    }
    args = (params, opt_state, env_state, obs, jax.random.PRNGKey(2), stats, np.float32(0.2), np.float32(0.0))
    return fused, args


def _build_optimizer(cfg, total_iters: int, updates_per_iter: int):
    lr = cfg.algo.optimizer.lr
    if cfg.algo.get("anneal_lr", False):
        lr = optax.linear_schedule(
            init_value=lr, end_value=0.0, transition_steps=total_iters * updates_per_iter
        )
    tx = instantiate(cfg.algo.optimizer, lr=lr)
    if cfg.algo.get("max_grad_norm", 0.0) and cfg.algo.max_grad_norm > 0.0:
        tx = optax.chain(optax.clip_by_global_norm(cfg.algo.max_grad_norm), tx)
    return tx


def _counter_name(name: str) -> str:
    """A counter of the sequence flavour, ``<phase>_<counter>`` as the program returns it, by
    the name the telemetry keeps: ``moe/<phase>_<counter>`` (the expert layers'), or
    ``<space>/<phase>_<counter>`` where the trunk named its space (``mla/decode_kernel_share``)."""
    phase, _, counter = name.partition("_")
    space, _, counter = counter.rpartition("/")
    return f"{space or 'moe'}/{phase}_{counter}"


def _measure_rollout_seconds(rollout_only, args, reps: int = 2):
    """One-shot wall-time measurement of the rollout-only half of the fused
    program: compiles and runs the acting sub-program ``reps`` times on the
    CURRENT carry (pure — outputs are discarded, nothing is donated) and
    returns the best wall time. The loop divides each fused call's wall time by
    this to split the ``rollout``/``train`` phases honestly. (A static XLA
    cost-model split was tried first and rejected: ``cost_analysis`` counts a
    ``lax.scan`` body once, not ``length`` times, so the ratio was off by the
    trip count.) Returns ``None`` on failure — the caller then attributes whole
    calls to ``rollout``."""
    try:
        out = rollout_only(*args)
        jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = rollout_only(*args)
            jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
            best = min(best, time.perf_counter() - t0)
        return best
    except Exception as exc:
        warnings.warn(f"anakin: rollout phase-split measurement failed ({exc!r})")
        return None


def run_anakin(fabric, cfg: Dict[str, Any]):
    """The shared ppo_anakin / a2c_anakin training loop."""
    _flavor(cfg)  # reject unknown algo names before any setup
    backend = str(cfg.env.get("backend", "host") or "host").lower()
    if backend != "jax":
        raise ValueError(
            f"{cfg.algo.name} requires the on-device env plane: set env.backend=jax "
            f"(got {backend!r}); host envs cannot live inside the fused program"
        )
    if len(cfg.algo.cnn_keys.encoder) > 0:
        raise ValueError("the anakin topology supports mlp observations only (cnn_keys must be empty)")
    if len(cfg.algo.mlp_keys.encoder) != 1:
        raise ValueError(
            f"the anakin topology expects exactly one mlp key, got {cfg.algo.mlp_keys.encoder!r}"
        )
    mlp_key = cfg.algo.mlp_keys.encoder[0]

    initial_ent_coef = float(cfg.algo.get("ent_coef", 0.0))
    initial_clip_coef = float(cfg.algo.get("clip_coef", 0.2))
    rank = fabric.global_rank
    world_size = fabric.world_size

    state = fabric.load(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name)
    logger = get_logger(fabric, cfg, log_dir=log_dir)
    fabric.logger = logger
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict())
    fabric.print(f"Log dir: {log_dir}")

    total_num_envs = int(cfg.env.num_envs * world_size)
    # ONE fused iteration covers num_envs * rollout_steps policy steps — often
    # more than the host-loop-tuned compile-warmup default, which would make
    # every initial compile look like a post-warmup recompile storm. Scale the
    # warmup to a handful of iterations (never shrink a larger user setting).
    tcfg = cfg.metric.get("telemetry") or {}
    if tcfg and int(tcfg.get("compile_warmup_steps") or 0) > 0:
        cfg.metric.telemetry.compile_warmup_steps = max(
            int(tcfg.get("compile_warmup_steps")),
            8 * total_num_envs * int(cfg.algo.rollout_steps),
        )
    telemetry = build_telemetry(fabric, cfg, log_dir, logger=logger)
    resilience = build_resilience(fabric, cfg, log_dir, telemetry=telemetry)
    if world_size > 1 and total_num_envs % world_size != 0:
        raise ValueError(f"num_envs*world_size ({total_num_envs}) must divide the mesh ({world_size})")
    env = make_jax_env(cfg, total_num_envs)
    spec = env.spec

    is_continuous = spec.action.kind == "continuous"
    actions_dim = spec.action.actions_dim
    observation_space = gym.spaces.Dict({mlp_key: spec.to_gym_obs_space()})

    key = fabric.seed_everything(cfg.seed + rank)
    key, agent_key, env_key = jax.random.split(key, 3)
    sequence = str(cfg.algo.get("policy", "mlp")) == "sequence"
    if sequence:
        agent, params = build_sequence_policy(cfg, spec.action.num_actions, agent_key)
    else:
        agent, params = build_agent(fabric, actions_dim, is_continuous, cfg, observation_space, agent_key)
    if state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, state["agent"])

    policy_steps_per_iter = int(total_num_envs * cfg.algo.rollout_steps)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    start_iter = (state["iter_num"] // world_size) + 1 if state is not None else 1
    policy_step = state["iter_num"] * policy_steps_per_iter // world_size if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )

    # the optimizer's lr schedule spans total_iters x the per-iteration
    # gradient-step count — the SAME _minibatch_plan the program builder uses
    _, plan_minibatches, plan_epochs = _minibatch_plan(cfg, world_size, total_num_envs)
    tx = _build_optimizer(cfg, total_iters, plan_epochs * plan_minibatches)
    opt_state = tx.init(params)
    if state is not None and "optimizer" in state:
        opt_state = jax.tree_util.tree_map(jnp.asarray, state["optimizer"])

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(cfg.metric.aggregator)

    anakin_step, rollout_only, updates_per_iter = make_anakin_program(
        agent, env, cfg, fabric, tx, actions_dim, is_continuous, mlp_key, total_num_envs
    )

    # params/opt-state replicated over the mesh; env state arrives data-sharded
    if world_size > 1:
        params = fabric.replicate_pytree(params)
        opt_state = fabric.replicate_pytree(opt_state)

    env_state, obs = jax.jit(env.reset)(env_key)
    if world_size > 1:
        env_state = fabric.shard_pytree(env_state)
        obs = fabric.shard_pytree(obs)

    stats = {
        "ep_return_sum": jnp.float32(0.0),
        "ep_length_sum": jnp.float32(0.0),
        "ep_count": jnp.float32(0.0),
        "losses": jnp.zeros((3,), jnp.float32),
    }
    _zero = jnp.float32(0.0)
    # host-side shadow of the on-device episode accumulators (the telemetry
    # episode feed reads deltas against it; reset alongside the device reset)
    last_ep_stats = {"ep_return_sum": 0.0, "ep_length_sum": 0.0, "ep_count": 0.0}

    ent_coef = initial_ent_coef
    clip_coef = initial_clip_coef
    bench = BenchWindow()

    # one-shot measured rollout/train split for phase attribution (pre-loop, so
    # telemetry's window anchor — set at the first step() — never sees it);
    # skipped when nothing consumes the timers
    rollout_seconds = None
    if not timer.disabled:
        rollout_seconds = _measure_rollout_seconds(rollout_only, (params, env_state, obs, key))

    for iter_num in range(start_iter, total_iters + 1):
        bench.maybe_start(policy_step, sync_tree=stats["losses"])
        policy_step += policy_steps_per_iter

        t0 = time.perf_counter()
        # one-shot injected learning pathology (resilience.fault=lr_spike):
        # identity unless the fault armed this iteration
        params = apply_armed_learn_fault(params)
        # the call and its wait are one span (`anakin_step`, on a capture's clock too);
        # the two phase timers below get their shares of the same seconds
        with timer("anakin_step"):
            params, opt_state, env_state, obs, key, stats, learn, *extras = anakin_step(
                params,
                opt_state,
                env_state,
                obs,
                key,
                stats,
                np.float32(clip_coef),
                np.float32(ent_coef),
            )
            # one scalar sync per ITERATION (T * num_envs env steps), not per env
            # step: keeps the host from racing ahead of the device queue and makes
            # the wall-time split below honest. No data is transferred.
            jax.block_until_ready(stats["losses"])
        elapsed = time.perf_counter() - t0
        if extras and not timer.disabled:
            # the sequence flavour's counters (scalars, behind the wait above)
            for name, value in jax.device_get(extras[0]["counters"]).items():
                timer.count(_counter_name(name), float(value))

        # split the fused call's wall time between the rollout (fused env+act)
        # and train phases by the measured rollout-only time; compile-dominated
        # first calls clamp to all-rollout-plus-remainder like any other call
        split_frac = (
            min(rollout_seconds / elapsed, 1.0)
            if (rollout_seconds and elapsed > 0)
            else 1.0
        )
        timer("Time/rollout_time").add(elapsed * split_frac)
        timer("Time/train_time").add(elapsed * (1.0 - split_frac))

        telemetry.observe_train(updates_per_iter, stats["losses"])
        telemetry.observe_learn(learn)
        if telemetry.enabled:
            # the on-device episode accumulators double as the episode feed:
            # three scalar pulls per iteration, already behind the per-iteration
            # block_until_ready above (telemetry off pays nothing). Per-episode
            # returns never leave the device — the window sees the batch MEAN
            # (one sample) with the exact episode count.
            ep_count = float(stats["ep_count"]) - last_ep_stats["ep_count"]
            if ep_count >= 1.0:
                mean_ret = (float(stats["ep_return_sum"]) - last_ep_stats["ep_return_sum"]) / ep_count
                mean_len = (float(stats["ep_length_sum"]) - last_ep_stats["ep_length_sum"]) / ep_count
                telemetry.observe_episodes([mean_ret], [mean_len], count=int(ep_count))
                last_ep_stats = {
                    k: float(stats[k]) for k in ("ep_return_sum", "ep_length_sum", "ep_count")
                }
        if telemetry.wants_program("anakin_step"):
            telemetry.register_program(
                "anakin_step",
                anakin_step,
                (params, opt_state, env_state, obs, key, stats, np.float32(0.0), np.float32(0.0)),
                units=updates_per_iter,
            )
        telemetry.step(policy_step)
        resilience.step(policy_step)

        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters or cfg.dry_run
        ):
            with timer("Time/logging_time"):
                # the ONLY steady-state device->host traffic: a handful of scalars
                stats_np = {k: np.asarray(stats[k]) for k in _STATS_ACC}
                losses_np = np.asarray(stats["losses"])
                if aggregator and not aggregator.disabled:
                    if stats_np["ep_count"] > 0:
                        aggregator.update(
                            "Rewards/rew_avg", float(stats_np["ep_return_sum"] / stats_np["ep_count"])
                        )
                        aggregator.update(
                            "Game/ep_len_avg", float(stats_np["ep_length_sum"] / stats_np["ep_count"])
                        )
                    aggregator.update("Loss/policy_loss", float(losses_np[0]))
                    aggregator.update("Loss/value_loss", float(losses_np[1]))
                    aggregator.update("Loss/entropy_loss", float(losses_np[2]))
                stats = dict(stats, ep_return_sum=_zero, ep_length_sum=_zero, ep_count=_zero)
                last_ep_stats = {"ep_return_sum": 0.0, "ep_length_sum": 0.0, "ep_count": 0.0}
                metrics_dict = aggregator.compute() if aggregator else {}
                if logger is not None:
                    logger.log_metrics(metrics_dict, policy_step)
                    timers = timer.to_dict(reset=False)
                    fused_seconds = timers.get("Time/rollout_time", 0.0) + timers.get(
                        "Time/train_time", 0.0
                    )
                    if fused_seconds > 0:
                        logger.log_metrics(
                            {"Time/sps_env_interaction": (policy_step - last_log) / fused_seconds},
                            policy_step,
                        )
                timer.to_dict(reset=True)
                if aggregator:
                    aggregator.reset()
            last_log = policy_step

        if cfg.algo.get("anneal_clip_coef", False):
            clip_coef = polynomial_decay(
                iter_num, initial=initial_clip_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )
        if cfg.algo.get("anneal_ent_coef", False):
            ent_coef = polynomial_decay(
                iter_num, initial=initial_ent_coef, final=0.0, max_decay_steps=total_iters, power=1.0
            )

        preempted = resilience.preempt_requested()
        if (
            (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
            or cfg.dry_run
            or (iter_num == total_iters and cfg.checkpoint.save_last)
            or preempted
        ):
            last_checkpoint = policy_step
            ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt")
            # the snapshot is part of the checkpoint's time: the pack programs of
            # `packed_device_get` compile on first use, and left outside the span that
            # was most of a short run's wall time, attributed to nothing
            with timer("Time/checkpoint_time"):
                # snapshot to host numpy first: params/opt_state are donated into the
                # NEXT anakin_step call, and an async checkpoint backend must never
                # hold references into donated device buffers
                ckpt_state = {
                    "agent": packed_device_get(params),
                    "optimizer": packed_device_get(opt_state),
                    "iter_num": iter_num * world_size,
                    "batch_size": int(cfg.algo.per_rank_batch_size * world_size),
                    "last_log": last_log,
                    "last_checkpoint": last_checkpoint,
                }
                fabric.call("on_checkpoint_coupled", ckpt_path=ckpt_path, state=ckpt_state)
            resilience.observe_checkpoint(ckpt_path, policy_step, preempted=preempted)
        if preempted:
            break

    bench.finish(policy_step, sync_tree=stats["losses"])
    wait_for_checkpoint()
    if not resilience.finalize(policy_step) and fabric.is_global_zero and cfg.algo.run_test:
        with timer("Time/test_time"):
            if sequence:
                # one more sampled rollout of every env, on the device
                ep = np.asarray(rollout_only(params, env_state, obs, key)[4])
                fabric.print(f"Test - Reward: {ep[0] / max(ep[2], 1.0)}")
            else:
                test(agent.apply, params, fabric, cfg, log_dir)
    telemetry.close(policy_step)
    if logger is not None:
        logger.finalize()
