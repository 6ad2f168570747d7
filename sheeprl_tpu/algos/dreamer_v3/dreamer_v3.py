"""Dreamer-V3, coupled training (capability parity with
sheeprl/algos/dreamer_v3/dreamer_v3.py:428-864).

TPU-native structure:
- the whole gradient step — dynamic-learning scan, world-model loss+update,
  imagination scan, actor update, critic update, target-critic EMA, Moments — is ONE
  jitted device program; each iteration's ``per_rank_gradient_steps`` steps run as a
  ``lax.scan`` over the ``[G, T, B, ...]`` replay block (one host→device upload per
  iteration). The reference instead pays a Python loop per gradient step with three
  ``torch.compile`` regions inside (dreamer_v3.py:741-783);
- sequence unrolls are ``lax.scan``s (agent.dynamic_scan / imagination_scan) — the
  reference's per-timestep GRU python loops (dreamer_v3.py:86-97, 148-156);
- under dp the batch axis is sharded over the mesh ``data`` axis: gradient psums and
  the Moments quantiles (reference all_gathers, utils.py:57) come from XLA collectives
  automatically;
- the act path is a jitted encoder→RSSM-step→actor program with an explicit carry
  (PlayerDV3), replacing the reference's stateful module + per-step ``.cpu()`` syncs.
"""

from __future__ import annotations

import os
import warnings
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.dreamer_v3.agent import (
    DV3Agent,
    PlayerDV3,
    actor_logprob_entropy,
    build_agent,
)
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import init_moments, prepare_obs, test, update_moments
from sheeprl_tpu.analysis.programs import register_fused_program
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.prefetch import make_replay_sampler
from sheeprl_tpu.envs.wrappers import RestartOnException
from sheeprl_tpu.obs import build_telemetry
from sheeprl_tpu.resilience import apply_armed_learn_fault, build_resilience
from sheeprl_tpu.utils import learn_stats
from sheeprl_tpu.utils.checkpoint import wait_for_checkpoint
from sheeprl_tpu.utils.mfu import unit_avals
from sheeprl_tpu.utils.distribution import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.utils.env import make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import (
    ActPlacement,
    BenchWindow,
    Ratio,
    compute_lambda_values,
    foreach_gradient_step,
    packed_device_get,
    save_configs,
)


def make_train_phase(
    agent: DV3Agent, cfg, world_tx, actor_tx, critic_tx, world_latent_hook=None,
    state_shardings=None,
):
    """Build the jitted multi-gradient-step train program. Returns
    train_phase(params, opt_state, moments_state, data, cum_steps, key).

    ``world_latent_hook(wm_params, latents, key) -> (head_latents, extra_loss,
    extra_metrics)`` lets forks transform the latent the world-model heads consume and
    add loss terms (offline_dreamer's CEM bottleneck); None keeps plain DV3.

    ``state_shardings`` — optional ``(params, opt_state, moments, metrics)``
    out_shardings pytrees (prefixes allowed) pinning the train-state OUTPUT
    placement on a multi-device mesh. Without the pin GSPMD is free to reshard
    state outputs however propagation likes (observed: small actor/critic leaves
    scattered over an 8-device data mesh), which breaks the params-stay-put
    contract the loops and the donation aliasing rely on; with it, outputs land
    exactly where the inputs live (replicated on a 1-D mesh, rule-sharded over
    ``model`` on a 2-D one — ``build_state_shardings``)."""
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    cnn_dec_keys = tuple(cfg.algo.cnn_keys.decoder)
    mlp_dec_keys = tuple(cfg.algo.mlp_keys.decoder)
    wm_cfg = cfg.algo.world_model
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    horizon = int(cfg.algo.horizon)
    ent_coef = float(cfg.algo.actor.ent_coef)
    discrete_size = agent.discrete_size
    tau = float(cfg.algo.critic.tau)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    moments_kw = dict(
        decay=float(cfg.algo.actor.moments.decay),
        maximum=float(cfg.algo.actor.moments.max),
        percentile_low=float(cfg.algo.actor.moments.percentile.low),
        percentile_high=float(cfg.algo.actor.moments.percentile.high),
    )
    # static clip thresholds for the learn-stats post-clip norms (the txs from
    # build_optimizers chain clip_by_global_norm with exactly these values).
    # learn_on: compile the Learn/* stats only when the telemetry learning
    # plane is on — the off path lowers byte-identically to the pre-plane program
    learn_on = learn_stats.enabled(cfg)
    clips = {
        "world_model": float(cfg.algo.world_model.clip_gradients or 0) or None,
        "actor": float(cfg.algo.actor.clip_gradients or 0) or None,
        "critic": float(cfg.algo.critic.clip_gradients or 0) or None,
    }

    def world_loss_fn(wm_params, batch, key):
        key, hook_key = jax.random.split(jnp.asarray(key))
        batch_obs = {k: batch[k] / 255.0 - 0.5 for k in cnn_keys}
        batch_obs.update({k: batch[k] for k in mlp_keys})
        is_first = batch["is_first"].at[0].set(jnp.ones_like(batch["is_first"][0]))
        # shift: a_t stored with o_t is the action *leaving* o_t; dynamics consume the
        # action that *led to* o_t (reference dreamer_v3.py:219-221)
        actions = jnp.concatenate(
            [jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], axis=0
        )
        with jax.named_scope("encoder"):
            embedded = agent.encoder.apply({"params": wm_params["encoder"]}, batch_obs)
        with jax.named_scope("rssm"):
            hs, zs, post_logits, prior_logits = agent.dynamic_scan(
                wm_params, embedded, actions, is_first, key
            )
        latents = jnp.concatenate([zs, hs], axis=-1)
        extra_loss, extra_metrics = 0.0, {}
        if world_latent_hook is not None:
            latents, extra_loss, extra_metrics = world_latent_hook(wm_params, latents, hook_key)
        with jax.named_scope("decoder"):
            recon = agent.observation_model.apply({"params": wm_params["observation_model"]}, latents)
            obs_lps = {
                k: MSEDistribution(recon[k], dims=len(recon[k].shape[2:])).log_prob(batch_obs[k])
                for k in cnn_dec_keys
            }
            obs_lps.update(
                {
                    k: SymlogDistribution(recon[k], dims=len(recon[k].shape[2:])).log_prob(batch_obs[k])
                    for k in mlp_dec_keys
                }
            )
        with jax.named_scope("heads"):
            reward_logits = agent.reward_model.apply({"params": wm_params["reward_model"]}, latents)
            reward_lp = TwoHotEncodingDistribution(reward_logits, dims=1).log_prob(batch["rewards"])
            cont_logits = agent.continue_model.apply({"params": wm_params["continue_model"]}, latents)
            cont_lp = Independent(BernoulliSafeMode(logits=cont_logits), 1).log_prob(
                1.0 - batch["terminated"]
            )
        rec_loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
            obs_lps,
            reward_lp,
            prior_logits,
            post_logits,
            discrete_size,
            kl_dynamic=wm_cfg.kl_dynamic,
            kl_representation=wm_cfg.kl_representation,
            kl_free_nats=wm_cfg.kl_free_nats,
            kl_regularizer=wm_cfg.kl_regularizer,
            continue_log_prob=cont_lp,
            continue_scale_factor=wm_cfg.continue_scale_factor,
        )

        def _cat_entropy(logits):
            shaped = logits.reshape(*logits.shape[:-1], -1, discrete_size)
            lp = jax.nn.log_softmax(shaped, axis=-1)
            return -jnp.sum(jnp.exp(lp) * lp, axis=(-2, -1)).mean()

        loss = rec_loss + extra_loss
        metrics = {
            "Loss/world_model_loss": loss,
            "Loss/observation_loss": observation_loss,
            "Loss/reward_loss": reward_loss,
            "Loss/state_loss": state_loss,
            "Loss/continue_loss": continue_loss,
            "State/kl": kl,
            "State/post_entropy": _cat_entropy(jax.lax.stop_gradient(post_logits)),
            "State/prior_entropy": _cat_entropy(jax.lax.stop_gradient(prior_logits)),
        }
        metrics.update(extra_metrics)
        return loss, (zs, hs, metrics)

    def actor_loss_fn(actor_params, params, zs, hs, true_continue, moments_state, key):
        wm = params["world_model"]
        z0 = jax.lax.stop_gradient(zs).reshape(-1, agent.stoch_state_size)
        h0 = jax.lax.stop_gradient(hs).reshape(-1, agent.recurrent_state_size)
        with jax.named_scope("imagine"):
            latents, actions = agent.imagination_scan(wm, actor_params, z0, h0, key, horizon)
        with jax.named_scope("critic"):
            predicted_values = TwoHotEncodingDistribution(
                agent.critic.apply({"params": params["critic"]}, latents), dims=1
            ).mean
        with jax.named_scope("heads"):
            predicted_rewards = TwoHotEncodingDistribution(
                agent.reward_model.apply({"params": wm["reward_model"]}, latents), dims=1
            ).mean
            continues = Independent(
                BernoulliSafeMode(logits=agent.continue_model.apply({"params": wm["continue_model"]}, latents)),
                1,
            ).mode
        continues = jnp.concatenate([true_continue[None], continues[1:]], axis=0)
        lambda_values = compute_lambda_values(
            predicted_rewards[1:], predicted_values[1:], continues[1:] * gamma, lmbda
        )
        discount = jax.lax.stop_gradient(jnp.cumprod(continues * gamma, axis=0) / gamma)

        offset, invscale, new_moments = update_moments(moments_state, lambda_values, **moments_kw)
        baseline = predicted_values[:-1]
        normed_lambda = (lambda_values - offset) / invscale
        normed_baseline = (baseline - offset) / invscale
        advantage = normed_lambda - normed_baseline
        with jax.named_scope("actor"):
            pre = agent.actor.apply({"params": actor_params}, jax.lax.stop_gradient(latents))
            lp, ent = actor_logprob_entropy(agent, pre, jax.lax.stop_gradient(actions))
        if agent.is_continuous:
            objective = advantage
        else:
            objective = lp[:-1] * jax.lax.stop_gradient(advantage)
        entropy = ent_coef * ent[..., None]
        policy_loss = -jnp.mean(discount[:-1] * (objective + entropy[:-1]))
        # learn-stats aux (scalars only): imagined-value statistics, the raw
        # (un-normalized) lambda-vs-baseline TD error, policy entropy
        aux_stats = learn_stats.maybe(learn_on, lambda: {
            **learn_stats.value_stats(jax.lax.stop_gradient(predicted_values)),
            **learn_stats.td_quantiles(jax.lax.stop_gradient(lambda_values - baseline)),
            **learn_stats.entropy_stats(jax.lax.stop_gradient(ent)),
        })
        return policy_loss, (latents, lambda_values, discount, new_moments, aux_stats)

    def critic_loss_fn(critic_params, target_params, latents, lambda_values, discount):
        with jax.named_scope("critic"):
            qv_logits = agent.critic.apply({"params": critic_params}, latents[:-1])
            qv = TwoHotEncodingDistribution(qv_logits, dims=1)
            target_values = TwoHotEncodingDistribution(
                agent.critic.apply({"params": target_params}, latents[:-1]), dims=1
            ).mean
            value_loss = -qv.log_prob(jax.lax.stop_gradient(lambda_values))
            value_loss = value_loss - qv.log_prob(jax.lax.stop_gradient(target_values))
            return jnp.mean(value_loss * discount[:-1].squeeze(-1))

    # ONE compiled program per single gradient step, driven by a host loop over the
    # [G, ...] replay block. Two reasons this beats an outer ``lax.scan`` over G:
    # (a) measured 3.6x faster steady-state on XLA CPU — the scan-carried
    # params/opt-state force layout copies and block fusion across the while-loop
    # body; (b) every distinct ``per_rank_gradient_steps`` value the Ratio governor
    # produces would recompile the whole scanned program (~45 s each); the
    # single-step program compiles once for any G.
    # donate_argnums: XLA reuses the params/opt-state/moments buffers in place
    # instead of copying the whole train state every gradient step (all drivers —
    # foreach_gradient_step, the trainers, warmup — rebind to the returned trees,
    # so the invalidated inputs are never read again).
    jit_kwargs = {}
    if state_shardings is not None:
        jit_kwargs["out_shardings"] = tuple(state_shardings)

    @partial(jax.jit, donate_argnums=(0, 1, 2), **jit_kwargs)
    def train_step(params, opt_state, moments_state, batch, cum, k):
        k_world, k_img = jax.random.split(jnp.asarray(k))

        # target-critic EMA before the step (reference dreamer_v3.py:756-761)
        do_ema = (cum % target_freq) == 0
        tau_eff = jnp.where(cum == 0, 1.0, tau)
        with jax.named_scope("optimizer"):
            params = {
                **params,
                "target_critic": jax.tree_util.tree_map(
                    lambda t, c: jnp.where(do_ema, tau_eff * c + (1 - tau_eff) * t, t),
                    params["target_critic"],
                    params["critic"],
                ),
            }

        (w_loss, (zs, hs, w_metrics)), w_grads = jax.value_and_grad(world_loss_fn, has_aux=True)(
            params["world_model"], batch, k_world
        )
        with jax.named_scope("optimizer"):
            w_updates, new_wopt = world_tx.update(w_grads, opt_state["world_model"], params["world_model"])
            params = {**params, "world_model": optax.apply_updates(params["world_model"], w_updates)}
        opt_state = {**opt_state, "world_model": new_wopt}

        true_continue = (1 - batch["terminated"]).reshape(-1, 1)
        (a_loss, (latents, lambda_values, discount, new_moments, aux_stats)), a_grads = (
            jax.value_and_grad(actor_loss_fn, has_aux=True)(
                params["actor"], params, zs, hs, true_continue, moments_state, k_img
            )
        )
        with jax.named_scope("optimizer"):
            a_updates, new_aopt = actor_tx.update(a_grads, opt_state["actor"], params["actor"])
            params = {**params, "actor": optax.apply_updates(params["actor"], a_updates)}
        opt_state = {**opt_state, "actor": new_aopt}
        moments_state = new_moments

        latents_sg = jax.lax.stop_gradient(latents)
        c_loss, c_grads = jax.value_and_grad(critic_loss_fn)(
            params["critic"], params["target_critic"], latents_sg, lambda_values, discount
        )
        with jax.named_scope("optimizer"):
            c_updates, new_copt = critic_tx.update(c_grads, opt_state["critic"], params["critic"])
            params = {**params, "critic": optax.apply_updates(params["critic"], c_updates)}
        opt_state = {**opt_state, "critic": new_copt}

        metrics = dict(w_metrics)
        metrics["Loss/policy_loss"] = a_loss
        metrics["Loss/value_loss"] = c_loss
        metrics["Grads/world_model"] = optax.global_norm(w_grads)
        metrics["Grads/actor"] = optax.global_norm(a_grads)
        metrics["Grads/critic"] = optax.global_norm(c_grads)
        # training-health block, riding the metrics dict (the Learn/ prefix is
        # what RunTelemetry.observe_learn extracts — utils/learn_stats.py)
        if learn_on:
            metrics.update(aux_stats)
            metrics.update(
                learn_stats.group_stats(
                    "world_model",
                    grads=w_grads,
                    updates=w_updates,
                    params=params["world_model"],
                    opt_state=new_wopt,
                    clip=clips["world_model"],
                )
            )
            metrics.update(
                learn_stats.group_stats(
                    "actor",
                    grads=a_grads,
                    updates=a_updates,
                    params=params["actor"],
                    opt_state=new_aopt,
                    clip=clips["actor"],
                )
            )
            metrics.update(
                learn_stats.group_stats(
                    "critic",
                    grads=c_grads,
                    updates=c_updates,
                    params=params["critic"],
                    opt_state=new_copt,
                    clip=clips["critic"],
                )
            )
            metrics.update(
                learn_stats.kl_stats(
                    w_metrics["State/kl"],
                    w_metrics["State/post_entropy"],
                    w_metrics["State/prior_entropy"],
                )
            )
            metrics["Learn/loss/world_model"] = w_loss
            metrics["Learn/loss/actor"] = a_loss
            metrics["Learn/loss/critic"] = c_loss
        return params, opt_state, moments_state, metrics

    def train_phase(params, opt_state, moments_state, data, cum_steps, train_key):
        return foreach_gradient_step(
            train_step, (params, opt_state, moments_state), data, train_key, cum_steps
        )

    # the compiled unit, exposed for FLOPs/MFU accounting (utils/mfu.py, bench.py)
    train_phase.train_step = train_step
    return train_phase


def build_optimizers(cfg, params):
    """The three Dreamer optimizers with per-group clipping (reference
    dreamer_v3.py:525-538). ONE construction shared by the coupled loop and the
    decoupled learner: the learner rebuilds training state from the shared seed
    with no weight transfer, so the two must stay bit-identical."""

    def _tx(opt_cfg, clip):
        base = instantiate(opt_cfg)
        if clip is not None and clip > 0:
            return optax.chain(optax.clip_by_global_norm(clip), base)
        return base

    world_tx = _tx(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
    actor_tx = _tx(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
    critic_tx = _tx(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
    opt_state = {
        "world_model": world_tx.init(params["world_model"]),
        "actor": actor_tx.init(params["actor"]),
        "critic": critic_tx.init(params["critic"]),
    }
    return world_tx, actor_tx, critic_tx, opt_state


@register_fused_program(
    "dreamer_v3.train_step",
    min_donated=3,
    doc="fused single-gradient-step Dreamer-V3 world/actor/critic update",
)
def _aot_train_step():
    """Tiny DV3 agent through the loop's own factory (the __graft_entry__
    dryrun recipe at AOT scale)."""
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.analysis.programs import (
        tiny_dreamer_batch,
        tiny_dreamer_cfg,
        tiny_fabric,
        tiny_obs_space,
    )

    cfg = tiny_dreamer_cfg("dreamer_v3", extra=("algo.world_model.discrete_size=4",))
    fabric = tiny_fabric()
    agent, params = build_agent(fabric, (4,), False, cfg, tiny_obs_space(), jax.random.PRNGKey(0))
    world_tx, actor_tx, critic_tx, opt_state = build_optimizers(cfg, params)
    train_phase = make_train_phase(agent, cfg, world_tx, actor_tx, critic_tx)
    batch = tiny_dreamer_batch(cfg)
    args = (params, opt_state, init_moments(), batch, jnp.asarray(0), np.asarray(jax.random.PRNGKey(1)))
    return train_phase.train_step, args


class _InlineTrainer:
    """Owns the training state and runs the fused train program in-process — the
    coupled path. The decoupled variant ships the replay block over a data channel
    to a learner (thread or process slice) instead and implements this same
    surface (dreamer_v3_decoupled.py), which is the only difference between the
    two training topologies."""

    # a deferring trainer (channel-backed) can only produce full checkpoint state
    # at train rounds; the loop then postpones an off-round checkpoint to the next
    # train round (or to close())
    defers_checkpoints = False
    # the donated train program and the player run on one thread, one after the
    # other, and the loop rebinds its act view from every train() call: the view
    # may be this trainer's own device buffers (run_dreamer decides, with the
    # fabric's device count). A channel-backed trainer receives its view as host
    # bytes from a learner that donates concurrently, and does not say this
    acts_on_own_buffers = True

    def __init__(self, *, fabric, cfg, act, train_phase, params, opt_state, moments_state):
        self.fabric = fabric
        self.act = act
        self.train_phase = train_phase
        self.params = params
        self.opt_state = opt_state
        self.moments_state = moments_state
        # the replay sampler stages train blocks with this sharding (off-thread when
        # prefetch is on); a channel trainer keeps it None — its data plane ships
        # host blocks and the learner stages them itself. The guard is TOTAL mesh
        # devices: a data x model mesh needs the batch committed to the mesh
        # (P("data") replicates it over the model axis) even when data extent is 1
        self.data_sharding = fabric.sharding(None, None, "data") if fabric.num_devices > 1 else None

    def train(self, data, cum_steps, train_key, want_full_state: bool, want_metrics: bool):
        """One train round over the ``[G, T, B, ...]`` block (already staged with
        ``data_sharding`` by the replay sampler). Returns
        ``(act_params, host_metrics_or_None)``."""
        # one-shot injected learning pathology (resilience.fault=lr_spike):
        # identity unless the fault armed this iteration
        self.params = apply_armed_learn_fault(self.params)
        with timer("train_dispatch"):  # G async dispatches: returns before the device is done
            self.params, self.opt_state, self.moments_state, metrics = self.train_phase(
                self.params,
                self.opt_state,
                self.moments_state,
                data,
                jnp.asarray(cum_steps),
                np.asarray(train_key),
            )
        # fresh output buffers (never donated), held for the telemetry health
        # guard — which only syncs them at window boundaries, off the hot path
        self.last_metrics = metrics
        host_metrics = None
        if want_metrics:
            with timer("metrics_get"):
                host_metrics = packed_device_get(metrics)
        return self.act.view(self.params), host_metrics

    def checkpoint_state(self):
        """(agent_params, opt_state, moments) for the checkpoint callback."""
        return self.params, self.opt_state, self.moments_state

    def sync_tree(self):
        """Tree to block on for steady-state bench windows (None = nothing)."""
        return self.params

    def close(self):
        """End-of-run teardown. A channel trainer returns the learner's FINAL full
        state here (paired with the shutdown sentinel) for a deferred last
        checkpoint; inline training has nothing deferred."""
        return None


def settle_act_placement(act: ActPlacement, trainer, fabric) -> None:
    """Where the player runs, decided here and nowhere else, from the trainer's class
    and the fabric's device count, before the first view is taken and the key placed
    (the placements, their costs and who may alias: ``ActPlacement``'s docstring).

    An inline trainer on one accelerator device: on that device, on the trainer's
    own buffers. The three player programs follow their arguments, ``prepare_obs``
    hands them host frames, and ``np.asarray(actions)`` is the loop's only wait for
    the device. Anything else keeps the host placement: a channel-backed trainer's
    view arrives as host bytes, and what a player over replicated or model-sharded
    parameters costs an env step on a mesh is not measured (PERF.md section 7)."""
    if getattr(trainer, "acts_on_own_buffers", False) and fabric.num_devices == 1:
        act.alias_device()


def run_dreamer(
    fabric,
    cfg: Dict[str, Any],
    *,
    build_agent_fn=None,
    player_cls=None,
    make_train_phase_fn=None,
    test_fn=None,
    trainer_factory=None,
    share_log_dir: bool = True,
    replay_factory=None,
    telemetry_factory=None,
):
    """The full Dreamer-V3 training loop, with the agent/player/train-phase factories
    injectable so forks with the same loop shape (offline_dreamer's CBWM, reference
    offline_dreamer.py:446-866) reuse it instead of copying ~400 lines.
    ``trainer_factory`` swaps the in-process trainer for a channel-backed one — the
    decoupled actor–learner topology (dreamer_v3_decoupled.py) reuses this exact
    loop as its player, passing ``share_log_dir=False`` in the multi-process
    topology: the learner processes never pair the log-dir share collective, so
    issuing it would desync the channel planes.

    ``replay_factory(cfg, log_dir, obs_keys, state, trainer, world_size) ->
    (rb, sampler)`` swaps the replay construction — the experience-service actor
    (``buffer.backend=service``) keeps only a tiny local ring for episode
    bookkeeping and ships rows to the standalone data plane. ``telemetry_factory``
    likewise overrides ``build_telemetry`` (per-actor role streams). A trainer
    advertising ``external_checkpoints = True`` (the service actor's — the
    LEARNER owns checkpoints there) makes this loop skip its checkpoint blocks
    entirely."""
    build_agent_fn = build_agent_fn or build_agent
    player_cls = player_cls or PlayerDV3
    make_train_phase_fn = make_train_phase_fn or make_train_phase
    test_fn = test_fn or test
    rank = fabric.global_rank
    world_size = fabric.world_size

    state = fabric.load(cfg.checkpoint.resume_from) if cfg.checkpoint.resume_from else None

    # These arguments cannot be changed (reference dreamer_v3.py:437-440)
    cfg.env.frame_stack = -1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, share=share_log_dir)
    logger = get_logger(fabric, cfg, log_dir=log_dir)
    fabric.logger = logger
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict())
    fabric.print(f"Log dir: {log_dir}")
    telemetry = (
        telemetry_factory(fabric, cfg, log_dir, logger)
        if telemetry_factory is not None
        else build_telemetry(fabric, cfg, log_dir, logger=logger)
    )
    resilience = build_resilience(fabric, cfg, log_dir, telemetry=telemetry)

    vectorized_env = gym.vector.SyncVectorEnv if cfg.env.sync_env else gym.vector.AsyncVectorEnv
    num_envs = int(cfg.env.num_envs)
    envs = vectorized_env(
        [
            partial(
                RestartOnException,
                make_env(
                    cfg,
                    cfg.seed + rank * num_envs + i,
                    rank * num_envs,
                    log_dir if rank == 0 else None,
                    "train",
                    vector_env_idx=i,
                ),
            )
            for i in range(num_envs)
        ],
        autoreset_mode=gym.vector.AutoresetMode.SAME_STEP,
    )
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    if (
        len(set(cnn_keys).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(mlp_keys).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
    if len(set(cfg.algo.cnn_keys.decoder) - set(cnn_keys)) > 0:
        raise RuntimeError(
            "The CNN keys of the decoder must be contained in the encoder ones. "
            f"Those keys are decoded without being encoded: {list(set(cfg.algo.cnn_keys.decoder))}"
        )
    if len(set(cfg.algo.mlp_keys.decoder) - set(mlp_keys)) > 0:
        raise RuntimeError(
            "The MLP keys of the decoder must be contained in the encoder ones. "
            f"Those keys are decoded without being encoded: {list(set(cfg.algo.mlp_keys.decoder))}"
        )
    if cfg.metric.log_level > 0:
        fabric.print("Encoder CNN keys:", cnn_keys)
        fabric.print("Encoder MLP keys:", mlp_keys)
        fabric.print("Decoder CNN keys:", list(cfg.algo.cnn_keys.decoder))
        fabric.print("Decoder MLP keys:", list(cfg.algo.mlp_keys.decoder))
    obs_keys = cnn_keys + mlp_keys

    key = fabric.seed_everything(cfg.seed + rank)
    key, agent_key = jax.random.split(key)
    agent, params = build_agent_fn(
        fabric,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        agent_key,
        state["agent"] if state else None,
    )
    player = player_cls(agent, num_envs, cnn_keys, mlp_keys)

    world_tx, actor_tx, critic_tx, opt_state = build_optimizers(cfg, params)
    if state is not None and "opt_state" in state:
        opt_state = jax.tree_util.tree_map(jnp.asarray, state["opt_state"])
    moments_state = init_moments()
    if state is not None and "moments" in state:
        moments_state = jax.tree_util.tree_map(jnp.asarray, state["moments"])

    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(cfg.metric.aggregator)

    rb = None
    if replay_factory is None:
        buffer_size = cfg.buffer.size // int(num_envs * world_size) if not cfg.dry_run else 8
        rb = EnvIndependentReplayBuffer(
            buffer_size,
            n_envs=num_envs,
            obs_keys=tuple(obs_keys),
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}"),
            buffer_cls=SequentialReplayBuffer,
        )
        if state is not None and "rb" in state:
            rb = state["rb"]

    from sheeprl_tpu.parallel.sharding import build_state_shardings

    train_phase = make_train_phase_fn(
        agent,
        cfg,
        world_tx,
        actor_tx,
        critic_tx,
        # pin the train state's output placement on any multi-device mesh:
        # replicated on 1-D dp, rule-sharded over `model` on a 2-D mesh
        state_shardings=build_state_shardings(fabric, params, opt_state, moments_state),
    )

    act = ActPlacement(fabric, lambda p: {"world_model": p["world_model"], "actor": p["actor"]})
    trainer = (trainer_factory or _InlineTrainer)(
        fabric=fabric,
        cfg=cfg,
        act=act,
        train_phase=train_phase,
        params=params,
        opt_state=opt_state,
        moments_state=moments_state,
    )
    settle_act_placement(act, trainer, fabric)
    act_params = act.view(params)
    key = act.place(key)

    # counters (reference dreamer_v3.py:571-597)
    start_iter = (state["iter_num"] // world_size) + 1 if state is not None else 1
    policy_step = state["iter_num"] * num_envs if state is not None else 0
    last_log = state["last_log"] if state is not None else 0
    last_checkpoint = state["last_checkpoint"] if state is not None else 0
    policy_steps_per_iter = int(num_envs * world_size)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if state is not None:
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size
        learning_starts += start_iter
        prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state is not None and "ratio" in state:
        ratio.load_state_dict(state["ratio"])

    # replay hot path: async prefetcher (sampling + sharded staging off-thread) or the
    # exact inline path when buffer.prefetch.enabled=false. Built AFTER the resume
    # block above so a restored batch size shapes the staged units. A
    # replay_factory (the experience-service actor) swaps in its own pair — a
    # tiny bookkeeping ring + an ingest-only sampler facade.
    if replay_factory is not None:
        rb, sampler = replay_factory(
            cfg=cfg,
            log_dir=log_dir,
            obs_keys=obs_keys,
            state=state,
            trainer=trainer,
            world_size=world_size,
        )
    else:
        sampler = make_replay_sampler(
            rb,
            cfg.buffer.get("prefetch"),
            sample_kwargs=dict(
                batch_size=cfg.algo.per_rank_batch_size * world_size,
                sequence_length=cfg.algo.per_rank_sequence_length,
            ),
            uint8_keys=cnn_keys,
            sharding=trainer.data_sharding,
            name="dv3-replay-prefetch",
        )
    telemetry.attach_sampler(sampler)

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )
    if cfg.checkpoint.every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The checkpoint.every parameter ({cfg.checkpoint.every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )

    # first observation
    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = np.asarray(obs[k])[np.newaxis]
    step_data["rewards"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["terminated"] = np.zeros((1, num_envs, 1), np.float32)
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    player.init_states(act_params)

    cumulative_per_rank_gradient_steps = 0
    train_step = 0
    last_train = 0
    act_dim = int(np.sum(actions_dim))
    pending_ckpt = False

    # Optional steady-state measurement window for bench.py (see bench.py docstring)
    bench = BenchWindow()
    bench.maybe_start(policy_step, trainer.sync_tree())  # then at the end of each iteration, for the next

    for iter_num in range(start_iter, total_iters + 1):
        policy_step += policy_steps_per_iter
        timer.iteration = iter_num  # the spans of one iteration share it

        with timer("Time/env_interaction_time"):
            if iter_num <= learning_starts and state is None:
                real_actions = actions = np.array(envs.action_space.sample())
                if not is_continuous:
                    # [num_envs, n_dims] (or [num_envs] for a single Discrete) → one
                    # one-hot block per action dim, env-major
                    per_dim = actions.reshape(num_envs, len(actions_dim)).T
                    actions = np.concatenate(
                        [np.eye(dim, dtype=np.float32)[act] for act, dim in zip(per_dim, actions_dim)],
                        axis=-1,
                    )
            else:
                with timer("act"):
                    jobs = prepare_obs(fabric, obs, cnn_keys=cnn_keys, mlp_keys=mlp_keys, num_envs=num_envs)
                    actions, key = player.get_actions(act_params, jobs, key)
                    actions = np.asarray(actions)
                if is_continuous:
                    real_actions = actions
                else:
                    splits = np.cumsum(actions_dim)[:-1]
                    real_actions = np.stack(
                        [b.argmax(-1) for b in np.split(actions, splits, axis=-1)], axis=-1
                    )

            step_data["actions"] = actions.reshape((1, num_envs, -1)).astype(np.float32)
            with timer("replay_add"):
                sampler.add(step_data, validate_args=cfg.buffer.validate_args)

            with timer("env_step"):
                next_obs, rewards, terminated, truncated, infos = envs.step(
                    real_actions.reshape(envs.action_space.shape)
                )
            dones = np.logical_or(terminated, truncated).astype(np.uint8)

        # episode stats, the copies of the next obs, the reset rows and the train decision:
        # with the spans around it, every host instant of an iteration lies in a span
        with timer("step_bookkeeping"):
            step_data["is_first"] = np.zeros_like(step_data["terminated"])
            if "restart_on_exception" in infos:
                # surface the crash-restart (previously invisible): Health/env_restarts
                # gauge + an immediate health event in telemetry.jsonl
                telemetry.observe_env_restart(int(np.sum(infos["restart_on_exception"])))
                # in-place ring-storage rewrite: take the sampler lock so a concurrent
                # prefetch gather never reads a torn episode-boundary row
                with sampler.lock:
                    for i, agent_roe in enumerate(infos["restart_on_exception"]):
                        if agent_roe and not dones[i]:
                            sub_rb = rb.buffer[i]
                            last_inserted_idx = (sub_rb._pos - 1) % sub_rb.buffer_size
                            sub_rb["terminated"][last_inserted_idx] = np.zeros_like(
                                sub_rb["terminated"][last_inserted_idx]
                            )
                            sub_rb["truncated"][last_inserted_idx] = np.ones_like(
                                sub_rb["truncated"][last_inserted_idx]
                            )
                            sub_rb["is_first"][last_inserted_idx] = np.zeros_like(
                                sub_rb["is_first"][last_inserted_idx]
                            )
                            step_data["is_first"][:, i] = np.ones_like(step_data["is_first"][:, i])

            ep_info = infos.get("final_info", infos)
            if (cfg.metric.log_level > 0 or telemetry.enabled) and "episode" in ep_info:
                ep = ep_info["episode"]
                mask = ep.get("_r", ep_info.get("_episode", np.ones(num_envs, bool)))
                rews, lens = ep["r"][mask], ep["l"][mask]
                if len(rews) > 0:
                    telemetry.observe_episodes(rews, lens)
                    if aggregator and not aggregator.disabled:
                        aggregator.update("Rewards/rew_avg", float(np.mean(rews)))
                        aggregator.update("Game/ep_len_avg", float(np.mean(lens)))

            # real next obs of finished episodes (reference dreamer_v3.py:701-708)
            real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
            final_obs_arr = infos.get("final_observation", infos.get("final_obs"))
            if final_obs_arr is not None:
                for idx in range(num_envs):
                    if final_obs_arr[idx] is not None:
                        for k in obs_keys:
                            real_next_obs[k][idx] = np.asarray(final_obs_arr[idx][k])

            for k in obs_keys:
                step_data[k] = np.asarray(next_obs[k])[np.newaxis]
            obs = next_obs

            rewards = np.asarray(rewards, dtype=np.float32).reshape((1, num_envs, -1))
            step_data["terminated"] = np.asarray(terminated, np.float32).reshape((1, num_envs, -1))
            step_data["truncated"] = np.asarray(truncated, np.float32).reshape((1, num_envs, -1))
            step_data["rewards"] = clip_rewards_fn(rewards)

            dones_idxes = dones.nonzero()[0].tolist()
            reset_envs = len(dones_idxes)
            if reset_envs > 0:
                reset_data = {}
                for k in obs_keys:
                    reset_data[k] = (real_next_obs[k][dones_idxes])[np.newaxis]
                reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
                reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
                reset_data["actions"] = np.zeros((1, reset_envs, act_dim), np.float32)
                reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
                reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
                with timer("replay_add"):
                    sampler.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)
                # the reset rows restart the episode in the *live* step_data
                step_data["rewards"][:, dones_idxes] = 0.0
                step_data["terminated"][:, dones_idxes] = 0.0
                step_data["truncated"][:, dones_idxes] = 0.0
                step_data["is_first"][:, dones_idxes] = 1.0
                with timer("player_reset"):
                    player.init_states(act_params, dones_idxes)

            # checkpoint due? (computed BEFORE the train round so a channel trainer can
            # ship the full state with it; a deferring trainer postpones off-round
            # checkpoints to the next train round). A preemption forces an
            # out-of-cadence emergency checkpoint through the same path; the flag is
            # snapshotted once per iteration so the save and the loop-exit break can
            # never disagree about it.
            preempted = resilience.preempt_requested()
            pending_ckpt = pending_ckpt or preempted or (
                (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every)
                or cfg.dry_run
                or (iter_num == total_iters and cfg.checkpoint.save_last)
            )
            trained_this_iter = False
            per_rank_gradient_steps = 0
            if iter_num >= learning_starts:
                ratio_steps = policy_step - prefill_steps * policy_steps_per_iter
                per_rank_gradient_steps = ratio(ratio_steps / world_size)

        # train
        if per_rank_gradient_steps > 0:
            with timer("Time/train_time"):
                with timer("replay_sample"):
                    data = sampler.sample(per_rank_gradient_steps)
                with timer("train_key"):
                    key, train_key = jax.random.split(key)
                act_params, host_metrics = trainer.train(
                    data,
                    cumulative_per_rank_gradient_steps,
                    train_key,
                    want_full_state=pending_ckpt,
                    want_metrics=bool(aggregator and not aggregator.disabled),
                )
                with timer("train_observe"):
                    cumulative_per_rank_gradient_steps += per_rank_gradient_steps
                    train_step += world_size * per_rank_gradient_steps
                    trained_this_iter = True
                    telemetry.observe_train(
                        per_rank_gradient_steps,
                        host_metrics if host_metrics is not None else getattr(trainer, "last_metrics", None),
                    )
                    # the Learn/ keys ride the metrics dict; device refs are
                    # fine — telemetry only fetches them at window cadence
                    telemetry.observe_learn(
                        host_metrics if host_metrics is not None else getattr(trainer, "last_metrics", None)
                    )
                    if telemetry.wants_program("train_step") and getattr(trainer, "params", None) is not None:
                        # the compiled unit is the single fused gradient step the
                        # host G-loop drives; its batch aval is one [T, B] slice of
                        # the staged [G, T, B] block (metadata only, no device op;
                        # sharding preserved so the lowering matches the live program)
                        batch_avals = unit_avals(data)
                        telemetry.register_program(
                            "train_step",
                            trainer.train_phase.train_step,
                            (
                                trainer.params,
                                trainer.opt_state,
                                trainer.moments_state,
                                batch_avals,
                                jnp.asarray(cumulative_per_rank_gradient_steps),
                                jnp.asarray(train_key),
                            ),
                            units=1,
                        )
                    if host_metrics is not None and aggregator and not aggregator.disabled:
                        for mk, mv in host_metrics.items():
                            aggregator.update(mk, float(mv))

        # log, checkpoint, and the next iteration's bench window: the iteration's last span
        with timer("loop_tail"):
            telemetry.step(policy_step)
            resilience.step(policy_step)
            if cfg.metric.log_level > 0 and (
                policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters or cfg.dry_run
            ):
                with timer("Time/logging_time"):
                    metrics_dict = aggregator.compute() if aggregator else {}
                    if logger is not None:
                        logger.log_metrics(metrics_dict, policy_step)
                        if policy_step > 0:
                            logger.log_metrics(
                                {
                                    "Params/replay_ratio": cumulative_per_rank_gradient_steps
                                    * world_size
                                    / max(policy_step, 1)
                                },
                                policy_step,
                            )
                        timers = timer.to_dict(reset=False)
                        if timers.get("Time/train_time", 0) > 0:
                            logger.log_metrics(
                                {"Time/sps_train": (train_step - last_train) / max(timers["Time/train_time"], 1e-9)},
                                policy_step,
                            )
                        if timers.get("Time/env_interaction_time", 0) > 0:
                            logger.log_metrics(
                                {
                                    "Time/sps_env_interaction": (
                                        (policy_step - last_log) / world_size * cfg.env.action_repeat
                                    )
                                    / max(timers["Time/env_interaction_time"], 1e-9)
                                },
                                policy_step,
                            )
                    timer.to_dict(reset=True)
                    if aggregator:
                        aggregator.reset()
                last_log = policy_step
                last_train = train_step

            # checkpoint (a deferring trainer only has full state at train rounds; its
            # last pending checkpoint, if any, is flushed by close() below; a trainer
            # with external_checkpoints — the service actor, whose LEARNER owns the
            # full state — never checkpoints from this loop at all)
            if (
                pending_ckpt
                and not getattr(trainer, "external_checkpoints", False)
                and (not trainer.defers_checkpoints or trained_this_iter)
            ):
                last_checkpoint = policy_step
                pending_ckpt = False
                ckpt_agent, ckpt_opt, ckpt_moments = trainer.checkpoint_state()
                ckpt_state = {
                    "agent": ckpt_agent,
                    "opt_state": ckpt_opt,
                    "moments": ckpt_moments,
                    "ratio": ratio.state_dict(),
                    "iter_num": iter_num * world_size,
                    "batch_size": cfg.algo.per_rank_batch_size * world_size,
                    "last_log": last_log,
                    "last_checkpoint": last_checkpoint,
                }
                ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt")
                # quiesce the prefetch worker so the pickled buffer (incl. its RNG
                # state) is not a torn mid-sample snapshot
                with sampler.lock, timer("Time/checkpoint_time"):
                    fabric.call(
                        "on_checkpoint_coupled",
                        ckpt_path=ckpt_path,
                        state=ckpt_state,
                        replay_buffer=rb if cfg.buffer.checkpoint else None,
                    )
                resilience.observe_checkpoint(ckpt_path, policy_step, preempted=preempted)
            if not preempted and iter_num < total_iters:
                bench.maybe_start(policy_step, trainer.sync_tree())
        if preempted:
            # still-pending emergency checkpoint (a deferring trainer without a
            # train round this iteration) is flushed by the close() path below;
            # breaking — rather than raising — runs the normal teardown, which
            # forwards the shutdown to channel trainer ranks
            break

    bench.finish(policy_step, trainer.sync_tree())

    sampler.close()
    final_state = trainer.close()
    if pending_ckpt and final_state is not None:
        # deferred last checkpoint: the learner's final full state rode the
        # shutdown handshake
        ckpt_agent, ckpt_opt, ckpt_moments = final_state
        ckpt_state = {
            "agent": ckpt_agent,
            "opt_state": ckpt_opt,
            "moments": ckpt_moments,
            "ratio": ratio.state_dict(),
            # iter_num (not total_iters): a preempt-break flushes here BEFORE the
            # run finished, and a resumed run must not think it completed
            "iter_num": iter_num * world_size,
            "batch_size": cfg.algo.per_rank_batch_size * world_size,
            "last_log": last_log,
            "last_checkpoint": policy_step,
        }
        ckpt_path = os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt")
        # quiesce the prefetch worker so the pickled buffer (incl. its RNG
        # state) is not a torn mid-sample snapshot
        with sampler.lock, timer("Time/checkpoint_time"):
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                replay_buffer=rb if cfg.buffer.checkpoint else None,
            )
        resilience.observe_checkpoint(ckpt_path, policy_step, preempted=preempted)

    envs.close()
    # an in-flight async (orbax) checkpoint write must land before teardown
    wait_for_checkpoint()
    if not resilience.finalize(policy_step) and fabric.is_global_zero and cfg.algo.run_test:
        with timer("Time/test_time"):
            test_fn(player, act_params, fabric, cfg, log_dir, greedy=False)
    # closed AFTER the final test so the summary phases include eval time; an
    # exception path that skips this is flushed by cli.run_algorithm with
    # clean_exit=False
    telemetry.close(policy_step)
    if logger is not None:
        logger.finalize()


@register_algorithm()
def main(fabric, cfg: Dict[str, Any]):
    return run_dreamer(fabric, cfg)
