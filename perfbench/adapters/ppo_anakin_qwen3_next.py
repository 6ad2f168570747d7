"""The adapter of the fused on-device PPO loop with a sequence policy on the `qwen3_next`
trunk (`algo.lm.model_type=qwen3_next`, `sheeprl_tpu/models/qwen3_next.py`): the
configuration `qwen3_next_80b_a3b_ep16` names this file.

It is `adapters/ppo_anakin_lm.py`'s adapter (loaded by its path, beside this file): the same
seams of `run_anakin`, the same copies of the timed path's FIRST fused call, the same twelve
compared numbers. What differs is the trunk's `model` block (`spec`), its FLOPs
(`step_flops`: `harness/q3n_flops.py`) and how the reference's side is run so that it fits
beside nothing: the plain reference's delta rule is the per-token recurrence, whose backward
pass keeps a matrix state a token a head (1.07 GB a sequence a layer at 512 steps), so it
follows the update ONE sequence at a time with a layer recomputed in its backward pass
(5.3 GB of temporaries by the compiler's count, beside the parameters and the gradient), and
Adam's two moments wait on the host while a minibatch's gradient is taken: with them on the
device the gradient's program did not load (my chip run, PR 35: 0.4 GB free of 16.9). The
weights the run started from are drawn again from the seed, inside the one program that
takes the parameters' change, as on the program's side.
"""

from __future__ import annotations

import os
from functools import partial

import numpy as np

from perfbench.harness.bench import load_file

_lm = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)), "ppo_anakin_lm.py"))
leaf_norms, named = _lm.leaf_norms, _lm.named

LM_KEYS = ("hidden_size", "moe_intermediate_size", "shared_expert_intermediate_size", "num_attention_heads",
           "num_key_value_heads", "head_dim", "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
           "linear_value_head_dim", "linear_conv_kernel_dim", "chunk_size", "num_experts_per_tok")


class Adapter(_lm.Adapter):
    reference_block = 1  # sequences the reference differentiates at a time
    forward_block = 8  # sequences its forward alone takes at a time (no state is kept for a backward pass)

    # -- the configuration -------------------------------------------------------
    def spec(self, cfg) -> dict:
        algo, lm = cfg.algo, cfg.algo.lm
        covered = {
            "sequence policy on the qwen3_next trunk": str(algo.get("policy")) == "sequence" and str(lm.get("model_type")) == "qwen3_next",
            "no schedule": not (algo.anneal_lr or algo.anneal_clip_coef or algo.anneal_ent_coef),
            "plain loss": algo.loss_reduction == "mean" and not algo.clip_vloss and not algo.normalize_advantages,
            "no gradient clip": not algo.max_grad_norm,
            "whole minibatches": int(cfg.env.num_envs) % int(algo.per_rank_batch_size) == 0,
            "the token env": cfg.env.id == "token_copy" and int(cfg.env.tokens.episode_steps) == int(algo.rollout_steps),
        }
        broken = [k for k, ok in covered.items() if not ok]
        if broken:
            raise ValueError(f"the plain reference does not cover this configuration: {broken}")
        return {
            **{k: int(lm[k]) for k in LM_KEYS},
            "rotary_dim": int(int(lm.head_dim) * float(lm.partial_rotary_factor)),
            "vocab_size": int(lm.vocab_size),
            "layer_types": [str(t) for t in lm.layer_types],
            "num_experts_routed": int(lm.num_experts),
            "experts_held": [int(lm.experts_held[0]), int(lm.experts_held[1])],
            "norm_eps": float(lm.norm_eps),
            "rope_theta": float(lm.rope_theta),
            "rollout_steps": int(algo.rollout_steps),
            "num_envs": int(cfg.env.num_envs),
            "prompt": [int(cfg.env.tokens.prompt_min), int(cfg.env.tokens.prompt_max)],
            "minibatch_sequences": int(algo.per_rank_batch_size),
            "update_epochs": int(algo.update_epochs),
            "gamma": float(algo.gamma),
            "gae_lambda": float(algo.gae_lambda),
            "clip_coef": float(algo.clip_coef),
            "vf_coef": float(algo.vf_coef),
            "ent_coef": float(algo.ent_coef),
            "lr": float(algo.optimizer.lr),
            "eps": float(algo.optimizer.eps),
            "precision": str(cfg.fabric.precision),
            "matmul_precision": str(cfg.float32_matmul_precision),
        }

    def step_flops(self, m: dict) -> float:
        """Model FLOPs of one whole iteration, rollout and update, the experts by the pairs the
        program counted on its held experts, the delta rule by its recurrent form's products."""
        from perfbench.harness import q3n_flops

        return q3n_flops.iteration_flops(m, self.counters)

    # -- the comparison ----------------------------------------------------------
    def run_reference(self, m: dict, seed: int, recorded: dict) -> dict:
        """The reference's side of the first call, as `ppo_anakin_lm.Adapter.run_reference`
        gives it (its forward over the recorded tokens following the program's expert
        choices, then every gradient step of the call with Adam), in blocks that fit."""
        import jax
        import jax.numpy as jnp

        ref, rec = self.ref, recorded["record"]
        traj = {k: np.swapaxes(np.asarray(v), 0, 1) for k, v in rec["traj"].items()}  # [E, T, ...]
        chosen = traj["route_ids"]
        with jax.default_matmul_precision("highest"):
            params = jax.jit(partial(ref.init_params, m))(np.int32(seed))
            forward = jax.jit(lambda p, tokens, ids: ref.forward(p, m, tokens, ids))
            logps, values, owns, margins = [], [], [], []
            for lo in range(0, traj["tokens"].shape[0], self.forward_block):
                part = slice(lo, lo + self.forward_block)
                logits, value, own, margin = forward(params, jnp.asarray(traj["tokens"][part]), jnp.asarray(chosen[part]))
                logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                           jnp.asarray(traj["actions"][part])[..., None], axis=-1)[..., 0]
                logps.append(np.asarray(logp)), values.append(np.asarray(value))
                owns.append(np.asarray(own)), margins.append(np.asarray(margin))
            del logits
            # the update's inputs are the program's own rollout: its values, log-probs, rewards
            to_time = lambda a: jnp.asarray(np.swapaxes(a, 0, 1))  # noqa: E731
            returns, advantages = jax.jit(partial(ref.gae, gamma=m["gamma"], lam=m["gae_lambda"]))(
                to_time(traj["rewards"]), to_time(traj["values"]), to_time(traj["dones"]),
                jnp.zeros((traj["tokens"].shape[0],), jnp.float32))
            data = {k: traj[k] for k in ("tokens", "actions", "logprobs", "mask")}
            data["returns"], data["advantages"] = np.asarray(returns).T, np.asarray(advantages).T
            step = jax.jit(partial(ref.block_grad, m), donate_argnums=(1,))
            adam = jax.jit(ref.adam_step, donate_argnums=(0, 1))
            # Adam's moments, on the host between its steps
            opt, losses, update_owns, update_margins, advantage_scale = jax.device_get(ref.adam_init(params)), [], [], [], []
            for g, rows in enumerate(np.asarray(rec["sequences"])):
                batch = {k: jnp.asarray(v[rows]) for k, v in data.items()}
                counted = data["mask"][rows].astype(np.float64)
                advantage_scale.append(float(np.sum(np.abs(data["advantages"][rows]) * counted) / max(counted.sum(), 1.0)))
                grads, parts, own, margin = ref.minibatch_grad(
                    step, params, batch, jnp.asarray(rec["update_route_ids"][g]), recorded["clip_coef"],
                    recorded["ent_coef"], self.reference_block)
                params, opt = adam(params, jax.device_put(opt), grads, m["lr"], m["eps"])
                del grads
                mu_norms = named(jax.jit(leaf_norms)(opt["mu"]))  # after the call's last step: the one compared
                opt = jax.device_get(opt)
                losses.append(np.asarray(parts))
                update_owns.append(own), update_margins.append(margin)
            del opt
            changed = lambda seed, after: leaf_norms(  # noqa: E731
                jax.tree_util.tree_map(jnp.subtract, after, ref.init_params(m, seed)))
            update_norms = named(jax.jit(changed)(np.int32(seed), params))
        episodes = recorded["episodes"]
        return {"mu_norms": mu_norms, "update_norms": update_norms, "losses": np.stack(losses),
                "advantage_scale": np.asarray(advantage_scale),
                "env": ref.copy_env(episodes["prompt"], episodes["prompt_len"], traj["actions"]),
                "logprobs": np.concatenate(logps), "values": np.concatenate(values),
                "own": np.concatenate(owns), "margin": np.concatenate(margins),
                "update_own": np.stack(update_owns), "update_margin": np.stack(update_margins)}
