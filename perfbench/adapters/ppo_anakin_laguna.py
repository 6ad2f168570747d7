"""The adapter of the fused on-device PPO loop with a sequence policy on the `laguna` trunk
(`algo.lm.model_type=laguna`, `sheeprl_tpu/models/laguna.py`): the configuration `laguna_xs2_ep16`
names this file.

It is `adapters/ppo_anakin_qwen3_next.py`'s adapter (loaded by its path, beside this file): the
same seams of `run_anakin`, the same copies of the timed path's FIRST fused call, the same twelve
compared numbers, and the reference's side run ONE sequence at a time with a layer recomputed in its
backward pass and the weights the run started from drawn again from the seed for the parameters'
change. The plain reference's attention is a masked `[T, T]` softmax on every layer, whose scores and
probabilities are 1.07 GB each for a sequence of 2,048 tokens in a layer of 64 heads, so its forward
takes two sequences at a time. Adam's two moments stay on the chip (`run_reference`): by the
compiler's count for a described v5e a gradient block needs 2.72 GB of temporaries beside its 3.92 GB
of parameters and gradient, so the 3.92 GB of moments fit beside them, and a run moves no 63 GB of
moments between the chip and the host. What differs besides is the trunk's `model` block (`spec`)
and its FLOPs (`step_flops`: `harness/lg_flops.py`).
"""

from __future__ import annotations

import json
import os
import time
from functools import partial

import numpy as np

from perfbench.harness.bench import load_file, log

_q3n = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)), "ppo_anakin_qwen3_next.py"))
leaf_norms, named = _q3n.leaf_norms, _q3n.named

LM_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "shared_expert_intermediate_size",
           "num_key_value_heads", "head_dim", "num_hidden_layers", "num_experts_per_tok", "sliding_window")
KINDS = ("full_attention", "sliding_attention")


def _rope(group) -> dict:
    """A layer kind's rope parameters, numbers as floats: the file's and the composed config's read alike."""
    return {str(k): v if isinstance(v, str) else float(v) for k, v in group.items()}


class Adapter(_q3n.Adapter):
    forward_block = 2  # sequences its forward takes at a time: a layer's scores are [64, 2048, 2048] a sequence

    # -- the configuration -------------------------------------------------------
    def spec(self, cfg) -> dict:
        algo, lm = cfg.algo, cfg.algo.lm
        covered = {
            "sequence policy on the laguna trunk": str(algo.get("policy")) == "sequence" and str(lm.get("model_type")) == "laguna",
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
            "layer_types": [str(t) for t in lm.layer_types],
            "mlp_layer_types": [str(t) for t in lm.mlp_layer_types],
            "num_attention_heads_per_layer": [int(n) for n in lm.num_attention_heads_per_layer],
            "rope_parameters": {kind: _rope(lm.rope_parameters[kind]) for kind in KINDS},
            "routed_scaling_factor": float(lm.moe_routed_scaling_factor),
            "vocab_size": int(lm.vocab_size),
            "num_experts_routed": int(lm.num_experts),
            "experts_held": [int(lm.experts_held[0]), int(lm.experts_held[1])],
            "norm_eps": float(lm.rms_norm_eps),
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
        program counted on its held experts, each attention layer by the keys its mask lets a
        query read (a sliding layer's window, a full layer's every earlier position)."""
        from perfbench.harness import lg_flops

        return lg_flops.iteration_flops(m, self.counters)

    # -- the comparison ----------------------------------------------------------
    def run_reference(self, m: dict, seed: int, recorded: dict) -> dict:
        """The reference's side of the first call, as `ppo_anakin_qwen3_next.Adapter.run_reference`
        gives it (its forward over the recorded tokens following the program's expert choices, then
        every gradient step of the call with Adam, one sequence at a time), with Adam's moments on the
        chip; the seconds of each part go to the log, the first call of each program apart (it is
        where a program compiles, or loads from the compile cache)."""
        import jax
        import jax.numpy as jnp

        ref, rec, seconds = self.ref, recorded["record"], {}
        clock = [time.perf_counter()]

        def lap(name):
            now = time.perf_counter()
            seconds[name] = round(seconds.get(name, 0.0) + now - clock[0], 3)
            clock[0] = now

        traj = {k: np.swapaxes(np.asarray(v), 0, 1) for k, v in rec["traj"].items()}  # [E, T, ...]
        chosen = traj["route_ids"]
        with jax.default_matmul_precision("highest"):
            params = jax.jit(partial(ref.init_params, m))(np.int32(seed))
            jax.block_until_ready(params)
            lap("weights")
            forward = jax.jit(lambda p, tokens, ids: ref.forward(p, m, tokens, ids))
            logps, values, owns, margins = [], [], [], []
            for lo in range(0, traj["tokens"].shape[0], self.forward_block):
                part = slice(lo, lo + self.forward_block)
                logits, value, own, margin = forward(params, jnp.asarray(traj["tokens"][part]), jnp.asarray(chosen[part]))
                logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                           jnp.asarray(traj["actions"][part])[..., None], axis=-1)[..., 0]
                logps.append(np.asarray(logp)), values.append(np.asarray(value))
                owns.append(np.asarray(own)), margins.append(np.asarray(margin))
                lap("forward_first" if lo == 0 else "forward_rest")
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
            opt, losses, update_owns, update_margins, advantage_scale = ref.adam_init(params), [], [], [], []
            for g, rows in enumerate(np.asarray(rec["sequences"])):
                batch = {k: jnp.asarray(v[rows]) for k, v in data.items()}
                counted = data["mask"][rows].astype(np.float64)
                advantage_scale.append(float(np.sum(np.abs(data["advantages"][rows]) * counted) / max(counted.sum(), 1.0)))
                grads, parts, own, margin = ref.minibatch_grad(
                    step, params, batch, jnp.asarray(rec["update_route_ids"][g]), recorded["clip_coef"],
                    recorded["ent_coef"], self.reference_block)
                params, opt = adam(params, opt, grads, m["lr"], m["eps"])
                del grads
                losses.append(np.asarray(parts))
                update_owns.append(own), update_margins.append(margin)
                lap("gradient_step_first" if g == 0 else "gradient_steps_rest")
            mu_norms = named(jax.jit(leaf_norms)(opt["mu"]))
            del opt
            changed = lambda seed, after: leaf_norms(  # noqa: E731
                jax.tree_util.tree_map(jnp.subtract, after, ref.init_params(m, seed)))
            update_norms = named(jax.jit(changed)(np.int32(seed), params))
            lap("norms")
        log("reference's side (s):", json.dumps(seconds))
        episodes = recorded["episodes"]
        return {"mu_norms": mu_norms, "update_norms": update_norms, "losses": np.stack(losses),
                "advantage_scale": np.asarray(advantage_scale),
                "env": ref.copy_env(episodes["prompt"], episodes["prompt_len"], traj["actions"]),
                "logprobs": np.concatenate(logps), "values": np.concatenate(values),
                "own": np.concatenate(owns), "margin": np.concatenate(margins),
                "update_own": np.stack(update_owns), "update_margin": np.stack(update_margins)}
