"""The adapter of the fused on-device PPO loop with a sequence policy on the `kimi_linear`
trunk (`algo.lm.model_type=kimi_linear`, `sheeprl_tpu/models/kimi_linear.py`): the
configuration `kimi_linear_48b_a3b_ep32` names this file.

It is `adapters/ppo_anakin_qwen3_next.py`'s adapter (loaded by its path, beside this file): the
same seams of `run_anakin`, the same copies of the timed path's FIRST fused call, the same twelve
compared numbers, and the reference's side run as that one runs it, ONE sequence at a time
with a layer recomputed in its backward pass and Adam's two moments on the host while a
minibatch's gradient is taken: the plain reference's Kimi delta attention is the per-token
recurrence, whose backward pass keeps a `[32, 128, 128]` state a token (1.07 GB a sequence a
layer at 512 steps), as Qwen3-Next's does. What differs is the trunk's `model` block (`spec`)
and its FLOPs (`step_flops`: `harness/kl_flops.py`).
"""

from __future__ import annotations

import os

from perfbench.harness.bench import load_file

_q3n = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)), "ppo_anakin_qwen3_next.py"))

LM_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "qk_nope_head_dim",
           "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "num_hidden_layers", "first_k_dense_replace",
           "num_experts_per_tok", "num_shared_experts", "chunk_size")
LINEAR_KEYS = {"linear_num_heads": "num_heads", "linear_head_dim": "head_dim", "short_conv_kernel_size": "short_conv_kernel_size"}


class Adapter(_q3n.Adapter):
    # -- the configuration -------------------------------------------------------
    def spec(self, cfg) -> dict:
        algo, lm = cfg.algo, cfg.algo.lm
        covered = {
            "sequence policy on the kimi_linear trunk": str(algo.get("policy")) == "sequence" and str(lm.get("model_type")) == "kimi_linear",
            "latent attention without rotary embedding": bool(lm.mla_use_nope),
            "no schedule": not (algo.anneal_lr or algo.anneal_clip_coef or algo.anneal_ent_coef),
            "plain loss": algo.loss_reduction == "mean" and not algo.clip_vloss and not algo.normalize_advantages,
            "no gradient clip": not algo.max_grad_norm,
            "whole minibatches": int(cfg.env.num_envs) % int(algo.per_rank_batch_size) == 0,
            "the token env": cfg.env.id == "token_copy" and int(cfg.env.tokens.episode_steps) == int(algo.rollout_steps),
        }
        broken = [k for k, ok in covered.items() if not ok]
        if broken:
            raise ValueError(f"the plain reference does not cover this configuration: {broken}")
        linear = lm.linear_attn_config
        return {
            **{k: int(lm[k]) for k in LM_KEYS},
            **{k: int(linear[name]) for k, name in LINEAR_KEYS.items()},
            "kda_layers": [int(i) for i in linear.kda_layers],
            "full_attn_layers": [int(i) for i in linear.full_attn_layers],
            "routed_scaling_factor": float(lm.routed_scaling_factor),
            "vocab_size": int(lm.vocab_size),
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
        program counted on its held experts, Kimi delta attention by its recurrent form's
        products, the latent attention by the expanded form in the update and the absorbed form
        in a decode step."""
        from perfbench.harness import kl_flops

        return kl_flops.iteration_flops(m, self.counters)
