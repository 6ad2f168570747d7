"""The adapter of the fused on-device PPO loop with a sequence policy on the `deepseek_v3`
trunk (`algo.lm.model_type=deepseek_v3`, `sheeprl_tpu/models/deepseek_v3.py`): the
configuration `moonlight_16b_a3b_ep8` names this file.

It is `adapters/ppo_anakin_lm.py`'s adapter as `adapters/ppo_anakin_qwen3_next.py` runs its
reference's side (both loaded by their paths, beside this file): the same seams of
`run_anakin`, the same copies of the timed path's FIRST fused call, the same twelve compared
numbers, and the reference followed in blocks with Adam's two moments on the host while a
minibatch's gradient is taken. What differs is the trunk's `model` block (`spec`), its FLOPs
(`step_flops`: `harness/dsv3_flops.py`) and the blocks' sizes. The reference (float32,
`highest`, the latent attention in its expanded form, a layer recomputed in its backward
pass) holds 2.68 GB of parameters and as much of gradient while it differentiates; FOUR
sequences at a time add about 1.5 GB of a layer's activations and the logits (4 x 512 x 20,480
floats, several times over), so the program that takes a block's gradient fits the chip's
16.9 GB with room, where sixteen at once would not; the forward alone takes eight.
"""

from __future__ import annotations

import os

from perfbench.harness.bench import load_file

_q3n = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)), "ppo_anakin_qwen3_next.py"))

LM_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "qk_nope_head_dim",
           "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "num_hidden_layers", "first_k_dense_replace",
           "num_experts_per_tok", "n_shared_experts")


class Adapter(_q3n.Adapter):
    reference_block = 4  # sequences the reference differentiates at a time
    forward_block = 8  # sequences its forward alone takes at a time

    # -- the configuration -------------------------------------------------------
    def spec(self, cfg) -> dict:
        algo, lm = cfg.algo, cfg.algo.lm
        covered = {
            "sequence policy on the deepseek_v3 trunk": str(algo.get("policy")) == "sequence" and str(lm.get("model_type")) == "deepseek_v3",
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
        program counted on its held experts, the update's attention by the expanded form and a
        decode step's by the absorbed form's products over the rows written so far."""
        from perfbench.harness import dsv3_flops

        return dsv3_flops.iteration_flops(m, self.counters)
