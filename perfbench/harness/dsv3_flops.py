"""Model FLOPs and bytes of the sequence-policy PPO iteration on the `deepseek_v3` trunk
(`adapters/ppo_anakin_deepseek_v3.py`), from the configuration's `model` block, the positions
and the pairs the program counted on its held experts; never from the program's buffers, so
that a roofline reads the same work whatever implements it. Matrix products only (2 FLOPs a
multiply-add); a backward pass counts as two forwards; recomputation and padding are not
counted. The latent attention counts in the form each phase is defined by: the UPDATE's by the
expanded form (a head's keys and values made from the latent once a token, scores over
`nope + rope` channels, values over `v_head_dim`), a DECODE step's by the absorbed form (the
query carried into the latent's space and the weighted latent carried out, a head each; scores
over the `rank + rope` channels of the rows written so far and the weighted sum over their
`rank`). Used by `train_step_mfu` (through the adapter's `step_flops`),
`dsv3_mla_decode_roofline_share` and `dsv3_experts_roofline_share`."""

from __future__ import annotations

from typing import Optional

from perfbench.harness import lm_flops

pair_macs = lm_flops.pair_macs


def _as_lm(m: dict) -> dict:
    """The block under the names `lm_flops` counts expert layers by."""
    return {**m, "layer_types": [None] * m["num_hidden_layers"], "num_dense_layers": m["first_k_dense_replace"]}


def counted_pairs(m: dict, counters: Optional[dict]):
    return lm_flops.counted_pairs(_as_lm(m), counters)


def projection_macs(m: dict) -> float:
    """Multiply-adds a token a layer of the mixer's products that both forms share: `W_q`, `W_kva`, `W_o`."""
    h, nh = m["hidden_size"], m["num_attention_heads"]
    return h * nh * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) \
        + nh * m["v_head_dim"] * h


def expanded_macs(m: dict, context: float) -> float:
    """Multiply-adds a token a layer of the expanded form alone, over `context` keys: `W_kvb`,
    the scores and the weighted values."""
    nh, dn, dr, dv = m["num_attention_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return m["kv_lora_rank"] * nh * (dn + dv) + nh * (dn + dr + dv) * context


def absorbed_macs(m: dict, rows: float) -> float:
    """Multiply-adds a token a layer of the absorbed form alone, over `rows` cached rows: the
    query into the latent's space, the scores over a row's `rank + rope` channels, the
    weighted sum of the latents, and a head's value map on it."""
    nh, r = m["num_attention_heads"], m["kv_lora_rank"]
    return nh * m["qk_nope_head_dim"] * r + nh * (2 * r + m["qk_rope_head_dim"]) * rows + nh * r * m["v_head_dim"]


def ffn_macs(m: dict) -> float:
    """Multiply-adds a token of every layer's feed-forward outside the routed experts: the
    leading dense layers, then the router and the shared experts' one SwiGLU."""
    h, dense = m["hidden_size"], m["first_k_dense_replace"]
    moe = h * m["num_experts_routed"] + 3 * h * m["n_shared_experts"] * m["moe_intermediate_size"]
    return dense * 3 * h * m["intermediate_size"] + (m["num_hidden_layers"] - dense) * moe


def head_macs(m: dict) -> float:
    return m["hidden_size"] * m["vocab_size"] + m["hidden_size"]


def iteration_flops(m: dict, counters: Optional[dict] = None) -> float:
    """One whole iteration: the rollout's forward, one token a step in the absorbed form over
    the rows written so far, and the update's forward and backward over every sequence
    `update_epochs` times in the expanded form."""
    tokens, layers = m["rollout_steps"] * m["num_envs"], m["num_hidden_layers"]
    mean_context = (m["rollout_steps"] + 1) / 2.0
    shared = layers * projection_macs(m) + ffn_macs(m) + head_macs(m)
    rollout_pairs, update_pairs = counted_pairs(m, counters)
    forward = 2.0 * (tokens * (shared + layers * absorbed_macs(m, mean_context)) + rollout_pairs * pair_macs(m))
    update = 3 * 2.0 * (tokens * m["update_epochs"] * (shared + layers * expanded_macs(m, mean_context))
                        + update_pairs * pair_macs(m))
    return forward + update


def update_experts_flops_bytes(m: dict, counters: Optional[dict] = None):
    """(FLOPs, bytes) the `experts` scope of ONE iteration's update needs: `lm_flops`'s count
    (the grouped products over the counted pairs; the held weights read twice and their
    gradient written, each pair's rows in and out), over this trunk's expert layers."""
    return lm_flops.update_experts_flops_bytes(_as_lm(m), counters)


def rollout_mla_attend_flops_bytes(m: dict):
    """(FLOPs, bytes) the `mla_attend` scope of ONE iteration's rollout needs, over every
    decode step and layer: the absorbed form's products of a step at position `t` over the
    `t + 1` rows written so far; `W_kvb` read once a step, those rows read once and one row
    written, a sequence each. float32."""
    steps, envs, layers = m["rollout_steps"], m["num_envs"], m["num_hidden_layers"]
    rows_read = steps * (steps + 1) / 2.0  # sum of t + 1 over the steps
    row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    flops = 2.0 * envs * layers * (steps * absorbed_macs(m, 0.0) + rows_read * (absorbed_macs(m, 1.0) - absorbed_macs(m, 0.0)))
    weights = m["kv_lora_rank"] * m["num_attention_heads"] * (m["qk_nope_head_dim"] + m["v_head_dim"])
    return flops, 4.0 * layers * (steps * weights + envs * row * (rows_read + steps))
