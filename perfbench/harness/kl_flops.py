"""Model FLOPs and bytes of the sequence-policy PPO iteration on the `kimi_linear` trunk
(`adapters/ppo_anakin_kimi_linear.py`), from the configuration's `model` block and the pairs the
program counted on its held experts; never from the program's buffers, so that a roofline reads
the same work whatever implements it. Matrix products only (2 FLOPs a multiply-add, and the short
convolutions' taps); a backward pass counts as two forwards; recomputation, chunking and padding
are not counted. Kimi delta attention counts by its RECURRENT form's three products a token a
head (`S^T k`, the rank-one write, `S^T q`: `dk x dk` multiply-adds each), as
`q3n_flops.py` counts the scalar rule; the latent attention as `dsv3_flops.py` counts it (the
update by the expanded form, a decode step by the absorbed form over the rows written so far).
Used by `train_step_mfu` (through the adapter's `step_flops`), `kl_kda_rule_roofline_share` and
`kl_experts_roofline_share`."""

from __future__ import annotations

from typing import Optional

from perfbench.harness import dsv3_flops, lm_flops

pair_macs = lm_flops.pair_macs


def _as_lm(m: dict) -> dict:
    """The block under the names `lm_flops` counts expert layers by."""
    return {**m, "layer_types": [None] * m["num_hidden_layers"], "num_dense_layers": m["first_k_dense_replace"]}


def counted_pairs(m: dict, counters: Optional[dict]):
    return lm_flops.counted_pairs(_as_lm(m), counters)


def kda_layers(m: dict) -> int:
    return len(m["kda_layers"])


def mla_layers(m: dict) -> int:
    return len(m["full_attn_layers"])


def linear_width(m: dict) -> int:
    return m["linear_num_heads"] * m["linear_head_dim"]


def kda_rule_macs(m: dict) -> float:
    """Multiply-adds of one token's rule in one KDA layer: three products a head."""
    return 3.0 * m["linear_num_heads"] * m["linear_head_dim"] ** 2


def kda_macs(m: dict) -> float:
    """Multiply-adds a token of one KDA layer: `W_q`, `W_k`, `W_v`, the three convolutions'
    taps, the decay's and the gate's low-rank pairs, `W_b`, the rule and `W_o`."""
    h, dk, width = m["hidden_size"], m["linear_head_dim"], linear_width(m)
    low_rank = 2 * (h * dk + dk * width)
    return 3 * h * width + 3 * m["short_conv_kernel_size"] * width + low_rank + h * m["linear_num_heads"] \
        + kda_rule_macs(m) + width * h


def ffn_macs(m: dict) -> float:
    """Multiply-adds a token of every layer's feed-forward outside the routed experts: the
    leading dense layers, then the router and the shared expert's one SwiGLU."""
    h, dense = m["hidden_size"], m["first_k_dense_replace"]
    moe = h * m["num_experts_routed"] + 3 * h * m["num_shared_experts"] * m["moe_intermediate_size"]
    return dense * 3 * h * m["intermediate_size"] + (m["num_hidden_layers"] - dense) * moe


def head_macs(m: dict) -> float:
    return m["hidden_size"] * m["vocab_size"] + m["hidden_size"]


def iteration_flops(m: dict, counters: Optional[dict] = None) -> float:
    """One whole iteration: the rollout's forward, one token a step (the latent attention in the
    absorbed form over the rows written so far), and the update's forward and backward over every
    sequence `update_epochs` times (the latent attention expanded)."""
    tokens, mla = m["rollout_steps"] * m["num_envs"], mla_layers(m)
    mean_context = (m["rollout_steps"] + 1) / 2.0
    shared = kda_layers(m) * kda_macs(m) + mla * dsv3_flops.projection_macs(m) + ffn_macs(m) + head_macs(m)
    rollout_pairs, update_pairs = counted_pairs(m, counters)
    forward = 2.0 * (tokens * (shared + mla * dsv3_flops.absorbed_macs(m, mean_context)) + rollout_pairs * pair_macs(m))
    update = 3 * 2.0 * (tokens * m["update_epochs"] * (shared + mla * dsv3_flops.expanded_macs(m, mean_context))
                        + update_pairs * pair_macs(m))
    return forward + update


def update_experts_flops_bytes(m: dict, counters: Optional[dict] = None):
    """(FLOPs, bytes) the `experts` scope of ONE iteration's update needs: `lm_flops`'s count
    (the grouped products over the counted pairs; the held weights read twice and their
    gradient written, each pair's rows in and out), over this trunk's expert layers."""
    return lm_flops.update_experts_flops_bytes(_as_lm(m), counters)


def update_kda_rule_flops_bytes(m: dict):
    """(FLOPs, bytes) the `kda_rule` scope of ONE iteration's update needs: the three products a
    token a head, forward and twice that backward; a token's q, k and g (a key-width vector
    each), v and beta in and its output out, forward, and twice that backward (the cotangents in,
    the gradients out). The state itself need never leave the chip. float32."""
    tokens = m["rollout_steps"] * m["num_envs"] * m["update_epochs"] * kda_layers(m)
    moved = 4.0 * (5 * linear_width(m) + m["linear_num_heads"])
    return 3 * 2.0 * tokens * kda_rule_macs(m), 3 * moved * tokens
