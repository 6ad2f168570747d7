"""Model FLOPs and bytes of the sequence-policy PPO iteration on the `qwen3_next` trunk
(`adapters/ppo_anakin_qwen3_next.py`), from the configuration's `model` block and the pairs
the program counted on its held experts. Matrix products only (2 FLOPs a multiply-add); a
backward pass counts as two forwards; recomputation, chunking and padding are not counted.
The gated delta rule counts by its RECURRENT form's three products a token a value head
(`S^T k`, the rank-one write, `S^T q`: `key dim x value dim` multiply-adds each), so that
the roofline reads the same work whatever implements it. Used by `train_step_mfu` (through
the adapter's `step_flops`), `q3n_delta_rule_roofline_share` and `q3n_experts_roofline_share`."""

from __future__ import annotations

from typing import Optional

from perfbench.harness import lm_flops

pair_macs, counted_pairs = lm_flops.pair_macs, lm_flops.counted_pairs


def widths(m: dict):
    return m["linear_num_key_heads"] * m["linear_key_head_dim"], m["linear_num_value_heads"] * m["linear_value_head_dim"]


def delta_rule_macs(m: dict) -> float:
    """Multiply-adds of one token's delta rule in one linear-attention layer."""
    return 3.0 * m["linear_num_value_heads"] * m["linear_key_head_dim"] * m["linear_value_head_dim"]


def linear_layers(m: dict) -> int:
    return sum(op == "linear_attention" for op in m["layer_types"])


def token_macs(m: dict, context: float) -> float:
    """Multiply-adds of one token's forward outside the routed experts, attending over `context` keys."""
    h, d = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    key_width, value_width = widths(m)
    macs = 0.0
    for op in m["layer_types"]:
        if op == "linear_attention":
            macs += h * (2 * key_width + 2 * value_width) + h * 2 * m["linear_num_value_heads"]
            macs += m["linear_conv_kernel_dim"] * (2 * key_width + value_width) + delta_rule_macs(m) + value_width * h
        else:
            macs += h * nq * d * 2 + 2 * h * nkv * d + nq * d * h + 2 * nq * d * context
        macs += h * m["num_experts_routed"] + 3 * h * m["shared_expert_intermediate_size"] + h
    return macs + h * m["vocab_size"] + h


def iteration_flops(m: dict, counters: Optional[dict] = None) -> float:
    """One whole iteration: the rollout's forward, one token a step over a growing cache,
    and the update's forward and backward over every sequence `update_epochs` times."""
    tokens = m["rollout_steps"] * m["num_envs"]
    dense = token_macs(m, context=(m["rollout_steps"] + 1) / 2.0)
    rollout_pairs, update_pairs = counted_pairs({**m, "num_dense_layers": 0}, counters)
    forward = 2.0 * (tokens * dense + rollout_pairs * pair_macs(m))
    update = 3 * 2.0 * (tokens * m["update_epochs"] * dense + update_pairs * pair_macs(m))
    return forward + update


def update_experts_flops_bytes(m: dict, counters: Optional[dict] = None):
    """(FLOPs, bytes) the `experts` scope of ONE iteration's update needs: `lm_flops`'s count
    (the grouped products over the counted pairs; the held weights read twice and their
    gradient written, each pair's rows in and out), every layer an expert layer."""
    return lm_flops.update_experts_flops_bytes({**m, "num_dense_layers": 0}, counters)


def update_delta_rule_flops_bytes(m: dict):
    """(FLOPs, bytes) the `delta_rule` scope of ONE iteration's update needs: the three
    products a token a value head, forward and twice that backward; a token's q and k (a key
    head each), v, g and beta in and its output out, forward, and twice that backward (the
    cotangents in, the gradients out). The state itself need never leave the chip. float32."""
    tokens = m["rollout_steps"] * m["num_envs"] * m["update_epochs"] * linear_layers(m)
    key_width, value_width = widths(m)
    moved = 4.0 * (2 * key_width + 2 * value_width + 2 * m["linear_num_value_heads"])
    return 3 * 2.0 * tokens * delta_rule_macs(m), 3 * moved * tokens
