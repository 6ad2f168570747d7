"""Model FLOPs and bytes of the sequence-policy PPO iteration (`adapters/ppo_anakin_lm.py`),
from the configuration's `model` block and the pairs the program counted on its held
experts. Matrix products only (2 FLOPs a multiply-add); a backward pass counts as two
forwards; recomputation is not counted. Used by `train_step_mfu` (through the adapter's
`step_flops`) and by `moe_experts_roofline_share`."""

from __future__ import annotations

from typing import Optional


def token_macs(m: dict, context: float) -> float:
    """Multiply-adds of one token's forward outside the experts, attending over `context` keys."""
    h, d = m["hidden_size"], m["head_dim"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    macs = 0.0
    for i, op in enumerate(m["layer_types"]):
        if op == "conv":
            macs += 3 * h * h + h * h + m["conv_L_cache"] * h
        else:
            macs += h * nq * d + 2 * h * nkv * d + nq * d * h + 2 * nq * d * context
        macs += 3 * h * m["intermediate_size"] if i < m["num_dense_layers"] else h * m["num_experts_routed"]
    return macs + h * m["vocab_size"] + h


def pair_macs(m: dict) -> float:
    """Multiply-adds of one (token, held expert) pair: a SwiGLU of the expert width."""
    return 3.0 * m["hidden_size"] * m["moe_intermediate_size"]


def expected_pairs(m: dict, tokens: float) -> float:
    """Pairs on held experts over all expert layers for `tokens` tokens under uniform routing."""
    layers = len(m["layer_types"]) - m["num_dense_layers"]
    return tokens * layers * m["num_experts_per_tok"] * m["experts_held"][1] / m["num_experts_routed"]


def counted_pairs(m: dict, counters: Optional[dict]):
    """(pairs in the rollout, pairs in the update's gradient steps) of one iteration, summed
    over the expert layers: the program's counters are means a decode step and a gradient
    step; without them, the expectation."""
    steps, envs = m["rollout_steps"], m["num_envs"]
    updates = m["update_epochs"] * (envs // m["minibatch_sequences"])
    if counters and "rollout_pairs_held" in counters and "update_pairs_held" in counters:
        return counters["rollout_pairs_held"] * steps, counters["update_pairs_held"] * updates
    tokens = steps * envs
    return expected_pairs(m, tokens), expected_pairs(m, tokens * m["update_epochs"])


def iteration_flops(m: dict, counters: Optional[dict] = None) -> float:
    """One whole iteration: the rollout's forward, one token a step over a growing cache,
    and the update's forward and backward over every sequence `update_epochs` times."""
    tokens = m["rollout_steps"] * m["num_envs"]
    dense = token_macs(m, context=(m["rollout_steps"] + 1) / 2.0)
    rollout_pairs, update_pairs = counted_pairs(m, counters)
    forward = 2.0 * (tokens * dense + rollout_pairs * pair_macs(m))
    update = 3 * 2.0 * (tokens * m["update_epochs"] * dense + update_pairs * pair_macs(m))
    return forward + update


def update_experts_flops_bytes(m: dict, counters: Optional[dict] = None):
    """(FLOPs, bytes) the `experts` scope of ONE iteration's update needs: the grouped
    products' forward and backward over the counted pairs; the held weights read in the
    forward, read in the backward and their gradient written, in every expert layer of
    every gradient step; each pair's rows in and out of the three products, forward and
    twice that backward. float32."""
    _, pairs = counted_pairs(m, counters)
    layers = len(m["layer_types"]) - m["num_dense_layers"]
    updates = m["update_epochs"] * (m["num_envs"] // m["minibatch_sequences"])
    flops = 3 * 2.0 * pairs * pair_macs(m)
    weights = 4.0 * 3 * m["experts_held"][1] * m["hidden_size"] * m["moe_intermediate_size"]
    rows = 4.0 * (2 * m["hidden_size"] + 3 * m["moe_intermediate_size"])
    return flops, 3 * weights * layers * updates + 3 * rows * pairs
