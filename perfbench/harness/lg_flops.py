"""Model FLOPs and bytes of the sequence-policy PPO iteration on the `laguna` trunk
(`adapters/ppo_anakin_laguna.py`), from the configuration's `model` block and the pairs the program
counted on its held experts; never from the program's buffers, so that a roofline reads the same
work whatever implements it. Matrix products only (2 FLOPs a multiply-add); a backward pass counts
as two forwards; recomputation and the keys a block reads beyond its mask are not counted. An
attention layer counts, a query, the keys its mask lets it read: every earlier position and itself
on a full layer, ``min(i + 1, sliding_window)`` on a sliding one, in the rollout's ring as in the
update's band. Used by `train_step_mfu` (through the adapter's `step_flops`),
`lg_swa_attend_roofline_share` and `lg_experts_roofline_share`."""

from __future__ import annotations

from typing import Optional

from perfbench.harness import lm_flops

pair_macs = lm_flops.pair_macs


def _as_lm(m: dict) -> dict:
    """The block under the names `lm_flops` counts expert layers by."""
    return {**m, "num_dense_layers": m["mlp_layer_types"].count("dense")}


def counted_pairs(m: dict, counters: Optional[dict]):
    return lm_flops.counted_pairs(_as_lm(m), counters)


def keys_read(m: dict, kind: str) -> float:
    """Keys a query reads, in the mean over an episode's positions, in a layer of `kind`."""
    t = m["rollout_steps"]
    if kind == "full_attention":
        return (t + 1) / 2.0
    w = min(m["sliding_window"], t)
    return (w * (w + 1) / 2.0 + (t - w) * w) / t


def attention_macs(m: dict, kind: str, heads: int) -> float:
    """Multiply-adds a token of one attention layer: `W_q`, `W_k`, `W_v`, the gate's `W_g`, `W_o`,
    and the scores and the weighted values over the keys its mask lets the query read."""
    h, d, nkv = m["hidden_size"], m["head_dim"], m["num_key_value_heads"]
    return h * heads * d + 2 * h * nkv * d + h * heads + heads * d * h + 2 * heads * d * keys_read(m, kind)


def token_macs(m: dict) -> float:
    """Multiply-adds of one token's forward outside the routed experts."""
    h = m["hidden_size"]
    macs = h * m["vocab_size"] + h
    for kind, ffn, heads in zip(m["layer_types"], m["mlp_layer_types"], m["num_attention_heads_per_layer"]):
        macs += attention_macs(m, kind, heads)
        if ffn == "dense":
            macs += 3 * h * m["intermediate_size"]
        else:
            macs += h * m["num_experts_routed"] + 3 * h * m["shared_expert_intermediate_size"]
    return macs


def iteration_flops(m: dict, counters: Optional[dict] = None) -> float:
    """One whole iteration: the rollout's forward, one token a step, and the update's forward and
    backward over every sequence `update_epochs` times."""
    tokens = m["rollout_steps"] * m["num_envs"]
    rollout_pairs, update_pairs = counted_pairs(m, counters)
    forward = 2.0 * (tokens * token_macs(m) + rollout_pairs * pair_macs(m))
    update = 3 * 2.0 * (tokens * m["update_epochs"] * token_macs(m) + update_pairs * pair_macs(m))
    return forward + update


def update_experts_flops_bytes(m: dict, counters: Optional[dict] = None):
    """(FLOPs, bytes) the `experts` scope of ONE iteration's update needs: `lm_flops`'s count
    (the grouped products over the counted pairs; the held weights read twice and their
    gradient written, each pair's rows in and out), over this trunk's expert layers."""
    return lm_flops.update_experts_flops_bytes(_as_lm(m), counters)


def swa_attend_flops_bytes(m: dict):
    """(FLOPs, bytes) the `swa_attend` scope of ONE iteration needs, rollout and update, over the
    sliding layers: a query's scores and weighted values over the keys of its window (2 x 2 x keys x
    heads x head_dim FLOPs), forward in the rollout and forward and twice that backward in the update.
    Bytes, float32: a decode step reads its query, the window's keys and values from the ring, writes
    its key and value and its output; the update reads a token's query, key and value and writes its
    output once, forward, and twice that backward (the cotangents in, the gradients out)."""
    tokens = m["rollout_steps"] * m["num_envs"]
    d, nkv, keys = m["head_dim"], m["num_key_value_heads"], keys_read(m, "sliding_attention")
    flops = nbytes = 0.0
    for kind, heads in zip(m["layer_types"], m["num_attention_heads_per_layer"]):
        if kind != "sliding_attention":
            continue
        per_query = 2 * 2.0 * keys * heads * d
        flops += tokens * per_query * (1 + 3 * m["update_epochs"])
        decode = 4.0 * (2 * heads * d + 2 * keys * nkv * d + 2 * nkv * d)
        update = 4.0 * (2 * heads * d + 2 * nkv * d)
        nbytes += tokens * (decode + 3 * m["update_epochs"] * update)
    return flops, nbytes
