"""The `lfm2_moe` trunk as a sequence-model policy (LFM2-8B-A1B's block: RMSNorm, a gated
short convolution or grouped-query attention with per-head q/k norms and RoPE, then a
SwiGLU feed-forward or a sparse expert layer), as pure functions over a parameter tree.

Every layer has two forms: over whole sequences ``[B, T, H]`` (the loss's teacher-forced
forward) and one step ``[B, H]`` that carries state (the rollout). The carry holds two
kinds of per-sequence state side by side: a KV cache per attention layer and the last
``conv_L_cache`` columns of ``B * z`` per convolution layer.

The expert layer is TOLD which experts it holds (``LFM2Spec.experts_held = (e0, n)``):
it routes over all ``num_experts`` (sigmoid scores, top-k of ``s + b``, weights ``s``
over their sum), and computes the part of the result its own experts give, by one sort
of the (token, expert) pairs and grouped matrix products over the experts held. What
absent experts would add is left out (that is another chip's part; on one chip the layer
runs without its exchange). No token is dropped and there is no capacity: the pairs'
buffers are bounded by the share held and further rounds compute what lands beyond them
(`lm_layers._experts_bounded`), and the grouped products visit only the rows in use;
a decode step's few tokens skip the sort and go through every held expert (`DENSE_TOKENS`).
The layer itself, with the norm's core, RoPE, SwiGLU, attention and the heads, is
``models/lm_layers.py``'s, which the ``qwen3_next`` trunk shares; this module keeps the
names it is known by (`route`, `expert_layer`, `grouped_matmul`, ...).

The parts carry ``jax.named_scope`` names (``embed``, ``short_conv``, ``attention``,
``router``, ``experts``, ``dense_ffn``, ``lm_head``, ``value_head``), which a profiler
capture shows on each op and which change no program (names are metadata).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.models import lm_layers
from sheeprl_tpu.models.lm_layers import (  # noqa: F401  (the names this trunk's tests and callers know it by)
    DENSE_TOKENS, INIT_STD, WEIGHT_SUM_EPS, _gmm_tpu, _warn_dense_groups, grouped_matmul, kernel_passes,
    attend, default_frequencies, matmul_passes, rms_core, rope, stack_routes, swiglu, tile_fill,
)

EXPERT_BIAS_STD = 0.05


@dataclass(frozen=True)
class LFM2Spec:
    """The sizes as run. ``layer_types`` lists the layers held (``conv`` /
    ``full_attention``), the first ``num_dense_layers`` of them with a dense feed-forward;
    ``experts_held`` is ``(first expert, count)`` of the ``num_experts`` the router scores;
    ``vocab_size`` is the slice of the vocabulary held."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: Tuple[int, int]
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_seq_len: int = 256
    # the expert layer's properties (`lm_layers.expert_layer`): LFM2's router, no shared expert
    router_scoring: str = "sigmoid_bias"
    shared_expert: bool = False

    def __post_init__(self):
        e0, n = self.experts_held
        if not (0 <= e0 and n >= 1 and e0 + n <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held} is no range of the {self.num_experts} routed experts")
        if self.hidden_size % self.num_attention_heads or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide the hidden size, and key/value heads the query heads")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layers(self):
        return [(op, "dense" if i < self.num_dense_layers else "moe") for i, op in enumerate(self.layer_types)]

    @property
    def num_moe_layers(self) -> int:
        return sum(ffn == "moe" for _, ffn in self.layers)

    @classmethod
    def from_cfg(cls, lm: Any, vocab_size: int, max_seq_len: int) -> "LFM2Spec":
        return cls(
            vocab_size=int(vocab_size), hidden_size=int(lm.hidden_size), intermediate_size=int(lm.intermediate_size),
            moe_intermediate_size=int(lm.moe_intermediate_size), num_attention_heads=int(lm.num_attention_heads),
            num_key_value_heads=int(lm.num_key_value_heads), layer_types=tuple(str(t) for t in lm.layer_types),
            num_dense_layers=int(lm.num_dense_layers), num_experts=int(lm.num_experts),
            num_experts_per_tok=int(lm.num_experts_per_tok),
            experts_held=(int(lm.experts_held[0]), int(lm.experts_held[1])), conv_L_cache=int(lm.conv_L_cache),
            norm_eps=float(lm.norm_eps), rope_theta=float(lm.rope_theta), max_seq_len=int(max_seq_len),
        )


# ---------------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------------
def init_params(spec: LFM2Spec, key: jax.Array) -> Dict[str, Any]:
    """N(0, 0.02) matrices, unit norm weights, the expert bias drawn once (a buffer: it is
    in the tree, chooses experts and gets no gradient)."""
    h, d = spec.hidden_size, spec.head_dim
    nq, nkv = spec.num_attention_heads, spec.num_key_value_heads
    count = [0]

    def normal(*shape, std=INIT_STD):
        count[0] += 1
        return std * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

    ones = partial(jnp.ones, dtype=jnp.float32)
    params: Dict[str, Any] = {"embed": normal(spec.vocab_size, h)}
    for i, (op, ffn) in enumerate(spec.layers):
        layer: Dict[str, Any] = {"op_norm": ones((h,)), "ffn_norm": ones((h,))}
        if op == "conv":
            layer["op"] = {"w_in": normal(h, 3 * h), "w_conv": normal(spec.conv_L_cache, h, std=0.3), "w_out": normal(h, h)}
        else:
            layer["op"] = {"wq": normal(h, nq * d), "wk": normal(h, nkv * d), "wv": normal(h, nkv * d),
                           "wo": normal(nq * d, h), "q_norm": ones((d,)), "k_norm": ones((d,))}
        if ffn == "dense":
            f = spec.intermediate_size
            layer["ffn"] = {"w1": normal(h, f), "w3": normal(h, f), "w2": normal(f, h)}
        else:
            f, n = spec.moe_intermediate_size, spec.experts_held[1]
            layer["ffn"] = {"router": normal(h, spec.num_experts), "bias": normal(spec.num_experts, std=EXPERT_BIAS_STD),
                            "w1": normal(n, h, f), "w3": normal(n, h, f), "w2": normal(n, f, h)}
        params[f"layer_{i}"] = layer
    params["norm"] = ones((h,))
    params["lm_head"] = normal(h, spec.vocab_size)
    params["value_head"] = normal(h, 1)
    return params


def init_carry(spec: LFM2Spec, batch: int) -> Dict[str, Any]:
    """The state a fresh batch of sequences starts from: position 0, empty caches."""
    carry: Dict[str, Any] = {"t": jnp.zeros((), jnp.int32)}
    for i, (op, _) in enumerate(spec.layers):
        if op == "conv":
            carry[f"layer_{i}"] = jnp.zeros((batch, spec.conv_L_cache, spec.hidden_size), jnp.float32)
        else:
            kv = (batch, spec.max_seq_len, spec.num_key_value_heads, spec.head_dim)
            carry[f"layer_{i}"] = (jnp.zeros(kv, jnp.float32), jnp.zeros(kv, jnp.float32))
    return carry


# ---------------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------------
def rms_norm(x, weight, eps):
    return rms_core(x, eps) * weight


def short_conv(p, u):
    """Whole sequences ``[B, T, H]``: ``C * causal_depthwise_conv1d(B * z)``, no bias; tap
    ``j`` multiplies the input ``K - 1 - j`` steps back."""
    b, c, z = jnp.split(u @ p["w_in"], 3, axis=-1)
    bz = b * z
    taps = p["w_conv"].shape[0]
    padded = jnp.pad(bz, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + bz.shape[1]] * p["w_conv"][j] for j in range(taps))
    return (c * y) @ p["w_out"]


def short_conv_step(p, state, u):
    """One step ``[B, H]``; ``state`` is the last ``K`` columns of ``B * z``, this step's last."""
    b, c, z = jnp.split(u @ p["w_in"], 3, axis=-1)
    state = jnp.concatenate([state[:, 1:], (b * z)[:, None]], axis=1)
    y = jnp.sum(state * p["w_conv"][None], axis=1)
    return (c * y) @ p["w_out"], state


def _qkv(p, u, positions, spec: LFM2Spec):
    """``u`` ``[B, T, H]`` -> q ``[B, T, nq, d]``, k and v ``[B, T, nkv, d]``, normed and rotated."""
    bsz, t, _ = u.shape
    nq, nkv, d = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    q = rms_norm((u @ p["wq"]).reshape(bsz, t, nq, d), p["q_norm"], spec.norm_eps)
    k = rms_norm((u @ p["wk"]).reshape(bsz, t, nkv, d), p["k_norm"], spec.norm_eps)
    v = (u @ p["wv"]).reshape(bsz, t, nkv, d)
    turn = lambda x: rope(x, positions, default_frequencies(spec.rope_theta, d))  # noqa: E731
    return turn(q), turn(k), v


def attention(p, u, spec: LFM2Spec):
    t = u.shape[1]
    q, k, v = _qkv(p, u, jnp.arange(t), spec)
    return attend(q, k, v, jnp.tril(jnp.ones((t, t), bool)), spec.num_key_value_heads) @ p["wo"]


def attention_step(p, cache, u, t, spec: LFM2Spec):
    """One step ``[B, H]`` at position ``t``: write this step's key and value into the
    cache ``[B, S, nkv, d]``, attend over the positions up to ``t``. The cache's step is
    `lm_layers.kv_cache_step`, which the full-attention layers of the `laguna` trunk take
    too."""
    q, k, v = _qkv(p, u[:, None], t[None], spec)
    out, cache = lm_layers.kv_cache_step(cache, q, k, v, t, spec.num_key_value_heads)
    return out[:, 0] @ p["wo"], cache


# -- the expert layer (`models/lm_layers.py`), under the names this trunk is known by -----
def route(p, u, spec: LFM2Spec):
    """LFM2's router: sigmoid scores, the top-k of ``s + b`` (``spec.router_scoring``)."""
    return lm_layers.route(p, u, spec)


def expert_layer(p, u, spec: LFM2Spec):
    """`lm_layers.expert_layer` behind this module's `route` (looked up when the layer is
    traced: a fault planted under that name is the router the layer takes)."""
    return lm_layers.expert_layer(p, u, spec, route)


# ---------------------------------------------------------------------------------
# the trunk
# ---------------------------------------------------------------------------------
def _ffn(p, u, ffn: str, spec: LFM2Spec):
    """``u`` ``[N, H]`` -> (output, chosen ids or None, counters or None)."""
    if ffn == "dense":
        with jax.named_scope("dense_ffn"):
            return swiglu(p, u), None, None
    return expert_layer(p, u, spec)


def heads(params, x, spec: LFM2Spec):
    return lm_layers.heads(params, rms_norm(x, params["norm"], spec.norm_eps))


def forward(params, spec: LFM2Spec, tokens):
    """Whole sequences ``tokens`` ``[B, T]`` -> logits ``[B, T, V]``, values ``[B, T]``, the
    chosen experts ``[B, T, expert layers, k]`` and the layers' counters. Each block is
    recomputed in a backward pass (``jax.checkpoint``): a gradient step keeps one block's
    activations, not every block's."""
    bsz, t = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes = []
    for i, (op, ffn) in enumerate(spec.layers):

        def block(p, x, op=op, ffn=ffn):
            u = rms_norm(x, p["op_norm"], spec.norm_eps)
            if op == "conv":
                with jax.named_scope("short_conv"):
                    x = x + short_conv(p["op"], u)
            else:
                with jax.named_scope("attention"):
                    x = x + attention(p["op"], u, spec)
            u = rms_norm(x, p["ffn_norm"], spec.norm_eps).reshape(bsz * t, -1)
            y, ids, counters = _ffn(p["ffn"], u, ffn, spec)
            return x + y.reshape(bsz, t, -1), ids, counters

        x, ids, counters = jax.checkpoint(block)(params[f"layer_{i}"], x)
        if ids is not None:
            routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    return logits, value, None if ids is None else ids.reshape(bsz, t, *ids.shape[1:]), counters


def step(params, spec: LFM2Spec, carry, tokens):
    """One token a sequence, ``tokens`` ``[B]``, through the carried state -> logits
    ``[B, V]``, values ``[B]``, the new carry, the chosen experts ``[B, expert layers, k]``
    and the layers' counters."""
    t = carry["t"]
    new_carry: Dict[str, Any] = {"t": t + 1}
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes = []
    for i, (op, ffn) in enumerate(spec.layers):
        p, name = params[f"layer_{i}"], f"layer_{i}"
        u = rms_norm(x, p["op_norm"], spec.norm_eps)
        if op == "conv":
            with jax.named_scope("short_conv"):
                y, new_carry[name] = short_conv_step(p["op"], carry[name], u)
        else:
            with jax.named_scope("attention"):
                y, new_carry[name] = attention_step(p["op"], carry[name], u, t, spec)
        x = x + y
        y, ids, counters = _ffn(p["ffn"], rms_norm(x, p["ffn_norm"], spec.norm_eps), ffn, spec)
        x = x + y
        if ids is not None:
            routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    return logits, value, new_carry, ids, counters


def parameter_count(spec: LFM2Spec) -> int:
    return lm_layers.parameter_count(init_params, spec)

