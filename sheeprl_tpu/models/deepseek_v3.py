"""The `deepseek_v3` trunk as a sequence-model policy (the DeepSeek-V3 block as
Moonlight-16B-A3B has it: RMSNorm, multi-head latent attention, then a dense SwiGLU in the
leading layers and a sparse expert layer with ungated shared experts in the rest), as pure
functions over a parameter tree, beside ``models/lfm2.py`` and ``models/qwen3_next.py`` and on
the same shared layers (``models/lm_layers.py``).

Multi-head latent attention (``q_lora_rank`` null: the query has no low-rank step), a token
``u``: ``q = W_q u``, a head's ``[q_nope, q_pe]``; ``[c, k_pe] = W_kva u``, ``c`` normed over
its ``kv_lora_rank`` channels; rotary embedding on every head's ``q_pe`` and on the ONE
``k_pe`` all heads share, unless the spec says ``mla_use_nope`` (NoPE, as Kimi-Linear's latent
attention layers have it, ``models/kimi_linear.py``: both go unrotated, in both forms and in
the cache's row, and a score does not depend on positions but through the causal mask);
``[k_nope_h, v_h] = W_kvb c`` a head; causal softmax of
``(q_nope_h . k_nope_h + q_pe_h . k_pe) / sqrt(nope + rope dims)``; ``W_o`` on the heads'
weighted values. It has two forms that agree to rounding:

- over whole sequences ``[B, T, H]`` (the loss's teacher-forced forward) the EXPANDED form:
  per-head keys and values are made from the latent, as published;
- one step ``[B, H]`` (the rollout) the ABSORBED form over a LATENT CACHE, the carry's state:
  a layer keeps, a token, the normed ``c`` and the (rotated) ``k_pe`` side by side,
  ``[B, S, kv_lora_rank + qk_rope_head_dim]``, and nothing per head. With ``W_kvb``'s columns
  of head ``h`` split into ``W_uk_h`` and ``W_uv_h``: ``score_h(s) = (W_uk_h^T q_nope_h) . c_s
  + q_pe_h . k_pe_s`` and ``o_h = W_uv_h (sum_s p_h(s) c_s)``: no key or value of a cached
  position is ever made. A step reads the rows written so far, a block at a time, and none past them.

The expert layer is `lm_layers.expert_layer` with this trunk's properties: sigmoid scores,
the top-k of ``s + b`` (``noaux_tc`` with one group), the normalised weights times
``routed_scaling_factor``, and ``n_shared_experts`` shared experts as ONE ungated SwiGLU of
``n_shared_experts x moe_intermediate_size``.

The parts carry ``jax.named_scope`` names (``embed``, ``mla`` with ``mla_attend`` inside it,
``router``, ``experts``, ``shared_expert``, ``dense_ffn``, ``lm_head``, ``value_head``), which
a profiler capture shows on each op and which change no program (names are metadata).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.models import lm_layers
from sheeprl_tpu.models.lm_layers import INIT_STD, default_frequencies, rms_core, rope, stack_routes, swiglu
from sheeprl_tpu.ops import latent_decode as decode_kernel

EXPERT_BIAS_STD = 0.05
CACHE_BLOCK = 128  # rows of the latent cache a decode step reads at a time (`cache_block`)


@dataclass(frozen=True)
class DeepseekV3Spec:
    """The sizes as run. ``num_hidden_layers`` counts the layers held, the first
    ``first_k_dense_replace`` of them with a dense feed-forward; ``experts_held`` is
    ``(first expert, count)`` of the ``num_experts`` the router scores; ``vocab_size`` is the
    slice of the vocabulary held."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: Tuple[int, int]
    n_shared_experts: int
    routed_scaling_factor: float
    norm_eps: float = 1e-5
    rope_theta: float = 5e4
    max_seq_len: int = 512
    # the expert layer's properties (`lm_layers.expert_layer`)
    router_scoring: str = "sigmoid_bias"
    shared_expert_gate: bool = False  # the shared experts are one ungated SwiGLU
    mla_use_nope: bool = False  # True: no rotary embedding on q_pe and k_pe (`_latent_inputs`)

    def __post_init__(self):
        e0, n = self.experts_held
        if not (0 <= e0 and n >= 1 and e0 + n <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held} is no range of the {self.num_experts} routed experts")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim = {self.qk_rope_head_dim} is no even number of channels")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts leading layers of the num_hidden_layers held")

    @property
    def shared_expert(self) -> bool:
        return self.n_shared_experts > 0

    @property
    def latent_width(self) -> int:
        """Floats a token a layer in the latent cache: the normed latent and the rotated shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def layers(self):
        return ["dense" if i < self.first_k_dense_replace else "moe" for i in range(self.num_hidden_layers)]

    @property
    def cache_bytes_per_sequence(self) -> int:
        """float32 bytes of the latent caches a sequence of ``max_seq_len`` positions holds."""
        return 4 * self.latent_width * self.max_seq_len * self.num_hidden_layers

    @classmethod
    def from_cfg(cls, lm: Any, vocab_size: int, max_seq_len: int) -> "DeepseekV3Spec":
        return cls(
            vocab_size=int(vocab_size), hidden_size=int(lm.hidden_size), intermediate_size=int(lm.intermediate_size),
            moe_intermediate_size=int(lm.moe_intermediate_size), num_attention_heads=int(lm.num_attention_heads),
            qk_nope_head_dim=int(lm.qk_nope_head_dim), qk_rope_head_dim=int(lm.qk_rope_head_dim),
            v_head_dim=int(lm.v_head_dim), kv_lora_rank=int(lm.kv_lora_rank),
            num_hidden_layers=int(lm.num_hidden_layers), first_k_dense_replace=int(lm.first_k_dense_replace),
            num_experts=int(lm.num_experts), num_experts_per_tok=int(lm.num_experts_per_tok),
            experts_held=(int(lm.experts_held[0]), int(lm.experts_held[1])), n_shared_experts=int(lm.n_shared_experts),
            routed_scaling_factor=float(lm.routed_scaling_factor), norm_eps=float(lm.norm_eps),
            rope_theta=float(lm.rope_theta), max_seq_len=int(max_seq_len),
        )


# ---------------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------------
def init_params(spec: DeepseekV3Spec, key: jax.Array) -> Dict[str, Any]:
    """N(0, 0.02) matrices, unit norm weights, the score-correction bias drawn once (a buffer:
    it is in the tree, chooses experts and gets no gradient)."""
    h, nh, r = spec.hidden_size, spec.num_attention_heads, spec.kv_lora_rank
    dn, dr, dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    count = [0]

    def normal(*shape, std=INIT_STD):
        count[0] += 1
        return std * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

    ones = partial(jnp.ones, dtype=jnp.float32)
    params: Dict[str, Any] = {"embed": normal(spec.vocab_size, h)}
    for i, ffn in enumerate(spec.layers):
        layer: Dict[str, Any] = {"op_norm": ones((h,)), "ffn_norm": ones((h,))}
        layer["op"] = {"wq": normal(h, nh * (dn + dr)), "w_kva": normal(h, r + dr), "kv_norm": ones((r,)),
                       "w_kvb": normal(r, nh * (dn + dv)), "wo": normal(nh * dv, h)}
        if ffn == "dense":
            f = spec.intermediate_size
            layer["ffn"] = {"w1": normal(h, f), "w3": normal(h, f), "w2": normal(f, h)}
        else:
            f, n = spec.moe_intermediate_size, spec.experts_held[1]
            layer["ffn"] = {"router": normal(h, spec.num_experts), "bias": normal(spec.num_experts, std=EXPERT_BIAS_STD),
                            "w1": normal(n, h, f), "w3": normal(n, h, f), "w2": normal(n, f, h)}
            if spec.shared_expert:
                fs = spec.n_shared_experts * f
                layer["ffn"]["shared"] = {"w1": normal(h, fs), "w3": normal(h, fs), "w2": normal(fs, h)}
        params[f"layer_{i}"] = layer
    params["norm"] = ones((h,))
    params["lm_head"] = normal(h, spec.vocab_size)
    params["value_head"] = normal(h, 1)
    return params


def init_carry(spec: DeepseekV3Spec, batch: int) -> Dict[str, Any]:
    """The state a fresh batch of sequences starts from: position 0, empty latent caches."""
    carry: Dict[str, Any] = {"t": jnp.zeros((), jnp.int32)}
    for i in range(spec.num_hidden_layers):
        carry[f"layer_{i}"] = jnp.zeros((batch, spec.max_seq_len, spec.latent_width), jnp.float32)
    return carry


# ---------------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------------
def rms_norm(x, weight, eps):
    return rms_core(x, eps) * weight


def _latent_inputs(p, u, positions, spec: DeepseekV3Spec):
    """``u`` ``[B, T, H]`` -> ``q_nope`` ``[B, T, heads, nope]``, ``q_pe`` ``[B, T, heads, rope]``
    (rotated), the normed latent ``c`` ``[B, T, rank]`` and the rotated key ``k_pe``
    ``[B, T, rope]`` that every head shares; where ``spec.mla_use_nope``, ``q_pe`` and ``k_pe``
    as projected."""
    bsz, t, _ = u.shape
    q = (u @ p["wq"]).reshape(bsz, t, spec.num_attention_heads, spec.qk_nope_head_dim + spec.qk_rope_head_dim)
    q_nope, q_pe = q[..., :spec.qk_nope_head_dim], q[..., spec.qk_nope_head_dim:]
    kva = u @ p["w_kva"]
    c = rms_norm(kva[..., :spec.kv_lora_rank], p["kv_norm"], spec.norm_eps)
    if spec.mla_use_nope:
        return q_nope, q_pe, c, kva[..., spec.kv_lora_rank:]
    turn = lambda x: rope(x, positions, default_frequencies(spec.rope_theta, spec.qk_rope_head_dim))  # noqa: E731
    k_pe = turn(kva[..., None, spec.kv_lora_rank:])[..., 0, :]
    return q_nope, turn(q_pe), c, k_pe


def _score_scale(spec: DeepseekV3Spec) -> float:
    return 1.0 / math.sqrt(spec.qk_nope_head_dim + spec.qk_rope_head_dim)


def mla(p, u, spec: DeepseekV3Spec):
    """Whole sequences ``[B, T, H]``, the expanded form: a head's keys and values from the latent."""
    bsz, t, _ = u.shape
    q_nope, q_pe, c, k_pe = _latent_inputs(p, u, jnp.arange(t), spec)
    kv = (c @ p["w_kvb"]).reshape(bsz, t, spec.num_attention_heads, spec.qk_nope_head_dim + spec.v_head_dim)
    k_nope, v = kv[..., :spec.qk_nope_head_dim], kv[..., spec.qk_nope_head_dim:]
    with jax.named_scope("mla_attend"):
        scores = jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope) + jnp.einsum("bqhr,bsr->bhqs", q_pe, k_pe)
        mask = jnp.tril(jnp.ones((t, t), bool))
        probs = jax.nn.softmax(jnp.where(mask, scores * _score_scale(spec), -jnp.inf), axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", probs, v)
    return out.reshape(bsz, t, -1) @ p["wo"]


def cache_block(positions: int) -> int:
    """Rows of the latent cache a decode step reads at a time: the largest divisor of the cache's
    ``positions`` that is at most `CACHE_BLOCK` (a lane tile of the chip: 128 of the cell's 512)."""
    return next(b for b in range(min(CACHE_BLOCK, positions), 0, -1) if positions % b == 0)


def decode_kernel_passes(cache_shape) -> int:
    """The bf16 passes the latent-cache kernel (`ops/latent_decode.py`) takes for a decode step
    over a cache of ``cache_shape`` in the program being traced (`lm_layers.matmul_passes`),
    or 0 where the step takes the XLA form: off the TPU, or a cache the kernel cannot chunk."""
    if jax.default_backend() != "tpu" or not decode_kernel.supports(cache_shape):
        return 0
    return lm_layers.matmul_passes()


def _attend_written(cache, t, row, query):
    """The XLA form of a step's attention over the latent cache: write ``row`` ``[B, W]`` at
    ``t``, then read the rows WRITTEN SO FAR a block at a time (as many blocks as ``t`` asks
    for: a loop whose trip count is the position's), keeping the running maximum, sum and
    weighted latents of a softmax of ``query`` ``[B, heads, W]`` over them. Rows past ``t``
    are never read as what they hold: the last block's count as 0."""
    cache = jax.lax.dynamic_update_slice_in_dim(cache, row[:, None], t, axis=1)
    block = cache_block(cache.shape[1])

    def one_block(i, so_far):
        most, total, weighed = so_far  # [B, heads], [B, heads], [B, heads, W]
        written = i * block + jnp.arange(block) <= t
        rows = jnp.where(written[None, :, None], jax.lax.dynamic_slice_in_dim(cache, i * block, block, axis=1), 0.0)
        scores = jnp.where(written[None, None], jnp.einsum("bhc,bsc->bhs", query, rows), -jnp.inf)
        new_most = jnp.maximum(most, scores.max(axis=-1))
        kept, weights = jnp.exp(most - new_most), jnp.exp(scores - new_most[..., None])
        return (new_most, total * kept + weights.sum(axis=-1),
                weighed * kept[..., None] + jnp.einsum("bhs,bsc->bhc", weights, rows))

    start = (jnp.full(query.shape[:2], -jnp.inf), jnp.zeros(query.shape[:2]), jnp.zeros(query.shape))
    _, total, weighed = jax.lax.fori_loop(0, t // block + 1, one_block, start)
    return weighed, total, cache


def mla_step(p, cache, u, t, spec: DeepseekV3Spec):
    """One step ``[B, H]`` at position ``t``, the absorbed form: carry the query into the
    latent's space, write this token's row ``[c, k_pe]`` into the latent cache
    ``[B, S, rank + rope]`` and attend over the rows written so far (on the TPU one kernel,
    `ops/latent_decode.py`, that writes the row in place and reads those rows once; else
    `_attend_written`), and only then apply a head's value map."""
    nh, dn, r = spec.num_attention_heads, spec.qk_nope_head_dim, spec.kv_lora_rank
    q_nope, q_pe, c, k_pe = _latent_inputs(p, u[:, None], t[None], spec)
    w_kvb = p["w_kvb"].reshape(r, nh, dn + spec.v_head_dim)
    with jax.named_scope("mla_attend"):
        row = jnp.concatenate([c, k_pe], axis=-1)[:, 0]
        q_latent = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_kvb[..., :dn])
        query = jnp.concatenate([q_latent, q_pe[:, 0]], axis=-1) * _score_scale(spec)
        passes = decode_kernel_passes(cache.shape)
        if passes:
            weighed, total, cache = decode_kernel.latent_decode(
                cache, t, row, query, passes, interpret=jax.default_backend() != "tpu")
        else:
            weighed, total, cache = _attend_written(cache, t, row, query)
        out = jnp.einsum("bhr,rhd->bhd", weighed[..., :r] / total[..., None], w_kvb[..., dn:])
    return out.reshape(out.shape[0], -1) @ p["wo"], cache


# -- the expert layer (`models/lm_layers.py`), under the names this trunk is known by -----
def route(p, u, spec: DeepseekV3Spec):
    """This trunk's router: sigmoid scores, the top-k of ``s + b``, the normalised weights
    times ``routed_scaling_factor`` (``spec.router_scoring``)."""
    return lm_layers.route(p, u, spec)


def expert_layer(p, u, spec: DeepseekV3Spec):
    """`lm_layers.expert_layer` behind this module's `route` (looked up when the layer is
    traced: a fault planted under that name is the router the layer takes)."""
    return lm_layers.expert_layer(p, u, spec, route)


# ---------------------------------------------------------------------------------
# the trunk
# ---------------------------------------------------------------------------------
def _ffn(p, u, ffn: str, spec: DeepseekV3Spec):
    """``u`` ``[N, H]`` -> (output, chosen ids or None, counters or None)."""
    if ffn == "dense":
        with jax.named_scope("dense_ffn"):
            return swiglu(p, u), None, None
    return expert_layer(p, u, spec)


def heads(params, x, spec: DeepseekV3Spec):
    return lm_layers.heads(params, rms_norm(x, params["norm"], spec.norm_eps))


def forward(params, spec: DeepseekV3Spec, tokens):
    """Whole sequences ``tokens`` ``[B, T]`` -> logits ``[B, T, V]``, values ``[B, T]``, the
    chosen experts ``[B, T, expert layers, k]`` and the layers' counters. Each block is
    recomputed in a backward pass (``jax.checkpoint``): a gradient step keeps one block's
    activations, not every block's."""
    bsz, t = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes = []
    for i, ffn in enumerate(spec.layers):

        def block(p, x, ffn=ffn):
            with jax.named_scope("mla"):
                x = x + mla(p["op"], rms_norm(x, p["op_norm"], spec.norm_eps), spec)
            u = rms_norm(x, p["ffn_norm"], spec.norm_eps).reshape(bsz * t, -1)
            y, ids, counters = _ffn(p["ffn"], u, ffn, spec)
            return x + y.reshape(bsz, t, -1), ids, counters

        x, ids, counters = jax.checkpoint(block)(params[f"layer_{i}"], x)
        if ids is not None:
            routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    return logits, value, None if ids is None else ids.reshape(bsz, t, *ids.shape[1:]), counters


def step(params, spec: DeepseekV3Spec, carry, tokens):
    """One token a sequence, ``tokens`` ``[B]``, through the latent caches -> logits
    ``[B, V]``, values ``[B]``, the new carry, the chosen experts ``[B, expert layers, k]``
    and the layers' counters, with ``mla/decode_kernel_share``: the share of the layers
    whose attention took the latent-cache kernel (`decode_kernel_passes`)."""
    t = carry["t"]
    new_carry: Dict[str, Any] = {"t": t + 1}
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes = []
    for i, ffn in enumerate(spec.layers):
        p, name = params[f"layer_{i}"], f"layer_{i}"
        with jax.named_scope("mla"):
            y, new_carry[name] = mla_step(p["op"], carry[name], rms_norm(x, p["op_norm"], spec.norm_eps), t, spec)
        x = x + y
        y, ids, counters = _ffn(p["ffn"], rms_norm(x, p["ffn_norm"], spec.norm_eps), ffn, spec)
        x = x + y
        if ids is not None:
            routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    if counters is not None:  # fixed when traced: the share of the layers whose step took the kernel
        kernel = [bool(decode_kernel_passes(carry[f"layer_{i}"].shape)) for i in range(spec.num_hidden_layers)]
        counters["mla/decode_kernel_share"] = jnp.float32(sum(kernel) / len(kernel))
    return logits, value, new_carry, ids, counters


def parameter_count(spec: DeepseekV3Spec) -> int:
    return lm_layers.parameter_count(init_params, spec)
