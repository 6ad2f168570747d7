"""The `kimi_linear` trunk as a sequence-model policy (Kimi-Linear-48B-A3B's block: RMSNorm, Kimi
delta attention (KDA) or NoPE multi-head latent attention by the layer's kind, then a dense SwiGLU
in the leading layers and a sparse expert layer with an ungated shared expert in the rest), as pure
functions over a parameter tree, beside ``models/qwen3_next.py`` and ``models/deepseek_v3.py``,
whose parts it composes, and on the shared layers (``models/lm_layers.py``).

The layer kinds come from two 1-indexed lists, as published (``kda_layers``,
``full_attn_layers``); the first ``first_k_dense_replace`` layers have the dense feed-forward.

Kimi delta attention, a token ``u`` and a head: ``q, k = L2Norm(SiLU(Conv(W_q u))),
L2Norm(SiLU(Conv(W_k u)))``, ``v = SiLU(Conv(W_v u))`` (three causal depthwise convolutions of
``short_conv_kernel_size`` taps); the decay, a value a KEY CHANNEL, ``g = -exp(A_log[h]) *
softplus(W_f_up W_f_down u + dt_bias)``; ``beta = sigmoid(W_b u)``; with ``S_0 = 0``:
``S <- Diag(exp(g_t)) S``; ``r = S^T k_t``; ``S <- S + k_t (beta_t (v_t - r))^T``;
``o_t = S^T q_t / sqrt(dk)``; out ``W_o (RMSNorm_head(o) * sigmoid(W_g_up W_g_down u + b_g))``.
It has two forms that agree: one step (`kda_step`: the rule's lines, on the TPU the delta-rule
decode kernel, `ops/delta_rule_decode.py`, with its decay a key channel) and whole sequences
(`chunk_kda`: chunked, exact, differentiated through; on the TPU a chunk's pair matrices by the
pairs kernel, `ops/kda_pairs.py`, forward and backward).

The latent attention is `models/deepseek_v3.py`'s, expanded over whole sequences and absorbed
one step through a latent cache, with ``mla_use_nope``: no rotary embedding anywhere. The
expert layer is `lm_layers.expert_layer` with this trunk's properties: sigmoid scores, the
top-k of ``s + b``, the normalised weights times ``routed_scaling_factor``, ``num_shared_experts``
shared experts as ONE ungated SwiGLU.

The carry holds, per KDA layer, the last ``K - 1`` columns of the three convolutions' input and
the matrix state ``[heads, dk, dk]``, and per latent-attention layer a latent cache
``[max_seq_len, kv_lora_rank + qk_rope_head_dim]``, a sequence each.

The parts carry ``jax.named_scope`` names (``embed``, ``kda`` with ``kda_rule`` inside it,
``mla`` with ``mla_attend`` inside it, ``router``, ``experts``, ``shared_expert``, ``dense_ffn``,
``lm_head``, ``value_head``), which a profiler capture shows on each op and which change no
program (names are metadata).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.models import deepseek_v3, lm_layers, qwen3_next
from sheeprl_tpu.models.lm_layers import INIT_STD, rms_core, stack_routes, swiglu
from sheeprl_tpu.models.qwen3_next import CHUNKS_A_TRIP, CONV_TAP_STD, DECAY_RANGE, DT_RANGE, l2_norm, unit_lower_solve
from sheeprl_tpu.ops import kda_pairs

EXPERT_BIAS_STD = deepseek_v3.EXPERT_BIAS_STD
# tokens of a sub-chunk of the chunked rule: pairs inside one take their decay as a
# [sub, sub, dk] exponent, pairs across two are factored at the later one's start
SUBCHUNK = 16


@dataclass(frozen=True)
class KimiLinearSpec:
    """The sizes as run. ``num_hidden_layers`` counts the layers held; ``kda_layers`` and
    ``full_attn_layers`` are their kinds, 1-indexed; ``experts_held`` is ``(first expert,
    count)`` of the ``num_experts`` the router scores; ``vocab_size`` is the slice of the
    vocabulary held."""

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
    kda_layers: Tuple[int, ...]
    full_attn_layers: Tuple[int, ...]
    linear_num_heads: int
    linear_head_dim: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: Tuple[int, int]
    num_shared_experts: int
    routed_scaling_factor: float
    short_conv_kernel_size: int = 4
    norm_eps: float = 1e-5
    rope_theta: float = 1e4
    mla_use_nope: bool = True
    max_seq_len: int = 512
    chunk_size: int = 64
    # the expert layer's properties (`lm_layers.expert_layer`)
    router_scoring: str = "sigmoid_bias"
    shared_expert_gate: bool = False  # the shared expert is one ungated SwiGLU

    def __post_init__(self):
        e0, n = self.experts_held
        if not (0 <= e0 and n >= 1 and e0 + n <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held} is no range of the {self.num_experts} routed experts")
        kinds = sorted(self.kda_layers) + sorted(self.full_attn_layers)
        if sorted(kinds) != list(range(1, self.num_hidden_layers + 1)):
            raise ValueError(f"kda_layers {list(self.kda_layers)} and full_attn_layers {list(self.full_attn_layers)} must "
                             f"name each of the layers 1 to {self.num_hidden_layers} once")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts leading layers of the num_hidden_layers held")
        if self.chunk_size % self.subchunk:
            raise ValueError(f"chunk_size {self.chunk_size} is no whole number of {self.subchunk}-token sub-chunks")

    @property
    def subchunk(self) -> int:
        return min(SUBCHUNK, self.chunk_size)

    @property
    def shared_expert(self) -> bool:
        return self.num_shared_experts > 0

    @property
    def linear_width(self) -> int:
        return self.linear_num_heads * self.linear_head_dim

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def layers(self):
        """(mixer, feed-forward) a layer held: ``kda`` or ``mla``, ``dense`` or ``moe``."""
        return [("kda" if i + 1 in self.kda_layers else "mla", "dense" if i < self.first_k_dense_replace else "moe")
                for i in range(self.num_hidden_layers)]

    @property
    def state_bytes_per_sequence(self) -> int:
        """float32 bytes of the carry a sequence of ``max_seq_len`` positions holds: a KDA
        layer's matrix state and convolution columns, a latent layer's cache."""
        kda = self.linear_num_heads * self.linear_head_dim ** 2 + (self.short_conv_kernel_size - 1) * 3 * self.linear_width
        return 4 * sum(kda if mixer == "kda" else self.max_seq_len * self.latent_width for mixer, _ in self.layers)

    @classmethod
    def from_cfg(cls, lm: Any, vocab_size: int, max_seq_len: int) -> "KimiLinearSpec":
        linear = lm.linear_attn_config
        return cls(
            vocab_size=int(vocab_size), hidden_size=int(lm.hidden_size), intermediate_size=int(lm.intermediate_size),
            moe_intermediate_size=int(lm.moe_intermediate_size), num_attention_heads=int(lm.num_attention_heads),
            qk_nope_head_dim=int(lm.qk_nope_head_dim), qk_rope_head_dim=int(lm.qk_rope_head_dim),
            v_head_dim=int(lm.v_head_dim), kv_lora_rank=int(lm.kv_lora_rank),
            num_hidden_layers=int(lm.num_hidden_layers), first_k_dense_replace=int(lm.first_k_dense_replace),
            kda_layers=tuple(int(i) for i in linear.kda_layers),
            full_attn_layers=tuple(int(i) for i in linear.full_attn_layers),
            linear_num_heads=int(linear.num_heads), linear_head_dim=int(linear.head_dim),
            short_conv_kernel_size=int(linear.short_conv_kernel_size),
            num_experts=int(lm.num_experts), num_experts_per_tok=int(lm.num_experts_per_tok),
            experts_held=(int(lm.experts_held[0]), int(lm.experts_held[1])),
            num_shared_experts=int(lm.num_shared_experts), routed_scaling_factor=float(lm.routed_scaling_factor),
            norm_eps=float(lm.norm_eps), rope_theta=float(lm.rope_theta), mla_use_nope=bool(lm.mla_use_nope),
            max_seq_len=int(max_seq_len), chunk_size=int(lm.chunk_size),
        )


# ---------------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------------
def init_params(spec: KimiLinearSpec, key: jax.Array) -> Dict[str, Any]:
    """N(0, 0.02) matrices, N(0, 0.3) convolution taps, unit norm weights, the output gate's
    bias 0, ``A_log = log(U(1, 16))`` a head and ``dt_bias`` the inverse softplus of
    ``dt ~ logU(0.001, 0.1)`` a key channel (as `models/qwen3_next.py` draws them), the
    score-correction bias drawn once (a buffer: it chooses experts and gets no gradient)."""
    h, nh, r = spec.hidden_size, spec.num_attention_heads, spec.kv_lora_rank
    dn, dr, dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    hk, dk, width, taps = spec.linear_num_heads, spec.linear_head_dim, spec.linear_width, spec.short_conv_kernel_size
    count = [0]

    def fresh():
        count[0] += 1
        return jax.random.fold_in(key, count[0])

    def normal(*shape, std=INIT_STD):
        return std * jax.random.normal(fresh(), shape, jnp.float32)

    def uniform(shape, low, high):
        return jax.random.uniform(fresh(), shape, jnp.float32, low, high)

    ones, zeros = partial(jnp.ones, dtype=jnp.float32), partial(jnp.zeros, dtype=jnp.float32)
    params: Dict[str, Any] = {"embed": normal(spec.vocab_size, h)}
    for i, (mixer, ffn) in enumerate(spec.layers):
        layer: Dict[str, Any] = {"op_norm": ones((h,)), "ffn_norm": ones((h,))}
        if mixer == "kda":
            dt = jnp.exp(uniform((width,), math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
            layer["op"] = {
                "wq": normal(h, width), "wk": normal(h, width), "wv": normal(h, width),
                "conv_q": normal(taps, width, std=CONV_TAP_STD), "conv_k": normal(taps, width, std=CONV_TAP_STD),
                "conv_v": normal(taps, width, std=CONV_TAP_STD),
                "w_f_down": normal(h, dk), "w_f_up": normal(dk, width),
                "A_log": jnp.log(uniform((hk,), *DECAY_RANGE)), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "w_b": normal(h, hk), "w_g_down": normal(h, dk), "w_g_up": normal(dk, width), "g_bias": zeros((width,)),
                "norm": ones((dk,)), "wo": normal(width, h)}
        else:
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
                fs = spec.num_shared_experts * f
                layer["ffn"]["shared"] = {"w1": normal(h, fs), "w3": normal(h, fs), "w2": normal(fs, h)}
        params[f"layer_{i}"] = layer
    params["norm"] = ones((h,))
    params["lm_head"] = normal(h, spec.vocab_size)
    params["value_head"] = normal(h, 1)
    return params


def init_carry(spec: KimiLinearSpec, batch: int) -> Dict[str, Any]:
    """The state a fresh batch of sequences starts from: position 0, empty convolution columns,
    ``S = 0``, empty latent caches."""
    carry: Dict[str, Any] = {"t": jnp.zeros((), jnp.int32)}
    for i, (mixer, _) in enumerate(spec.layers):
        if mixer == "kda":
            dk = spec.linear_head_dim
            carry[f"layer_{i}"] = (
                jnp.zeros((batch, spec.short_conv_kernel_size - 1, 3 * spec.linear_width), jnp.float32),
                jnp.zeros((batch, spec.linear_num_heads, dk, dk), jnp.float32))
        else:
            carry[f"layer_{i}"] = jnp.zeros((batch, spec.max_seq_len, spec.latent_width), jnp.float32)
    return carry


# ---------------------------------------------------------------------------------
# Kimi delta attention
# ---------------------------------------------------------------------------------
def rms_norm(x, weight, eps):
    return rms_core(x, eps) * weight


def decode_kernel_taken(state_shape) -> bool:
    """Whether a decode step over a state of ``state_shape`` takes the delta-rule decode kernel."""
    return qwen3_next.decode_kernel_taken(state_shape)


def kda_step(state, q, k, v, g, beta):
    """One token: ``state`` ``[B, H, dk, dv]``, ``q``, ``k``, ``g`` ``[B, H, dk]``, ``v``
    ``[B, H, dv]``, ``beta`` ``[B, H]`` -> (``o`` ``[B, H, dv]``, the new state): the rule's
    lines with a decay a key channel (`qwen3_next.delta_rule_step`: on the TPU the decode kernel)."""
    return qwen3_next.delta_rule_step(state, q, k, v, g, beta)


def _decayed_pairs(x, y, since, sub: int):
    """``sum_c x_tc y_jc exp(G_tc - G_jc)`` for ``j <= t`` of one chunk (0 above the diagonal):
    ``x``, ``y``, ``since`` (``G``, the decay accumulated since the chunk began, never
    increasing) ``[..., c, dk]`` -> ``[..., c, c]``. No exponent of a positive number is formed:
    inside a sub-chunk of ``sub`` tokens a pair's exponent is taken whole (``[sub, sub, dk]``,
    masked before the exponential), and across sub-chunks it is factored at the later
    sub-chunk's start ``r``: ``(x_t e^{G_t - G_r}) . (y_j e^{G_r - G_j})``, both exponents <= 0."""
    *lead, c, dk = x.shape
    m = c // sub
    xs, ys, gs = (a.reshape(*lead, m, sub, dk) for a in (x, y, since))
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    inside = jnp.where(lower[:, :, None], gs[..., :, None, :] - gs[..., None, :, :], -jnp.inf)
    diagonal = jnp.sum(xs[..., :, None, :] * ys[..., None, :, :] * jnp.exp(inside), axis=-1)  # [..., m, sub, sub]
    # a sub-chunk's start: the decay accumulated before its first token (0, the chunk's start, for the first)
    start = jnp.concatenate([jnp.zeros_like(gs[..., :1, 0, :]), gs[..., :-1, -1, :]], axis=-2)  # [..., m, dk]
    earlier = jnp.tril(jnp.ones((m, m), bool), -1)  # [a, b]: sub-chunk b before sub-chunk a
    back = jnp.where(earlier[:, :, None, None], start[..., :, None, None, :] - gs[..., None, :, :, :], -jnp.inf)
    right = ys[..., None, :, :, :] * jnp.exp(back)  # [..., a, b, sub, dk]
    left = xs * jnp.exp(gs - start[..., None, :])  # [..., a, sub, dk]
    across = jnp.einsum("...aic,...abjc->...abij", left, right)  # [..., a, b, sub, sub]
    blocks = jnp.where(jnp.eye(m, dtype=bool)[:, :, None, None], diagonal[..., :, None, :, :], across)
    return jnp.swapaxes(blocks, -3, -2).reshape(*lead, c, c)


def pairs_kernel_taken(shape, sub: int) -> bool:
    """Whether the chunked rule over ``q``, ``k`` of ``shape`` ``[..., chunk, dk]`` in sub-chunks of
    ``sub`` makes its two pair matrices with the pairs kernel (`ops/kda_pairs.py`): on the TPU,
    where the kernel tiles them."""
    return jax.default_backend() == "tpu" and kda_pairs.supports(shape, sub)


def chunk_kda(q, k, v, g, beta, chunk: int, sub: int = SUBCHUNK):
    """Whole sequences: ``q``, ``k``, ``g`` ``[B, T, H, dk]``, ``v`` ``[B, T, H, dv]``, ``beta``
    ``[B, T, H]`` -> ``o`` ``[B, T, H, dv]``, from ``S_0 = 0``. As `qwen3_next.chunk_delta_rule`
    with the decay a key channel: inside a chunk, with ``G_t`` the decay vector accumulated since
    it began, ``A_tj = beta_t sum_c k_tc k_jc exp(G_tc - G_jc)`` (``j < t``) and ``inside_tj =
    sum_c q_tc k_jc exp(G_tc - G_jc)`` (``j <= t``) by `_decayed_pairs` (where `pairs_kernel_taken`,
    both by one kernel that keeps the exponents in VMEM, forward and backward), ``U = (I + A)^-1 beta V``
    and ``W = (I + A)^-1 beta (K e^G)`` (`unit_lower_solve`), all chunks at once; the scan over
    the chunks carries ``S`` alone: ``D = U - W S``; ``o = (Q e^G) S + inside D``;
    ``S <- Diag(e^{G_end}) S + (K e^{G_end - G})^T D``. Exact, with nothing the recurrence lacks."""
    bsz, t, heads, dk = q.shape
    dv = v.shape[-1]
    sub = min(sub, chunk)
    pad = (-t) % chunk  # a padded token writes nothing (k, v, beta 0) and decays nothing (g 0)
    n = (t + pad) // chunk

    def chunks(x):  # [B, T, H, ...] -> [n, B, H, chunk, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(bsz, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.swapaxes(x, 2, 3), 1, 0)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    since = jnp.cumsum(g, axis=-2)  # log G_t, a key channel each
    if pairs_kernel_taken(k.shape, sub):
        keys, inside = kda_pairs.decayed_pairs(q, k, since, sub, jax.default_backend() != "tpu")
    else:
        keys, inside = _decayed_pairs(k, k, since, sub), _decayed_pairs(q, k, since, sub)
    a = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1), beta[..., None] * keys, 0.0)
    from_start = jnp.exp(since)
    k_beta = k * beta[..., None]
    solved = unit_lower_solve(a, jnp.concatenate([v * beta[..., None], k_beta * from_start], axis=-1))
    k_to_end = k * jnp.exp(since[..., -1:, :] - since)
    whole = jnp.exp(since[..., -1, :])[..., None]  # [n, B, H, dk, 1]: the chunk's decay of the state's rows

    def one_chunk(state, xs):
        u, w, q_decayed, inside, k_to_end, whole = xs  # [B, H, chunk, ...]
        pseudo = u - w @ state
        out = q_decayed @ state + inside @ pseudo
        return whole * state + jnp.swapaxes(k_to_end, -1, -2) @ pseudo, out

    xs = (solved[..., :dv], solved[..., dv:], q * from_start, inside, k_to_end, whole)
    _, out = jax.lax.scan(one_chunk, jnp.zeros((bsz, heads, dk, dv), q.dtype), xs, unroll=CHUNKS_A_TRIP)
    out = jnp.swapaxes(jnp.moveaxis(out, 0, 1), 2, 3).reshape(bsz, t + pad, heads, dv)
    return out[:, :t]


def _kda_inputs(p, u, spec: KimiLinearSpec):
    """``u`` ``[..., H]`` -> the three convolutions' input ``[..., 3 x width]`` (q, k, v side by
    side), the output gate's pre-activation ``[..., width]``, ``beta`` ``[..., heads]`` and the
    log decay ``g`` ``[..., heads, dk]``."""
    hk, dk = spec.linear_num_heads, spec.linear_head_dim
    mixed = jnp.concatenate([u @ p["wq"], u @ p["wk"], u @ p["wv"]], axis=-1)
    gate = (u @ p["w_g_down"]) @ p["w_g_up"] + p["g_bias"]
    decay = jax.nn.softplus((u @ p["w_f_down"]) @ p["w_f_up"] + p["dt_bias"])
    g = -jnp.exp(p["A_log"])[:, None] * decay.reshape(*decay.shape[:-1], hk, dk)
    return mixed, gate, jax.nn.sigmoid(u @ p["w_b"]), g


def _conv_taps(p):
    return jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], axis=-1)


def _kda_heads(mixed, spec: KimiLinearSpec):
    """The convolved channels -> ``q`` (L2-normalised per head, over ``sqrt(dk)``), ``k``
    (L2-normalised) and ``v``, ``[..., heads, dk]`` each."""
    hk, dk = spec.linear_num_heads, spec.linear_head_dim
    q, k, v = (x.reshape(*x.shape[:-1], hk, dk) for x in jnp.split(mixed, 3, axis=-1))
    return l2_norm(q) / math.sqrt(dk), l2_norm(k), v


def _kda_output(p, out, gate, spec: KimiLinearSpec):
    """``W_o (w_n * o * rsqrt(mean(o^2) + eps) * sigmoid(gate))``, the norm per head."""
    gated = p["norm"] * rms_core(out, spec.norm_eps) * jax.nn.sigmoid(gate.reshape(out.shape))
    return gated.reshape(*gated.shape[:-2], spec.linear_width) @ p["wo"]


def kda(p, u, spec: KimiLinearSpec):
    """Whole sequences ``[B, T, H]``: the three causal depthwise convolutions (tap ``j``
    multiplies the input ``K - 1 - j`` steps back), SiLU, the chunked rule."""
    mixed, gate, beta, g = _kda_inputs(p, u, spec)
    taps = _conv_taps(p)
    padded = jnp.pad(mixed, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + mixed.shape[1]] * taps[j] for j in range(taps.shape[0])))
    q, k, v = _kda_heads(mixed, spec)
    with jax.named_scope("kda_rule"):
        out = chunk_kda(q, k, v, g, beta, spec.chunk_size, spec.subchunk)
    return _kda_output(p, out, gate, spec)


def kda_layer_step(p, state, u, spec: KimiLinearSpec):
    """One step ``[B, H]``; ``state`` is (the last ``K - 1`` columns of the convolutions' input,
    the matrix state ``S``)."""
    columns, matrix = state
    mixed, gate, beta, g = _kda_inputs(p, u, spec)
    window = jnp.concatenate([columns, mixed[:, None]], axis=1)
    q, k, v = _kda_heads(jax.nn.silu(jnp.sum(window * _conv_taps(p)[None], axis=1)), spec)
    with jax.named_scope("kda_rule"):
        out, matrix = kda_step(matrix, q, k, v, g, beta)
    return _kda_output(p, out, gate, spec), (window[:, 1:], matrix)


# -- the expert layer (`models/lm_layers.py`), under the names this trunk is known by -----
def route(p, u, spec: KimiLinearSpec):
    """This trunk's router: sigmoid scores, the top-k of ``s + b``, the normalised weights
    times ``routed_scaling_factor`` (``spec.router_scoring``)."""
    return lm_layers.route(p, u, spec)


def expert_layer(p, u, spec: KimiLinearSpec):
    """`lm_layers.expert_layer` behind this module's `route` (looked up when the layer is
    traced: a fault planted under that name is the router the layer takes)."""
    return lm_layers.expert_layer(p, u, spec, route)


# ---------------------------------------------------------------------------------
# the trunk
# ---------------------------------------------------------------------------------
def _ffn(p, u, ffn: str, spec: KimiLinearSpec):
    """``u`` ``[N, H]`` -> (output, chosen ids or None, counters or None)."""
    if ffn == "dense":
        with jax.named_scope("dense_ffn"):
            return swiglu(p, u), None, None
    return expert_layer(p, u, spec)


def heads(params, x, spec: KimiLinearSpec):
    return lm_layers.heads(params, rms_norm(x, params["norm"], spec.norm_eps))


def forward(params, spec: KimiLinearSpec, tokens):
    """Whole sequences ``tokens`` ``[B, T]`` -> logits ``[B, T, V]``, values ``[B, T]``, the
    chosen experts ``[B, T, expert layers, k]`` and the layers' counters, with
    ``kda/pairs_kernel_share`` (the share of the KDA layers whose chunked rule took the pairs
    kernel). Each block is recomputed in a backward pass (``jax.checkpoint``)."""
    bsz, t = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes = []
    for i, (mixer, ffn) in enumerate(spec.layers):

        def block(p, x, mixer=mixer, ffn=ffn):
            u = rms_norm(x, p["op_norm"], spec.norm_eps)
            with jax.named_scope(mixer):
                x = x + (kda(p["op"], u, spec) if mixer == "kda" else deepseek_v3.mla(p["op"], u, spec))
            u = rms_norm(x, p["ffn_norm"], spec.norm_eps).reshape(bsz * t, -1)
            y, ids, counters = _ffn(p["ffn"], u, ffn, spec)
            return x + y.reshape(bsz, t, -1), ids, counters

        x, ids, counters = jax.checkpoint(block)(params[f"layer_{i}"], x)
        if ids is not None:
            routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    if counters is not None and spec.kda_layers:  # fixed when traced; every KDA layer's rule has the same shapes
        taken = pairs_kernel_taken((spec.chunk_size, spec.linear_head_dim), spec.subchunk)
        counters["kda/pairs_kernel_share"] = jnp.float32(taken)
    return logits, value, None if ids is None else ids.reshape(bsz, t, *ids.shape[1:]), counters


def step(params, spec: KimiLinearSpec, carry, tokens):
    """One token a sequence, ``tokens`` ``[B]``, through the carried state -> logits ``[B, V]``,
    values ``[B]``, the new carry, the chosen experts ``[B, expert layers, k]`` and the layers'
    counters, with ``kda/decode_kernel_share`` (the share of the KDA layers whose rule took the
    delta-rule decode kernel) and ``mla/decode_kernel_share`` (of the latent layers whose
    attention took the latent-cache kernel)."""
    t = carry["t"]
    new_carry: Dict[str, Any] = {"t": t + 1}
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes = []
    for i, (mixer, ffn) in enumerate(spec.layers):
        p, name = params[f"layer_{i}"], f"layer_{i}"
        u = rms_norm(x, p["op_norm"], spec.norm_eps)
        with jax.named_scope(mixer):
            if mixer == "kda":
                y, new_carry[name] = kda_layer_step(p["op"], carry[name], u, spec)
            else:
                y, new_carry[name] = deepseek_v3.mla_step(p["op"], carry[name], u, t, spec)
        x = x + y
        y, ids, counters = _ffn(p["ffn"], rms_norm(x, p["ffn_norm"], spec.norm_eps), ffn, spec)
        x = x + y
        if ids is not None:
            routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    if counters is not None:  # fixed when traced
        for space, taken in (("kda", lambda i: decode_kernel_taken(carry[f"layer_{i}"][1].shape)),
                             ("mla", lambda i: bool(deepseek_v3.decode_kernel_passes(carry[f"layer_{i}"].shape)))):
            kernel = [taken(i) for i, (mixer, _) in enumerate(spec.layers) if mixer == space]
            if kernel:
                counters[f"{space}/decode_kernel_share"] = jnp.float32(sum(kernel) / len(kernel))
    return logits, value, new_carry, ids, counters


def parameter_count(spec: KimiLinearSpec) -> int:
    return lm_layers.parameter_count(init_params, spec)
