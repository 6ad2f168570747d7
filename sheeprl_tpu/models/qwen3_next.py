"""The `qwen3_next` trunk as a sequence-model policy (Qwen3-Next-80B-A3B's block: a
zero-centred RMSNorm, a gated delta-rule linear-attention mixer or gated softmax attention,
3:1, then a sparse expert layer with a shared expert), as pure functions over a parameter
tree, beside ``models/lfm2.py`` and on the same shared layers (``models/lm_layers.py``).

Every layer has two forms that agree: over whole sequences ``[B, T, H]`` (the loss's
teacher-forced forward) and one step ``[B, H]`` that carries state (the rollout). The carry
holds three kinds of per-sequence state side by side: a KV cache per attention layer, and
per linear-attention layer the last ``kernel - 1`` columns of the convolution's input and a
matrix state ``S`` ``[value heads, key dim, value dim]``.

The gated delta rule, per value head and token, with ``S_0 = 0``:
``S <- exp(g_t) S``; ``r = S^T k_t``; ``S <- S + k_t (beta_t (v_t - r))^T``; ``o_t = S^T q_t``.
The step form is those four lines (on the TPU one kernel, `ops/delta_rule_decode.py`, that
reads and writes ``S`` once a step). The whole-sequence form is chunked (`chunk_delta_rule`):
inside a chunk of ``chunk_size`` tokens the rule's pseudo-values solve one unit lower
triangular system, whose inverse is formed by products for all chunks at once, and a scan
over the chunks carries ``S``; it is exact, with no approximation the recurrence does not
have, and the update differentiates through it.

The expert layer is `lm_layers.expert_layer` with this trunk's properties: a float32
softmax over all ``num_experts``, ``num_experts_per_tok`` of them a token, a shared expert
behind a sigmoid gate.

The parts carry ``jax.named_scope`` names (``embed``, ``linear_attention`` with
``delta_rule`` inside it, ``attention``, ``router``, ``experts``, ``shared_expert``,
``lm_head``, ``value_head``), which a profiler capture shows on each op and which change
no program (names are metadata).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.models import lm_layers
from sheeprl_tpu.models.lm_layers import INIT_STD, attend, default_frequencies, rms_core, rope, stack_routes
from sheeprl_tpu.ops import delta_rule_decode as decode_kernel

CONV_TAP_STD = 0.3
L2_EPS = 1e-6
DECAY_RANGE = (1.0, 16.0)  # A ~ U: `A_log = log(A)`
DT_RANGE = (1e-3, 1e-1)  # dt ~ logU: `dt_bias` is its inverse softplus
CHUNKS_A_TRIP = 8  # of the chunked rule's scan: straight-line code needs no slicing and stacking of the chunks' arrays


@dataclass(frozen=True)
class Qwen3NextSpec:
    """The sizes as run. ``layer_types`` lists the layers held (``linear_attention`` /
    ``full_attention``); ``experts_held`` is ``(first expert, count)`` of the ``num_experts``
    the router scores; ``vocab_size`` is the slice of the vocabulary held."""

    vocab_size: int
    hidden_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    layer_types: Tuple[str, ...]
    num_experts: int
    num_experts_per_tok: int
    experts_held: Tuple[int, int]
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 0.25
    norm_eps: float = 1e-6
    rope_theta: float = 1e7
    max_seq_len: int = 512
    chunk_size: int = 64
    # the expert layer's properties (`lm_layers.expert_layer`)
    router_scoring: str = "softmax"
    shared_expert: bool = True

    def __post_init__(self):
        e0, n = self.experts_held
        if not (0 <= e0 and n >= 1 and e0 + n <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held} is no range of the {self.num_experts} routed experts")
        if self.num_attention_heads % self.num_key_value_heads or self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("key/value heads must divide the query heads, and linear key heads the linear value heads")
        if self.rotary_dim % 2:
            raise ValueError(f"partial_rotary_factor x head_dim = {self.rotary_dim} is no even number of channels")
        unknown = set(self.layer_types) - {"linear_attention", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_width + self.value_width

    @property
    def num_moe_layers(self) -> int:
        return len(self.layer_types)

    @property
    def linear_state_bytes_per_sequence(self) -> int:
        """float32 bytes of the linear-attention layers' state a sequence: every value head's
        matrix state and the convolution's carried columns."""
        a_layer = (self.linear_num_value_heads * self.linear_key_head_dim * self.linear_value_head_dim
                   + (self.linear_conv_kernel_dim - 1) * self.conv_channels)
        return 4 * a_layer * sum(op == "linear_attention" for op in self.layer_types)

    @classmethod
    def from_cfg(cls, lm: Any, vocab_size: int, max_seq_len: int) -> "Qwen3NextSpec":
        return cls(
            vocab_size=int(vocab_size), hidden_size=int(lm.hidden_size),
            moe_intermediate_size=int(lm.moe_intermediate_size),
            shared_expert_intermediate_size=int(lm.shared_expert_intermediate_size),
            num_attention_heads=int(lm.num_attention_heads), num_key_value_heads=int(lm.num_key_value_heads),
            head_dim=int(lm.head_dim), linear_num_key_heads=int(lm.linear_num_key_heads),
            linear_num_value_heads=int(lm.linear_num_value_heads), linear_key_head_dim=int(lm.linear_key_head_dim),
            linear_value_head_dim=int(lm.linear_value_head_dim), layer_types=tuple(str(t) for t in lm.layer_types),
            num_experts=int(lm.num_experts), num_experts_per_tok=int(lm.num_experts_per_tok),
            experts_held=(int(lm.experts_held[0]), int(lm.experts_held[1])),
            linear_conv_kernel_dim=int(lm.linear_conv_kernel_dim), partial_rotary_factor=float(lm.partial_rotary_factor),
            norm_eps=float(lm.norm_eps), rope_theta=float(lm.rope_theta), max_seq_len=int(max_seq_len),
            chunk_size=int(lm.chunk_size),
        )


# ---------------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------------
def init_params(spec: Qwen3NextSpec, key: jax.Array) -> Dict[str, Any]:
    """N(0, 0.02) matrices, N(0, 0.3) convolution taps, zero-centred norm weights at 0 (the
    linear attention's output norm, which is not zero-centred, at 1), ``A_log = log(U(1, 16))``
    and ``dt_bias`` the inverse softplus of ``dt ~ logU(0.001, 0.1)``: a step's decay
    ``exp(g)`` then lies in about 0.2 to 0.999, so the carried state matters over hundreds
    of steps."""
    h, d = spec.hidden_size, spec.head_dim
    nq, nkv, hv = spec.num_attention_heads, spec.num_key_value_heads, spec.linear_num_value_heads
    count = [0]

    def fresh():
        count[0] += 1
        return jax.random.fold_in(key, count[0])

    def normal(*shape, std=INIT_STD):
        return std * jax.random.normal(fresh(), shape, jnp.float32)

    def uniform(shape, low, high):
        return jax.random.uniform(fresh(), shape, jnp.float32, low, high)

    zeros = lambda n: jnp.zeros((n,), jnp.float32)  # noqa: E731
    params: Dict[str, Any] = {"embed": normal(spec.vocab_size, h)}
    for i, op in enumerate(spec.layer_types):
        layer: Dict[str, Any] = {"op_norm": zeros(h), "ffn_norm": zeros(h)}
        if op == "linear_attention":
            dt = jnp.exp(uniform((hv,), math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
            layer["op"] = {
                "w_qkvz": normal(h, spec.conv_channels + spec.value_width), "w_ba": normal(h, 2 * hv),
                "w_conv": normal(spec.linear_conv_kernel_dim, spec.conv_channels, std=CONV_TAP_STD),
                "A_log": jnp.log(uniform((hv,), *DECAY_RANGE)), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": jnp.ones((spec.linear_value_head_dim,), jnp.float32), "w_out": normal(spec.value_width, h)}
        else:
            layer["op"] = {"wq": normal(h, nq * d * 2), "wk": normal(h, nkv * d), "wv": normal(h, nkv * d),
                           "wo": normal(nq * d, h), "q_norm": zeros(d), "k_norm": zeros(d)}
        f, fs, n = spec.moe_intermediate_size, spec.shared_expert_intermediate_size, spec.experts_held[1]
        layer["ffn"] = {"router": normal(h, spec.num_experts),
                        "w1": normal(n, h, f), "w3": normal(n, h, f), "w2": normal(n, f, h),
                        "shared": {"w1": normal(h, fs), "w3": normal(h, fs), "w2": normal(fs, h)},
                        "shared_gate": normal(h, 1)}
        params[f"layer_{i}"] = layer
    params["norm"] = zeros(h)
    params["lm_head"] = normal(h, spec.vocab_size)
    params["value_head"] = normal(h, 1)
    return params


def init_carry(spec: Qwen3NextSpec, batch: int) -> Dict[str, Any]:
    """The state a fresh batch of sequences starts from: position 0, empty caches, ``S = 0``."""
    carry: Dict[str, Any] = {"t": jnp.zeros((), jnp.int32)}
    for i, op in enumerate(spec.layer_types):
        if op == "linear_attention":
            carry[f"layer_{i}"] = (
                jnp.zeros((batch, spec.linear_conv_kernel_dim - 1, spec.conv_channels), jnp.float32),
                jnp.zeros((batch, spec.linear_num_value_heads, spec.linear_key_head_dim, spec.linear_value_head_dim), jnp.float32))
        else:
            kv = (batch, spec.max_seq_len, spec.num_key_value_heads, spec.head_dim)
            carry[f"layer_{i}"] = (jnp.zeros(kv, jnp.float32), jnp.zeros(kv, jnp.float32))
    return carry


# ---------------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------------
def rms_norm(x, weight, eps):
    """The trunk's norm, zero-centred: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``."""
    return rms_core(x, eps) * (1.0 + weight)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


# -- the gated delta rule -----------------------------------------------------------------
def decode_kernel_taken(state_shape) -> bool:
    """Whether a decode step over a state of ``state_shape`` takes the kernel
    (`ops/delta_rule_decode.py`): on the TPU, where the kernel tiles the state."""
    return jax.default_backend() == "tpu" and decode_kernel.supports(state_shape)


def delta_rule_step(state, q, k, v, g, beta):
    """One token: ``state`` ``[B, H, dk, dv]``, ``q``, ``k`` ``[B, H, dk]``, ``v`` ``[B, H, dv]``,
    ``g`` ``[B, H]`` (or ``[B, H, dk]``, a decay a key channel that scales the state's rows:
    `models/kimi_linear.py`'s), ``beta`` ``[B, H]`` -> (``o`` ``[B, H, dv]``, the new state).
    Where `decode_kernel_taken`, one kernel that reads each head's state once and writes it
    back in place; else the rule's four lines in XLA."""
    if decode_kernel_taken(state.shape):
        return decode_kernel.delta_rule_decode(state, q, k, v, g, beta, interpret=jax.default_backend() != "tpu")
    state = state * (jnp.exp(g)[..., None, None] if g.ndim == 2 else jnp.exp(g)[..., None])
    read = jnp.einsum("bhkv,bhk->bhv", state, k)
    state = state + k[..., :, None] * (beta[..., None] * (v - read))[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q), state


def unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower triangular ``a`` ``[..., c, c]`` by products of its
    diagonal blocks, whose size doubles from 1 to ``c``:
    ``[[L11, 0], [L21, L22]]^-1 = [[M11, 0], [-M22 L21 M11, M22]]``, exact because every block is
    unit lower triangular. The blocks are at most ``c / 2`` wide, too small for the matrix unit,
    so the batch lies in the lanes and a product is an elementwise multiply and a sum, in float32."""
    lead, c = a.shape[:-2], a.shape[-1]
    size = 1 << (c - 1).bit_length()  # padded to a power of two: ``[[I + a, 0], [0, I]]``
    x = jnp.moveaxis(jnp.pad(a.reshape(-1, c, c), ((0, 0), (0, size - c), (0, size - c))), 0, -1)  # [size, size, N]

    def product(u, v):  # [pairs, s, s, N] each
        return jnp.sum(u[:, :, :, None] * v[:, None], axis=2)

    inverse, s = jnp.ones((size, 1, 1, x.shape[-1]), a.dtype), 1  # a diagonal block each
    while s < size:
        below = jnp.stack([x[i + s:i + 2 * s, i:i + s] for i in range(0, size, 2 * s)])  # every L21
        first, second = inverse[0::2], inverse[1::2]
        corner = -product(second, product(below, first))
        inverse = jnp.concatenate([jnp.concatenate([first, jnp.zeros_like(first)], axis=2),
                                   jnp.concatenate([corner, second], axis=2)], axis=1)
        s *= 2
    return jnp.moveaxis(inverse[0], -1, 0)[:, :c, :c].reshape(*lead, c, c)


@jax.custom_vjp
def unit_lower_solve(a, rhs):
    """``(I + a)^-1 rhs``, ``a`` strictly lower triangular ``[..., c, c]``, ``rhs`` ``[..., c, m]``.
    The backward pass solves nothing either: it keeps the inverse, ``d rhs = inverse^T ct`` and
    ``d a = -tril(d rhs . solved^T, -1)``."""
    return unit_lower_inverse(a) @ rhs


def _unit_lower_solve_fwd(a, rhs):
    inverse = unit_lower_inverse(a)
    solved = inverse @ rhs
    return solved, (inverse, solved)


def _unit_lower_solve_bwd(kept, ct):
    inverse, solved = kept
    d_rhs = jnp.swapaxes(inverse, -1, -2) @ ct
    return -jnp.tril(d_rhs @ jnp.swapaxes(solved, -1, -2), -1), d_rhs


unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


def chunk_delta_rule(q, k, v, g, beta, chunk: int):
    """Whole sequences: ``q``, ``k`` ``[B, T, H, dk]``, ``v`` ``[B, T, H, dv]``, ``g``, ``beta``
    ``[B, T, H]`` -> ``o`` ``[B, T, H, dv]``, from ``S_0 = 0``. Inside a chunk, with ``G_t`` the
    decay accumulated since the chunk began and ``S_0`` the state it began with, the
    pseudo-values ``d_t = beta_t (v_t - S~_t^T k_t)`` solve ``(I + A) D = beta V - (beta G K) S_0``,
    ``A_tj = beta_t (G_t / G_j) (k_t . k_j)`` for ``j < t``. What does not need ``S_0`` is done
    once for all chunks together: ``U = (I + A)^-1 beta V``, ``W = (I + A)^-1 beta G K``
    (`unit_lower_solve`), the decayed ``q k^T`` inside a chunk, ``G q`` and the keys decayed to
    the chunk's end. The scan over the chunks carries ``S`` alone, three products a chunk:
    ``D = U - W S``; ``o = (G q) S + inside D``; ``S <- G_end S + k_to_end^T D``. It takes
    `CHUNKS_A_TRIP` chunks a trip, so up to that many chunks there is no loop at all."""
    bsz, t, heads, dk = q.shape
    dv = v.shape[-1]
    pad = (-t) % chunk  # a padded token writes nothing (k, v, beta 0) and decays nothing (g 0)
    n = (t + pad) // chunk

    def chunks(x):  # [B, T, H, ...] -> [n, B, H, chunk, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(bsz, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.swapaxes(x, 2, 3), 1, 0)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    since = jnp.cumsum(g, axis=-1)  # log G_t
    # masked before the exponential: above the diagonal the exponent is positive and may overflow
    decay = jnp.exp(jnp.where(lower, since[..., :, None] - since[..., None, :], -jnp.inf))
    from_start = jnp.exp(since)[..., None]
    k_beta = k * beta[..., None]
    a = jnp.where(jnp.tril(lower, -1), jnp.einsum("...ik,...jk->...ij", k_beta, k) * decay, 0.0)
    solved = unit_lower_solve(a, jnp.concatenate([v * beta[..., None], k_beta * from_start], axis=-1))
    inside = jnp.einsum("...ik,...jk->...ij", q, k) * decay  # (G_t / G_j) (q_t . k_j), j <= t
    k_to_end = k * jnp.exp(since[..., -1:] - since)[..., None]
    whole = jnp.exp(since[..., -1])[..., None, None]

    def one_chunk(state, xs):
        u, w, q_decayed, inside, k_to_end, whole = xs  # [B, H, chunk, ...]
        pseudo = u - w @ state
        out = q_decayed @ state + inside @ pseudo
        return whole * state + jnp.swapaxes(k_to_end, -1, -2) @ pseudo, out

    xs = (solved[..., :dv], solved[..., dv:], q * from_start, inside, k_to_end, whole)
    _, out = jax.lax.scan(one_chunk, jnp.zeros((bsz, heads, dk, dv), q.dtype), xs, unroll=CHUNKS_A_TRIP)
    out = jnp.swapaxes(jnp.moveaxis(out, 0, 1), 2, 3).reshape(bsz, t + pad, heads, dv)
    return out[:, :t]


def _linear_inputs(p, u, spec: Qwen3NextSpec):
    """``u`` ``[..., H]`` -> the convolution's input ``[..., 2 key + value width]``, the output
    gate ``z``, the write strength ``beta`` and the log decay ``g`` (a value head each)."""
    qkvz = u @ p["w_qkvz"]
    b, a = jnp.split(u @ p["w_ba"], 2, axis=-1)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    return qkvz[..., :spec.conv_channels], qkvz[..., spec.conv_channels:], jax.nn.sigmoid(b), g


def _linear_heads(mixed, spec: Qwen3NextSpec):
    """The convolved channels -> ``q``, ``k`` (L2-normalised per head, ``q`` over
    ``sqrt(key dim)``, each key head serving ``value heads / key heads`` value heads) and ``v``."""
    hk, hv, dk = spec.linear_num_key_heads, spec.linear_num_value_heads, spec.linear_key_head_dim
    q, k, v = jnp.split(mixed, [spec.key_width, 2 * spec.key_width], axis=-1)
    q = l2_norm(q.reshape(*q.shape[:-1], hk, dk)) / math.sqrt(dk)
    k = l2_norm(k.reshape(*k.shape[:-1], hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=-2) for x in (q, k))
    return q, k, v.reshape(*v.shape[:-1], hv, spec.linear_value_head_dim)


def _linear_output(p, out, z, spec: Qwen3NextSpec):
    """``W_o (w_n * o * rsqrt(mean(o^2) + eps) * silu(z))``, the norm per head."""
    gated = p["norm"] * rms_core(out, spec.norm_eps) * jax.nn.silu(z.reshape(out.shape))
    return gated.reshape(*gated.shape[:-2], spec.value_width) @ p["w_out"]


def linear_attention(p, u, spec: Qwen3NextSpec):
    """Whole sequences ``[B, T, H]``: a causal depthwise convolution over q, k and v together
    (tap ``j`` multiplies the input ``K - 1 - j`` steps back), SiLU, the chunked delta rule."""
    mixed, z, beta, g = _linear_inputs(p, u, spec)
    taps = p["w_conv"].shape[0]
    padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + mixed.shape[1]] * p["w_conv"][j] for j in range(taps)))
    q, k, v = _linear_heads(mixed, spec)
    with jax.named_scope("delta_rule"):
        out = chunk_delta_rule(q, k, v, g, beta, spec.chunk_size)
    return _linear_output(p, out, z, spec)


def linear_attention_step(p, state, u, spec: Qwen3NextSpec):
    """One step ``[B, H]``; ``state`` is (the last ``K - 1`` columns of the convolution's
    input, the matrix state ``S``)."""
    columns, matrix = state
    mixed, z, beta, g = _linear_inputs(p, u, spec)
    window = jnp.concatenate([columns, mixed[:, None]], axis=1)
    q, k, v = _linear_heads(jax.nn.silu(jnp.sum(window * p["w_conv"][None], axis=1)), spec)
    with jax.named_scope("delta_rule"):
        out, matrix = delta_rule_step(matrix, q, k, v, g, beta)
    return _linear_output(p, out, z, spec), (window[:, 1:], matrix)


# -- gated attention ----------------------------------------------------------------------
def _qkv_gate(p, u, positions, spec: Qwen3NextSpec):
    """``u`` ``[B, T, H]`` -> q ``[B, T, nq, d]``, k and v ``[B, T, nkv, d]`` (q and k normed
    per head and rotated over the head's first ``rotary_dim``), and the query-wide gate."""
    bsz, t, _ = u.shape
    nq, nkv, d = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    q, gate = jnp.split((u @ p["wq"]).reshape(bsz, t, nq, 2 * d), 2, axis=-1)  # a head's query, then its gate
    q = rms_norm(q, p["q_norm"], spec.norm_eps)
    k = rms_norm((u @ p["wk"]).reshape(bsz, t, nkv, d), p["k_norm"], spec.norm_eps)
    v = (u @ p["wv"]).reshape(bsz, t, nkv, d)
    turn = lambda x: rope(x, positions, default_frequencies(spec.rope_theta, spec.rotary_dim))  # noqa: E731
    return turn(q), turn(k), v, gate.reshape(bsz, t, nq * d)


def attention(p, u, spec: Qwen3NextSpec):
    t = u.shape[1]
    q, k, v, gate = _qkv_gate(p, u, jnp.arange(t), spec)
    out = attend(q, k, v, jnp.tril(jnp.ones((t, t), bool)), spec.num_key_value_heads)
    return (out * jax.nn.sigmoid(gate)) @ p["wo"]


def attention_step(p, cache, u, t, spec: Qwen3NextSpec):
    """One step ``[B, H]`` at position ``t``: write this step's key and value into the
    cache ``[B, S, nkv, d]``, attend over the positions up to ``t``."""
    q, k, v, gate = _qkv_gate(p, u[:, None], t[None], spec)
    keys = jax.lax.dynamic_update_slice_in_dim(cache[0], k, t, axis=1)
    values = jax.lax.dynamic_update_slice_in_dim(cache[1], v, t, axis=1)
    mask = (jnp.arange(keys.shape[1]) <= t)[None]
    out = attend(q, keys, values, mask, spec.num_key_value_heads)
    return (out * jax.nn.sigmoid(gate))[:, 0] @ p["wo"], (keys, values)


# -- the expert layer (`models/lm_layers.py`), under the names this trunk is known by -----
def route(p, u, spec: Qwen3NextSpec):
    """This trunk's router: a float32 softmax over all experts, its k largest (``spec.router_scoring``)."""
    return lm_layers.route(p, u, spec)


def expert_layer(p, u, spec: Qwen3NextSpec):
    """`lm_layers.expert_layer` behind this module's `route` (looked up when the layer is
    traced: a fault planted under that name is the router the layer takes)."""
    return lm_layers.expert_layer(p, u, spec, route)


# ---------------------------------------------------------------------------------
# the trunk
# ---------------------------------------------------------------------------------
def heads(params, x, spec: Qwen3NextSpec):
    return lm_layers.heads(params, rms_norm(x, params["norm"], spec.norm_eps))


def forward(params, spec: Qwen3NextSpec, tokens):
    """Whole sequences ``tokens`` ``[B, T]`` -> logits ``[B, T, V]``, values ``[B, T]``, the
    chosen experts ``[B, T, layers, k]`` and the layers' counters. Each block is recomputed
    in a backward pass (``jax.checkpoint``): a gradient step keeps one block's activations."""
    bsz, t = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes = []
    for i, op in enumerate(spec.layer_types):

        def block(p, x, op=op):
            u = rms_norm(x, p["op_norm"], spec.norm_eps)
            if op == "linear_attention":
                with jax.named_scope("linear_attention"):
                    x = x + linear_attention(p["op"], u, spec)
            else:
                with jax.named_scope("attention"):
                    x = x + attention(p["op"], u, spec)
            u = rms_norm(x, p["ffn_norm"], spec.norm_eps).reshape(bsz * t, -1)
            y, ids, counters = expert_layer(p["ffn"], u, spec)
            return x + y.reshape(bsz, t, -1), ids, counters

        x, ids, counters = jax.checkpoint(block)(params[f"layer_{i}"], x)
        routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    return logits, value, ids.reshape(bsz, t, *ids.shape[1:]), counters


def step(params, spec: Qwen3NextSpec, carry, tokens):
    """One token a sequence, ``tokens`` ``[B]``, through the carried state -> logits
    ``[B, V]``, values ``[B]``, the new carry, the chosen experts ``[B, layers, k]`` and the
    layers' counters, with ``lin_attn/decode_kernel_share``: the share of the linear-attention
    layers whose delta rule took the decode kernel (`decode_kernel_taken`)."""
    t = carry["t"]
    new_carry: Dict[str, Any] = {"t": t + 1}
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes = []
    for i, op in enumerate(spec.layer_types):
        p, name = params[f"layer_{i}"], f"layer_{i}"
        u = rms_norm(x, p["op_norm"], spec.norm_eps)
        if op == "linear_attention":
            with jax.named_scope("linear_attention"):
                y, new_carry[name] = linear_attention_step(p["op"], carry[name], u, spec)
        else:
            with jax.named_scope("attention"):
                y, new_carry[name] = attention_step(p["op"], carry[name], u, t, spec)
        x = x + y
        y, ids, counters = expert_layer(p["ffn"], rms_norm(x, p["ffn_norm"], spec.norm_eps), spec)
        x = x + y
        routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    kernel = [decode_kernel_taken(carry[f"layer_{i}"][1].shape) for i, op in enumerate(spec.layer_types)
              if op == "linear_attention"]
    if kernel:  # fixed when traced
        counters["lin_attn/decode_kernel_share"] = jnp.float32(sum(kernel) / len(kernel))
    return logits, value, new_carry, ids, counters


def parameter_count(spec: Qwen3NextSpec) -> int:
    return lm_layers.parameter_count(init_params, spec)
