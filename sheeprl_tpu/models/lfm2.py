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
buffer is the static worst case, and the grouped products visit only the rows in use;
a decode step's few tokens skip the sort and go through every held expert (`DENSE_TOKENS`).

The parts carry ``jax.named_scope`` names (``embed``, ``short_conv``, ``attention``,
``router``, ``experts``, ``dense_ffn``, ``lm_head``, ``value_head``), which a profiler
capture shows on each op and which change no program (names are metadata).
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from sheeprl_tpu.ops.grouped_matmul import gmm, gmm_tiling, row_tiles_visited, tgmm, tgmm_tiling

INIT_STD = 0.02
EXPERT_BIAS_STD = 0.05
WEIGHT_SUM_EPS = 1e-20


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
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


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


def _rotate_half(x):
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-b, a], axis=-1)


def rope(x, positions, theta):
    """``x`` ``[..., T, heads, d]`` at ``positions`` ``[T]``: rotate-half over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * inv[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _qkv(p, u, positions, spec: LFM2Spec):
    """``u`` ``[B, T, H]`` -> q ``[B, T, nq, d]``, k and v ``[B, T, nkv, d]``, normed and rotated."""
    bsz, t, _ = u.shape
    nq, nkv, d = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    q = rms_norm((u @ p["wq"]).reshape(bsz, t, nq, d), p["q_norm"], spec.norm_eps)
    k = rms_norm((u @ p["wk"]).reshape(bsz, t, nkv, d), p["k_norm"], spec.norm_eps)
    v = (u @ p["wv"]).reshape(bsz, t, nkv, d)
    return rope(q, positions, spec.rope_theta), rope(k, positions, spec.rope_theta), v


def _attend(q, k, v, mask, spec: LFM2Spec):
    """Grouped-query attention: query head ``i`` reads key/value head ``i // group``;
    ``mask`` ``[Tq, Tk]`` is True where a query may look."""
    bsz, tq, nq, d = q.shape
    nkv = spec.num_key_value_heads
    q = q.reshape(bsz, tq, nkv, nq // nkv, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(jnp.float32(d))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(bsz, tq, nq * d)


def attention(p, u, spec: LFM2Spec):
    t = u.shape[1]
    q, k, v = _qkv(p, u, jnp.arange(t), spec)
    return _attend(q, k, v, jnp.tril(jnp.ones((t, t), bool)), spec) @ p["wo"]


def attention_step(p, cache, u, t, spec: LFM2Spec):
    """One step ``[B, H]`` at position ``t``: write this step's key and value into the
    cache ``[B, S, nkv, d]``, attend over the positions up to ``t``."""
    q, k, v = _qkv(p, u[:, None], t[None], spec)
    keys = jax.lax.dynamic_update_slice_in_dim(cache[0], k, t, axis=1)
    values = jax.lax.dynamic_update_slice_in_dim(cache[1], v, t, axis=1)
    mask = (jnp.arange(keys.shape[1]) <= t)[None]
    return _attend(q, keys, values, mask, spec)[:, 0] @ p["wo"], (keys, values)


def swiglu(p, u):
    return (jax.nn.silu(u @ p["w1"]) * (u @ p["w3"])) @ p["w2"]


# -- the expert layer ------------------------------------------------------------------
def route(p, u, spec: LFM2Spec):
    """``u`` ``[N, H]`` -> the chosen experts ``[N, k]`` (top-k of ``s + b``) and their
    weights (``s`` without ``b``, over the sum of all k chosen). The published
    ``use_expert_bias`` and ``norm_topk_prob`` are both true and ``routed_scaling_factor``
    is 1: the one model there is has no other value, so none is an option here."""
    s = jax.nn.sigmoid(u @ p["router"])
    ids = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"]), spec.num_experts_per_tok)[1]
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / (w.sum(axis=-1, keepdims=True) + WEIGHT_SUM_EPS)


@jax.custom_vjp
def _permute(rows, perm, inverse):
    """``rows[perm]`` for a permutation whose inverse is known: the transpose is the gather
    by the inverse, where a gather's own transpose would be a scatter-add."""
    return rows[perm]


def _permute_fwd(rows, perm, inverse):
    return rows[perm], (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


# bf16 passes of a float32 product at each ambient matmul precision (`jax.default_matmul_precision`,
# which `cli.py` sets from `float32_matmul_precision`): what XLA:TPU gives every `@` of this model
_PASSES = {None: 1, "default": 1, "high": 3, "highest": 6}


def matmul_passes() -> int:
    """How many bf16 passes the grouped products take: as many as the precision in force
    when the program is traced gives every other product (`high` three, `highest` six)."""
    precision = jax.config.jax_default_matmul_precision
    if precision not in _PASSES:
        raise ValueError(f"lfm2: no count of bf16 passes is known for jax_default_matmul_precision={precision!r}")
    return _PASSES[precision]


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_tpu(rows, weights, group_sizes, passes):
    """The repo's grouped matmul kernels (`ops/grouped_matmul.py`) in float32 at `passes`
    bf16 passes, forward and backward: a tile is read from HBM once and split in VMEM.
    Off the chip (tests) the same kernels run in Pallas' interpreter."""
    (m, k), n = rows.shape, weights.shape[2]
    return gmm(rows, weights, group_sizes, gmm_tiling(m, k, n), passes, interpret=_interpret())


def _gmm_tpu_fwd(rows, weights, group_sizes, passes):
    return _gmm_tpu(rows, weights, group_sizes, passes), (rows, weights, group_sizes)


def _gmm_tpu_bwd(passes, res, g):
    rows, weights, group_sizes = res
    (m, k), n = rows.shape, weights.shape[2]
    d_rows = gmm(g, weights, group_sizes, gmm_tiling(m, n, k), passes, transpose_rhs=True, interpret=_interpret())
    d_weights = tgmm(rows, g, group_sizes, tgmm_tiling(m, k, n), passes, interpret=_interpret())
    return d_rows, d_weights, None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def tile_fill(group_sizes, tm: int):
    """Pairs landed over the rows of the ``tm``-row tiles the grouped products visit for
    them: under 1 by the tiles that straddle a group's end, each computed in full."""
    rows = row_tiles_visited(group_sizes, tm) * tm
    return group_sizes.sum().astype(jnp.float32) / jnp.maximum(rows, 1).astype(jnp.float32)


def kernel_passes(m: int, k: int, n: int) -> int:
    """The bf16 passes the grouped kernels take for ``[m, k] x [groups, k, n]`` in the program
    being traced, or 0 where they are not taken: off the TPU, or at a size they cannot tile."""
    if jax.default_backend() != "tpu" or any(size % 128 for size in (m, k, n)):
        return 0
    return matmul_passes()


def grouped_matmul(rows, weights, group_sizes, valid):
    """``rows`` ``[M, K]`` sorted by group, ``weights`` ``[G, K, N]``: row ``i`` of group
    ``g`` times ``weights[g]``. Rows past ``sum(group_sizes)`` (``valid`` False) belong to
    no group here: they are not computed and read as 0, both ways. Where the TPU is the
    default backend the products are the repo's grouped matmul kernels, which visit only
    the tiles in use and take the bf16 passes of the ambient matmul precision
    (`matmul_passes`); elsewhere ``lax.ragged_dot``. XLA:TPU expands a ``ragged_dot`` to
    one dense product per group (8 times the FLOPs at 8 groups), so a TPU run whose widths
    the kernel cannot tile says so, once, rather than be measured on that path with
    nothing said."""
    rows = jnp.where(valid[:, None], rows, 0.0)
    sizes = rows.shape[0], rows.shape[1], weights.shape[2]
    passes = kernel_passes(*sizes)
    if passes:
        out = _gmm_tpu(rows, weights, group_sizes, passes)
    else:
        if jax.default_backend() == "tpu":
            _warn_dense_groups(sizes, weights.shape[0])
        out = jax.lax.ragged_dot(rows, weights, group_sizes)
    return jnp.where(valid[:, None], out, 0.0)


@lru_cache(maxsize=None)
def _warn_dense_groups(sizes, groups: int) -> None:
    warnings.warn(
        f"lfm2: grouped products of [M, K, N] = {list(sizes)} have a size that is no multiple of 128, so the "
        f"Pallas grouped matmul cannot tile them: on this TPU they run as `lax.ragged_dot`, which XLA expands "
        f"to one dense product for each of the {groups} groups",
        RuntimeWarning, stacklevel=3,
    )


# at or under this many tokens every held expert takes every token (weight 0 where it was
# not chosen): a group's tile in the grouped products is 128 rows at the least, so the
# sort would buy nothing, and a decode step is bound by reading the weights either way
DENSE_TOKENS = 128


def _experts_dense(p, u, ids, w, spec: LFM2Spec):
    """Few tokens (a decode step): every held expert over all of them, as batched products."""
    e0, held = spec.experts_held
    with jax.named_scope("router"):
        chosen = ids[:, :, None] == (e0 + jnp.arange(held))[None, None]  # [N, k, held]
        weight = jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)  # [N, held]
        group_sizes = chosen.sum(axis=(0, 1)).astype(jnp.int32)
    with jax.named_scope("experts"):
        hidden = jax.nn.silu(jnp.einsum("nh,ehf->enf", u, p["w1"])) * jnp.einsum("nh,ehf->enf", u, p["w3"])
        y = jnp.einsum("enf,efh,ne->nh", hidden, p["w2"], weight)
    return y, group_sizes, group_sizes.sum(), {}


def _experts_grouped(p, u, ids, w, spec: LFM2Spec):
    """Many tokens (the update): one sort of the (token, expert) pairs by held expert, the
    absent experts' pairs last, and grouped products over the rows in use."""
    n_tokens, k = ids.shape
    e0, held = spec.experts_held
    with jax.named_scope("router"):
        flat = ids.reshape(-1)
        here = (flat >= e0) & (flat < e0 + held)
        group = jnp.where(here, flat - e0, held)
        order = jnp.argsort(group, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        landed = group_sizes.sum()
        pad = (-n_tokens * k) % 128
        valid = jnp.arange(n_tokens * k + pad) < landed
        rows = _permute(jnp.repeat(u, k, axis=0), order, inverse)
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
    with jax.named_scope("experts"):
        hidden = jax.nn.silu(grouped_matmul(rows, p["w1"], group_sizes, valid)) * grouped_matmul(
            rows, p["w3"], group_sizes, valid)
        out = grouped_matmul(hidden, p["w2"], group_sizes, valid)
    with jax.named_scope("router"):
        out = _permute(out[:n_tokens * k], inverse, order).reshape(n_tokens, k, -1)
        y = jnp.sum(out * w[..., None], axis=1)
        sizes = rows.shape[0], rows.shape[1], p["w1"].shape[2]
        of_the_form = {"tile_fill": tile_fill(group_sizes, gmm_tiling(*sizes)[0]),
                       "grouped_product_passes": jnp.float32(kernel_passes(*sizes))}
    return y, group_sizes, valid.sum(), of_the_form


def expert_layer(p, u, spec: LFM2Spec):
    """``u`` ``[N, H]`` -> the held experts' part of the layer ``[N, H]``, the chosen ids
    ``[N, k]`` and the counters (pairs on held experts, the fullest held expert's load
    over the mean, pairs dropped: those on held experts that no product computed; where
    the products are grouped, the share of their row tiles that pairs fill and the bf16
    passes a product takes in the kernels, 0 where `lax.ragged_dot` takes it)."""
    e0, held = spec.experts_held
    with jax.named_scope("router"):
        ids, w = route(p, u, spec)
    experts = _experts_dense if u.shape[0] <= DENSE_TOKENS else _experts_grouped
    y, group_sizes, computed, of_the_form = experts(p, u, ids, w, spec)
    landed = jnp.sum((ids >= e0) & (ids < e0 + held))
    counters = {
        "pairs_held": landed.astype(jnp.float32),
        "max_load": group_sizes.max().astype(jnp.float32) * held / jnp.maximum(landed, 1).astype(jnp.float32),
        "pairs_dropped": (landed - computed).astype(jnp.float32),
        **of_the_form,
    }
    return y, ids, counters


# ---------------------------------------------------------------------------------
# the trunk
# ---------------------------------------------------------------------------------
def _ffn(p, u, ffn: str, spec: LFM2Spec):
    """``u`` ``[N, H]`` -> (output, chosen ids or None, counters or None)."""
    if ffn == "dense":
        with jax.named_scope("dense_ffn"):
            return swiglu(p, u), None, None
    return expert_layer(p, u, spec)


def _stack_routes(routes):
    """Per-layer (ids ``[N, k]``, counters) -> ids ``[N, layers, k]`` and counters summed
    (``max_load``, ``tile_fill`` and ``grouped_product_passes``: the mean over the layers)."""
    if not routes:
        return None, None
    ids = jnp.stack([r[0] for r in routes], axis=1)
    counters = {name: sum(r[1][name] for r in routes) for name in routes[0][1]}
    for name in ("max_load", "tile_fill", "grouped_product_passes"):  # in this order: a set's changes from run to run
        if name in counters:
            counters[name] = counters[name] / len(routes)
    return ids, counters


def heads(params, x, spec: LFM2Spec):
    x = rms_norm(x, params["norm"], spec.norm_eps)
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]
    with jax.named_scope("value_head"):
        value = (x @ params["value_head"])[..., 0]
    return logits, value


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
    ids, counters = _stack_routes(routes)
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
    ids, counters = _stack_routes(routes)
    return logits, value, new_carry, ids, counters


def parameter_count(spec: LFM2Spec) -> int:
    shapes = jax.eval_shape(lambda: init_params(spec, jax.random.PRNGKey(0)))
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))

