"""What the sequence-model trunks (``models/lfm2.py``, ``models/qwen3_next.py``, ``models/deepseek_v3.py``,
``models/kimi_linear.py``, ``models/laguna.py``) share: the RMS norm's core, RoPE with its two frequency laws
(the default and YaRN's), attention and its KV cache's decode step, SwiGLU, the heads, and the sparse expert
layer that is TOLD which experts it holds, with its dense and grouped paths and its counters.

The expert layer reads its properties from the trunk's spec: ``num_experts``,
``num_experts_per_tok``, ``experts_held = (e0, n)``, ``router_scoring`` (``sigmoid_bias``:
sigmoid scores, the top-k of ``s + b``; ``softmax``: a float32 softmax over all experts),
``routed_scaling_factor`` (times the normalised weights; 1 where a spec states none),
``shared_expert`` (a SwiGLU every token takes, as wide as its weights, behind a sigmoid gate
unless the spec says ``shared_expert_gate`` False; every chip of the deployment computes it alike). It
routes over all ``num_experts``, normalises the k chosen weights over all k, and computes
the part of the result its own experts give. What absent experts would add is left out (another chip's
part; on one chip the layer runs without its exchange). No token is dropped and there is no capacity.

Two paths, one result. A decode step's few tokens go through every held expert
(`DENSE_TOKENS`). The update sorts its (token, expert) pairs by held expert and runs
grouped products over the rows in use, on buffers of `dispatch_rows` rows: a bound that
follows from the share held (`DISPATCH_SLACK` times the pairs of uniform routing, never
more than ``tokens x k``). When more pairs land than a buffer holds, further rounds of the
same products compute the rest, as many as the routing asks for (`_experts_bounded`).
"""

from __future__ import annotations

import math
import warnings
from functools import lru_cache, partial
from typing import Any

import jax
import jax.numpy as jnp

from sheeprl_tpu.ops.grouped_matmul import ROW_TILE, gmm, gmm_tiling, row_tiles_visited, tgmm, tgmm_tiling

INIT_STD = 0.02
WEIGHT_SUM_EPS = 1e-20


# ---------------------------------------------------------------------------------
# small layers
# ---------------------------------------------------------------------------------
def rms_core(x, eps):
    """``x`` over the root of its mean square along the last axis: the norm without its weight."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def rotate_half(x):
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-b, a], axis=-1)


def default_frequencies(theta, dim: int):
    """RoPE's inverse frequencies over ``dim`` rotated channels: ``theta^(-2i / dim)`` a channel pair ``i``."""
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_frequencies(rope_parameters, dim: int):
    """YaRN over ``dim`` rotated channels, from a layer kind's ``rope_parameters`` (``rope_theta``,
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``, ``attention_factor``)
    -> (the inverse frequencies ``[dim / 2]``, the attention factor). As the transformers library's
    ``_compute_yarn_parameters``: ``inv = inv_interp (1 - e) + inv_extrap e`` with ``inv_extrap`` the
    default law's and ``inv_interp = inv_extrap / factor``, ``e = 1 - ramp(low, high)`` over the pairs,
    ``low`` and ``high`` the floor and the ceiling of the channels at which ``beta_fast`` and
    ``beta_slow`` rotations fit in ``original_max_position_embeddings`` positions; the factor, where
    none is given, is ``0.1 ln(factor) + 1``."""
    theta, factor = float(rope_parameters["rope_theta"]), float(rope_parameters["factor"])
    positions = float(rope_parameters["original_max_position_embeddings"])

    def correction(rotations):
        return dim * math.log(positions / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(float(rope_parameters.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction(float(rope_parameters.get("beta_slow", 1)))), dim - 1)
    pos_freqs = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / ((high - low) or 0.001), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    inv = 1.0 / (factor * pos_freqs) * (1.0 - extrapolated) + 1.0 / pos_freqs * extrapolated
    attention_factor = rope_parameters.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv, float(attention_factor)


def rope(x, positions, inv_freq, attention_factor=1.0):
    """``x`` ``[..., T, heads, d]`` at ``positions`` ``[T]``: rotate-half over the first ``2 len(inv_freq)``
    channels of each head at the inverse frequencies ``inv_freq`` (`default_frequencies` for the default law,
    `yarn_frequencies` for YaRN's), the rest untouched; ``attention_factor`` (YaRN's; 1 for the default law)
    scales the rotated channels' cos and sin, and only theirs."""
    d = 2 * inv_freq.shape[0]
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    scaled = (lambda c: c) if attention_factor == 1.0 else (lambda c: c * attention_factor)  # noqa: E731
    if d == x.shape[-1]:
        return x * scaled(jnp.cos(angles)) + rotate_half(x) * scaled(jnp.sin(angles))
    turned, kept = x[..., :d], x[..., d:]
    return jnp.concatenate([turned * scaled(jnp.cos(angles)) + rotate_half(turned) * scaled(jnp.sin(angles)), kept], axis=-1)


def attend(q, k, v, mask, num_kv_heads: int):
    """Grouped-query attention: ``q`` ``[B, Tq, nq, d]`` on ``k``, ``v`` ``[B, Tk, nkv, d]``,
    query head ``i`` reads key/value head ``i // group``; ``mask`` ``[Tq, Tk]`` is True
    where a query may look. Scores over ``sqrt(d)`` -> ``[B, Tq, nq * d]``."""
    bsz, tq, nq, d = q.shape
    q = q.reshape(bsz, tq, num_kv_heads, nq // num_kv_heads, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(jnp.float32(d))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(bsz, tq, nq * d)


def kv_cache_step(cache, q, k, v, t, num_kv_heads: int):
    """One decode step at position ``t`` through a KV cache ``(keys, values)`` ``[B, S, nkv, d]``:
    this step's ``k``, ``v`` ``[B, 1, nkv, d]`` written at row ``t``, ``q`` ``[B, 1, nq, d]``
    attending over the rows up to ``t`` -> (``[B, 1, nq * d]``, the new cache)."""
    keys = jax.lax.dynamic_update_slice_in_dim(cache[0], k, t, axis=1)
    values = jax.lax.dynamic_update_slice_in_dim(cache[1], v, t, axis=1)
    mask = (jnp.arange(keys.shape[1]) <= t)[None]
    return attend(q, keys, values, mask, num_kv_heads), (keys, values)


def swiglu(p, u):
    return (jax.nn.silu(u @ p["w1"]) * (u @ p["w3"])) @ p["w2"]


def heads(params, x):
    """``x``: the final hidden state, normed -> the logits over the vocabulary held, the value."""
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]
    with jax.named_scope("value_head"):
        value = (x @ params["value_head"])[..., 0]
    return logits, value


# ---------------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------------
def route(p, u, spec: Any):
    """``u`` ``[N, H]`` -> the chosen experts ``[N, k]`` and their weights over the sum of all k chosen (``norm_topk_prob``),
    times ``routed_scaling_factor`` where it is not 1. ``sigmoid_bias``: the top-k of ``s + b``, weights ``s`` without ``b``
    (LFM2's ``use_expert_bias``; the DeepSeek-V3 block's ``noaux_tc`` with one group); ``softmax``: float32, over all experts."""
    if spec.router_scoring == "softmax":
        s = jax.nn.softmax((u @ p["router"]).astype(jnp.float32), axis=-1)
        ids = jax.lax.top_k(s, spec.num_experts_per_tok)[1]
    else:
        s = jax.nn.sigmoid(u @ p["router"])
        ids = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"]), spec.num_experts_per_tok)[1]
    w, scale = jnp.take_along_axis(s, ids, axis=-1), getattr(spec, "routed_scaling_factor", 1.0)
    w = w / (w.sum(axis=-1, keepdims=True) + WEIGHT_SUM_EPS)
    return ids, w if scale == 1.0 else w * scale


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
# which `cli.py` sets from `float32_matmul_precision`): what XLA:TPU gives every `@` of these models
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


# The update's buffers hold this many times the pairs that uniform routing lands on the held
# experts. A constant and no option: the rounds make any bound exact, so it decides cost alone
# (rows that carry nothing against a second round of the products). On the `qwen3_next` cell the
# pairs that land are within 2% of uniform's (`moe/update_dispatch_fill` 0.51 on the chip), so 2 runs
# one round until the routing sends this chip twice its share; no other value was measured (PERF.md,
# section 7).
DISPATCH_SLACK = 2.0


def dispatch_rows(spec: Any, tokens: int) -> int:
    """Rows of the buffers the update's grouped products run on for ``tokens`` tokens:
    `DISPATCH_SLACK` times the pairs that land on the held experts under uniform routing
    (``tokens x k x held / num_experts``), never more than ``tokens x k`` (the static worst
    case), in whole row tiles of the grouped kernels."""
    worst = -(-tokens * spec.num_experts_per_tok // ROW_TILE) * ROW_TILE
    expected = tokens * spec.num_experts_per_tok * spec.experts_held[1] / spec.num_experts
    return min(worst, max(1, math.ceil(DISPATCH_SLACK * expected / ROW_TILE)) * ROW_TILE)


def _experts_dense(p, u, ids, w, spec: Any):
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


def _sorted_pairs(ids, spec: Any):
    """The (token, expert) pairs sorted by held expert, the absent experts' pairs last:
    (the pairs' order, its inverse, the held experts' pair counts)."""
    e0, held = spec.experts_held
    flat = ids.reshape(-1)
    here = (flat >= e0) & (flat < e0 + held)
    group = jnp.where(here, flat - e0, held)
    order = jnp.argsort(group, stable=True)
    inverse = jnp.argsort(order)
    group_sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    return order, inverse, group_sizes


def _swiglu_grouped(p, rows, group_sizes, valid):
    hidden = jax.nn.silu(grouped_matmul(rows, p["w1"], group_sizes, valid)) * grouped_matmul(
        rows, p["w3"], group_sizes, valid)
    return grouped_matmul(hidden, p["w2"], group_sizes, valid)


def _grouped_counters(p, buffer_rows: int, group_sizes, landed, ran):
    """The counters of a grouped path whose products ran on buffers of ``buffer_rows`` rows,
    ``ran`` rows in all."""
    sizes = buffer_rows, p["w1"].shape[1], p["w1"].shape[2]
    return {"tile_fill": tile_fill(group_sizes, gmm_tiling(*sizes)[0]),
            "grouped_product_passes": jnp.float32(kernel_passes(*sizes)),
            "dispatch_fill": landed.astype(jnp.float32) / jnp.asarray(ran, jnp.float32)}


# -- the bounded dispatch ---------------------------------------------------------------
# A round works on `bound` rows of the sorted pairs: `spread` brings their tokens' rows in,
# the grouped products run, `collect` adds each token's weighted results up. The two are
# each other's transpose, and both are gathers: a token's pairs are looked up by where the
# sort put them (`at` [N, k], `bound` where the pair is not of this round), so that no
# scatter-add is taken either way.
@jax.custom_vjp
def _spread(u, tokens, at):
    """``u`` ``[N, H]`` -> the round's rows ``[bound, H]``: row ``r`` is ``u[tokens[r]]``."""
    return u[tokens]


def _spread_fwd(u, tokens, at):
    return u[tokens], (tokens, at)


def _spread_bwd(res, g):
    tokens, at = res
    return _collect(g, tokens, at), None, None


_spread.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def _collect(rows, tokens, at):
    """The round's rows ``[bound, H]`` -> ``[N, H]``: token ``n`` gets the sum of its rows,
    ``rows[at[n, j]]`` over its k pairs (one gather a pair; a pair of another round, of an
    absent expert or of no group reads a row of zeros)."""
    padded = jnp.concatenate([rows, jnp.zeros_like(rows[:1])], axis=0)
    out = padded[at[:, 0]]
    for j in range(1, at.shape[1]):
        out = out + padded[at[:, j]]
    return out


def _collect_fwd(rows, tokens, at):
    return _collect(rows, tokens, at), (tokens, at)


def _collect_bwd(res, g):
    tokens, at = res
    return _spread(g, tokens, at), None, None


_collect.defvjp(_collect_fwd, _collect_bwd)


def _round(p, u, w_sorted, r, route, bound: int):
    """Round ``r`` of the bounded dispatch: the sorted pairs ``[r * bound, (r + 1) * bound)``
    through the held experts -> their part of the layer's result ``[N, H]``, and the pairs
    the round's products were given (the groups' sizes they ran on)."""
    tokens_sorted, position, starts, ends, landed = route
    lo = r * bound
    with jax.named_scope("router"):
        tokens = jax.lax.dynamic_slice(tokens_sorted, (lo,), (bound,))
        weight = jax.lax.dynamic_slice(w_sorted, (lo,), (bound,))
        valid = lo + jnp.arange(bound) < landed
        group_sizes = (jnp.clip(ends, lo, lo + bound) - jnp.clip(starts, lo, lo + bound)).astype(jnp.int32)
        local = position - lo
        at = jnp.where((local >= 0) & (local < bound) & (position < landed), local, bound)
        rows = _spread(u, tokens, at)
    with jax.named_scope("experts"):
        out = _swiglu_grouped(p, rows, group_sizes, valid)
    with jax.named_scope("router"):
        return _collect(out * weight[:, None], tokens, at), group_sizes.sum()


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rounds(p, u, w_sorted, route, rounds, bound: int):
    """``rounds`` rounds of `_round`: their results added up, and the pairs their products
    were given, counted as the loop ran. The number of rounds is the routing's, known on the
    device only: the loops here, forward and backward, run as many as it says (the first
    outside them, so that at uniform routing nothing is added to anything), which the reverse
    mode of a `lax.scan` over the most there can be would keep every round's rows for."""
    return _rounds_fwd(p, u, w_sorted, route, rounds, bound)[0]


def _rounds_fwd(p, u, w_sorted, route, rounds, bound: int):
    first, pull, pairs = jax.vjp(
        lambda p, u, w_sorted: _round(p, u, w_sorted, 0, route, bound), p, u, w_sorted, has_aux=True)

    def further(r, so_far):
        y, pairs = _round(p, u, w_sorted, r, route, bound)
        return so_far[0] + y, so_far[1] + pairs

    return jax.lax.fori_loop(1, rounds, further, (first, pairs)), (pull, p, u, w_sorted, route, rounds)


def _rounds_bwd(bound: int, res, g):
    pull, p, u, w_sorted, route, rounds = res
    g = g[0]  # the count of pairs carries no gradient

    def further(r, grads):
        _, pull_r, _ = jax.vjp(
            lambda p, u, w_sorted: _round(p, u, w_sorted, r, route, bound), p, u, w_sorted, has_aux=True)
        return jax.tree_util.tree_map(jnp.add, grads, pull_r(g))

    grads = jax.lax.fori_loop(1, rounds, further, pull(g))
    return (*grads, None, None)


_rounds.defvjp(_rounds_fwd, _rounds_bwd)


def _experts_bounded(p, u, ids, w, spec: Any, bound: int):
    """Many tokens (the update), buffers of ``bound`` rows: the same sort, then as many
    rounds of ``bound`` sorted pairs as the pairs that landed ask for. EVERY pair on a held
    expert is computed at any routing: at uniform routing one round, at the worst (every
    token on held experts) ``tokens x k / bound`` of them."""
    n_tokens, k = ids.shape
    weights = {name: p[name] for name in ("w1", "w3", "w2")}
    with jax.named_scope("router"):
        order, inverse, group_sizes = _sorted_pairs(ids, spec)
        landed = group_sizes.sum()
        most = -(-n_tokens * k // bound)
        rounds = jnp.clip(-(-landed // bound), 1, most).astype(jnp.int32)
        pad = most * bound - n_tokens * k
        tokens_sorted = jnp.pad((order // k).astype(jnp.int32), (0, pad))
        w_sorted = jnp.pad(_permute(w.reshape(-1), order, inverse), (0, pad))
        ends = jnp.cumsum(group_sizes)
        route = (tokens_sorted, inverse.reshape(n_tokens, k).astype(jnp.int32), ends - group_sizes, ends, landed)
    y, computed = _rounds(weights, u, w_sorted, route, rounds, bound)
    with jax.named_scope("router"):
        of_the_form = _grouped_counters(p, bound, group_sizes, landed, rounds * bound)
    return y, group_sizes, computed, of_the_form


def expert_layer(p, u, spec: Any, route=route):
    """``u`` ``[N, H]`` -> the held experts' part of the layer ``[N, H]`` (with the shared
    expert's, where the spec has one), the chosen ids ``[N, k]`` and the counters (pairs on
    held experts, the fullest held expert's load over the mean, pairs dropped: those on
    held experts that no product computed; where the products are grouped, the share of
    their row tiles that pairs fill, the bf16 passes a product takes in the kernels, 0
    where `lax.ragged_dot` takes it, and the pairs landed over the rows of the buffers the
    products ran on). ``route`` is the trunk's router (its module's name for `route`)."""
    e0, held = spec.experts_held
    with jax.named_scope("router"):
        ids, w = route(p, u, spec)
    if u.shape[0] <= DENSE_TOKENS:
        y, group_sizes, computed, of_the_form = _experts_dense(p, u, ids, w, spec)
    else:
        y, group_sizes, computed, of_the_form = _experts_bounded(p, u, ids, w, spec, dispatch_rows(spec, u.shape[0]))
    if spec.shared_expert:
        with jax.named_scope("shared_expert"):
            if getattr(spec, "shared_expert_gate", True):
                y = y + jax.nn.sigmoid(u @ p["shared_gate"]) * swiglu(p["shared"], u)
            else:
                y = y + swiglu(p["shared"], u)
    landed = jnp.sum((ids >= e0) & (ids < e0 + held))
    counters = {
        "pairs_held": landed.astype(jnp.float32),
        "max_load": group_sizes.max().astype(jnp.float32) * held / jnp.maximum(landed, 1).astype(jnp.float32),
        "pairs_dropped": (landed - computed).astype(jnp.float32),
        **of_the_form,
    }
    return y, ids, counters


# the counters that are means over the expert layers (the others are sums), in a fixed
# order: a set's order changes from run to run, and with it the program's text
MEAN_COUNTERS = ("max_load", "tile_fill", "grouped_product_passes", "dispatch_fill")


def stack_routes(routes):
    """Per-layer (ids ``[N, k]``, counters) -> ids ``[N, layers, k]`` and counters summed
    (`MEAN_COUNTERS`: the mean over the layers)."""
    if not routes:
        return None, None
    ids = jnp.stack([r[0] for r in routes], axis=1)
    counters = {name: sum(r[1][name] for r in routes) for name in routes[0][1]}
    for name in MEAN_COUNTERS:
        if name in counters:
            counters[name] = counters[name] / len(routes)
    return ids, counters


def parameter_count(init_params, spec: Any) -> int:
    shapes = jax.eval_shape(lambda: init_params(spec, jax.random.PRNGKey(0)))
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
