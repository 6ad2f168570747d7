"""The `laguna` trunk as a sequence-model policy (Laguna-XS.2's block: RMSNorm, grouped-query
attention over a sliding window of keys or over every earlier position by the layer's kind, with
a sigmoid gate a head on its output, then a dense SwiGLU in the leading layer and a sparse expert
layer with an ungated shared expert in the rest), as pure functions over a parameter tree, on the
shared layers (``models/lm_layers.py``).

The layers' properties come from the published lists, one entry a layer: ``layer_types``
(``full_attention`` or ``sliding_attention``), ``mlp_layer_types`` (``dense`` or ``sparse``) and
``num_attention_heads_per_layer``; every layer has ``num_key_value_heads`` key/value heads of
``head_dim`` channels, and ``rope_parameters`` gives each kind its rotary law (YaRN over a part of
the head on the full layers, the default law over the whole head on the sliding ones).

A head reads the keys ``j`` of a query ``i`` with ``j <= i`` on a full layer and ``i - W < j <= i``
on a sliding one (``W = sliding_window``, the query's own key among them); softmax over
``q k / sqrt(head_dim)``; then ``o_h <- sigmoid(u W_g)_h o_h`` (`output_gate`, ``u`` the layer's
normed input) before ``W_o``. Both kinds have two forms that agree:

- one step (the rollout): a full layer writes its rotated key and value at row ``t`` of a cache of
  ``max_seq_len`` rows and reads the rows up to ``t`` (`lm_layers.kv_cache_step`); a sliding layer
  keeps a RING of ``W`` rows, writes at slot ``t mod W`` the key rotated at ``t`` and reads the
  ``min(t + 1, W)`` slots written (`swa_step`): softmax does not care about the order of keys, so
  the ring is never reordered;
- whole sequences (the update): query blocks of ``QUERY_BLOCK`` (``W`` where the window is
  smaller) read only the keys their mask can reach, each block recomputed in a backward pass: a
  sliding layer's block ``[lo, hi)`` the keys ``[lo - W, hi)``, its own block and the one before
  (`swa_forward`), a full layer's every key up to ``hi`` (`full_forward`). No ``[T, T]`` score
  matrix is formed, and the result is exact.

The expert layer is `lm_layers.expert_layer` with this trunk's properties: sigmoid scores, the
top-k of ``s + b`` with the bias held at 0, the k weights over their sum times
``moe_routed_scaling_factor``, one ungated shared expert of ``shared_expert_intermediate_size``.

The parts carry ``jax.named_scope`` names (``embed``, ``swa`` with ``swa_attend`` inside it around
the ring's read or the band, ``full_attn`` with ``attend`` inside it, ``router``, ``experts``,
``shared_expert``, ``dense_ffn``, ``lm_head``, ``value_head``), which a profiler capture shows on
each op and which change no program (names are metadata). The decode step reports
``swa/ring_rows_read``: the ring rows a sliding layer's step reads, ``W`` (the slots not yet written
are read and masked), which a ring that grew with the episode, or a step that read only the slots
written, would move.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.models import lm_layers
from sheeprl_tpu.models.lm_layers import INIT_STD, attend, rms_core, stack_routes, swiglu

KINDS = ("full_attention", "sliding_attention")
SCOPES = {"full_attention": ("full_attn", "attend"), "sliding_attention": ("swa", "swa_attend")}
# queries a block of the whole-sequence attention: a block's scores are [B, heads, block, keys]
QUERY_BLOCK = 512


@dataclass(frozen=True)
class LagunaSpec:
    """The sizes as run. The three lists have an entry a layer held; ``experts_held`` is ``(first
    expert, count)`` of the ``num_experts`` the router scores; ``vocab_size`` is the slice of the
    vocabulary held; ``rope_parameters`` holds ``(layer kind, its published keys as pairs)``."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_key_value_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    num_attention_heads_per_layer: Tuple[int, ...]
    sliding_window: int
    rope_parameters: Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...]
    num_experts: int
    num_experts_per_tok: int
    experts_held: Tuple[int, int]
    routed_scaling_factor: float
    norm_eps: float = 1e-6
    max_seq_len: int = 512
    # the expert layer's properties (`lm_layers.expert_layer`)
    router_scoring: str = "sigmoid_bias"
    shared_expert: bool = True
    shared_expert_gate: bool = False  # the shared expert is one ungated SwiGLU

    def __post_init__(self):
        e0, n = self.experts_held
        if not (0 <= e0 and n >= 1 and e0 + n <= self.num_experts):
            raise ValueError(f"experts_held {self.experts_held} is no range of the {self.num_experts} routed experts")
        lists = (self.layer_types, self.mlp_layer_types, self.num_attention_heads_per_layer)
        if len({len(x) for x in lists}) != 1:
            raise ValueError("layer_types, mlp_layer_types and num_attention_heads_per_layer need an entry a layer each")
        unknown = (set(self.layer_types) - set(KINDS)) | (set(self.mlp_layer_types) - {"dense", "sparse"})
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if any(n % self.num_key_value_heads for n in self.num_attention_heads_per_layer):
            raise ValueError("key/value heads must divide every layer's query heads")
        missing = set(self.layer_types) - {kind for kind, _ in self.rope_parameters}
        if missing:
            raise ValueError(f"rope_parameters has no entry for {sorted(missing)}")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def layers(self):
        """(attention kind, feed-forward kind, query heads) a layer held."""
        return list(zip(self.layer_types, self.mlp_layer_types, self.num_attention_heads_per_layer))

    def rope_of(self, kind: str) -> Dict[str, Any]:
        return dict(dict(self.rope_parameters)[kind])

    @property
    def ring_rows(self) -> int:
        """Rows of a sliding layer's ring: the window, or the whole episode where it is shorter."""
        return min(self.sliding_window, self.max_seq_len)

    @property
    def cache_bytes_per_sequence(self) -> int:
        """float32 bytes of the carry a sequence holds: keys and values of ``max_seq_len`` rows a
        full layer, of `ring_rows` a sliding one."""
        rows = sum(self.max_seq_len if kind == "full_attention" else self.ring_rows for kind in self.layer_types)
        return 4 * 2 * rows * self.num_key_value_heads * self.head_dim

    @classmethod
    def from_cfg(cls, lm: Any, vocab_size: int, max_seq_len: int) -> "LagunaSpec":
        kinds = tuple(str(t) for t in lm.layer_types)
        if int(lm.num_hidden_layers) != len(kinds):
            raise ValueError(f"num_hidden_layers {lm.num_hidden_layers} is not the {len(kinds)} entries of layer_types")
        rope = tuple(sorted((str(kind), tuple(sorted((str(k), v) for k, v in group.items())))
                            for kind, group in lm.rope_parameters.items() if kind in KINDS))
        return cls(
            vocab_size=int(vocab_size), hidden_size=int(lm.hidden_size), intermediate_size=int(lm.intermediate_size),
            moe_intermediate_size=int(lm.moe_intermediate_size),
            shared_expert_intermediate_size=int(lm.shared_expert_intermediate_size),
            num_key_value_heads=int(lm.num_key_value_heads), head_dim=int(lm.head_dim), layer_types=kinds,
            mlp_layer_types=tuple(str(t) for t in lm.mlp_layer_types),
            num_attention_heads_per_layer=tuple(int(n) for n in lm.num_attention_heads_per_layer),
            sliding_window=int(lm.sliding_window), rope_parameters=rope, num_experts=int(lm.num_experts),
            num_experts_per_tok=int(lm.num_experts_per_tok),
            experts_held=(int(lm.experts_held[0]), int(lm.experts_held[1])),
            routed_scaling_factor=float(lm.moe_routed_scaling_factor), norm_eps=float(lm.rms_norm_eps),
            max_seq_len=int(max_seq_len),
        )


# ---------------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------------
def init_params(spec: LagunaSpec, key: jax.Array) -> Dict[str, Any]:
    """N(0, 0.02) matrices, unit norm weights, the router's score-correction bias 0 (a buffer:
    it enters the choice, gets no gradient, and is held at 0)."""
    h, d, nkv = spec.hidden_size, spec.head_dim, spec.num_key_value_heads
    count = [0]

    def normal(*shape):
        count[0] += 1
        return INIT_STD * jax.random.normal(jax.random.fold_in(key, count[0]), shape, jnp.float32)

    ones = partial(jnp.ones, dtype=jnp.float32)
    params: Dict[str, Any] = {"embed": normal(spec.vocab_size, h)}
    for i, (_, ffn, nq) in enumerate(spec.layers):
        layer: Dict[str, Any] = {"op_norm": ones((h,)), "ffn_norm": ones((h,))}
        layer["op"] = {"wq": normal(h, nq * d), "wk": normal(h, nkv * d), "wv": normal(h, nkv * d),
                       "wg": normal(h, nq), "wo": normal(nq * d, h)}
        if ffn == "dense":
            f = spec.intermediate_size
            layer["ffn"] = {"w1": normal(h, f), "w3": normal(h, f), "w2": normal(f, h)}
        else:
            f, n, fs = spec.moe_intermediate_size, spec.experts_held[1], spec.shared_expert_intermediate_size
            layer["ffn"] = {"router": normal(h, spec.num_experts), "bias": jnp.zeros((spec.num_experts,), jnp.float32),
                            "w1": normal(n, h, f), "w3": normal(n, h, f), "w2": normal(n, f, h),
                            "shared": {"w1": normal(h, fs), "w3": normal(h, fs), "w2": normal(fs, h)}}
        params[f"layer_{i}"] = layer
    params["norm"] = ones((h,))
    params["lm_head"] = normal(h, spec.vocab_size)
    params["value_head"] = normal(h, 1)
    return params


def init_carry(spec: LagunaSpec, batch: int) -> Dict[str, Any]:
    """The state a fresh batch of sequences starts from: position 0, empty caches (a full layer's
    of ``max_seq_len`` rows, a sliding layer's ring of `ring_rows`)."""
    carry: Dict[str, Any] = {"t": jnp.zeros((), jnp.int32)}
    for i, kind in enumerate(spec.layer_types):
        rows = spec.max_seq_len if kind == "full_attention" else spec.ring_rows
        kv = (batch, rows, spec.num_key_value_heads, spec.head_dim)
        carry[f"layer_{i}"] = (jnp.zeros(kv, jnp.float32), jnp.zeros(kv, jnp.float32))
    return carry


# ---------------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------------
def rms_norm(x, weight, eps):
    return rms_core(x, eps) * weight


def rope_frequencies(spec: LagunaSpec, kind: str):
    """(inverse frequencies, attention factor) of a layer kind's ``rope_parameters``: YaRN
    (`lm_layers.yarn_frequencies`) or the default law, over ``partial_rotary_factor`` of the head."""
    rope = spec.rope_of(kind)
    dim = int(spec.head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    if rope.get("rope_type", "default") == "yarn":
        return lm_layers.yarn_frequencies(rope, dim)
    return lm_layers.default_frequencies(float(rope["rope_theta"]), dim), 1.0


def _qkv(p, u, positions, kind: str, spec: LagunaSpec):
    """``u`` ``[B, T, H]`` -> q ``[B, T, nq, d]``, k and v ``[B, T, nkv, d]``; q and k rotated at
    ``positions`` by the layer kind's law."""
    bsz, t, _ = u.shape
    nkv, d = spec.num_key_value_heads, spec.head_dim
    inv, factor = rope_frequencies(spec, kind)
    turn = lambda x: lm_layers.rope(x, positions, inv, factor)  # noqa: E731
    q = (u @ p["wq"]).reshape(bsz, t, -1, d)
    k = (u @ p["wk"]).reshape(bsz, t, nkv, d)
    v = (u @ p["wv"]).reshape(bsz, t, nkv, d)
    return turn(q), turn(k), v


def output_gate(p, u, out):
    """The gate a head: ``out`` ``[..., nq * d]`` with each head's channels times
    ``sigmoid(u W_g)`` ``[..., nq]``."""
    gate = jax.nn.sigmoid(u @ p["wg"])
    heads = out.reshape(*out.shape[:-1], gate.shape[-1], -1)
    return (heads * gate[..., None]).reshape(out.shape)


def _blocked(q, k, v, window, num_kv_heads: int):
    """Whole sequences, a block of queries at a time: block ``[lo, hi)`` reads the keys
    ``[lo - window, hi)`` (every key up to ``hi``: ``window`` None) under ``j <= i`` and, with a
    window, ``i - window < j``; each block is recomputed in a backward pass."""
    t = q.shape[1]
    block = QUERY_BLOCK if window is None else min(window, QUERY_BLOCK)
    outs = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        start = 0 if window is None else max(0, lo - window)
        i, j = np.arange(lo, hi)[:, None], np.arange(start, hi)[None]
        mask = (j <= i) if window is None else (j <= i) & (j > i - window)
        part = jax.checkpoint(lambda q, k, v, mask=mask: attend(q, k, v, mask, num_kv_heads))
        outs.append(part(q[:, lo:hi], k[:, start:hi], v[:, start:hi]))
    return jnp.concatenate(outs, axis=1)


def swa_forward(q, k, v, window: int, num_kv_heads: int):
    """The sliding layer over whole sequences, exactly and banded: ``q`` ``[B, T, nq, d]``, ``k``,
    ``v`` ``[B, T, nkv, d]`` -> ``[B, T, nq * d]``; a query ``i`` reads the keys ``i - window < j <= i``,
    a block of queries its own block of keys and the one before."""
    return _blocked(q, k, v, window, num_kv_heads)


def full_forward(q, k, v, num_kv_heads: int):
    """The full layer over whole sequences: causal, a block of queries at a time."""
    return _blocked(q, k, v, None, num_kv_heads)


def swa_step(ring, q, k, v, t, num_kv_heads: int):
    """One decode step at position ``t`` through a ring ``(keys, values)`` ``[B, R, nkv, d]``:
    this step's ``k`` (already rotated at ``t``) and ``v`` written at slot ``t mod R``, ``q``
    ``[B, 1, nq, d]`` attending over the ``min(t + 1, R)`` slots written -> (``[B, 1, nq * d]``,
    the new ring, the rows read: all ``R``, the slots not yet written masked)."""
    rows = ring[0].shape[1]
    keys = jax.lax.dynamic_update_slice_in_dim(ring[0], k, t % rows, axis=1)
    values = jax.lax.dynamic_update_slice_in_dim(ring[1], v, t % rows, axis=1)
    written = jnp.arange(rows) <= t
    return attend(q, keys, values, written[None], num_kv_heads), (keys, values), rows


def attention(p, u, kind: str, spec: LagunaSpec):
    """Whole sequences ``[B, T, H]`` through one attention layer of ``kind``."""
    q, k, v = _qkv(p, u, jnp.arange(u.shape[1]), kind, spec)
    with jax.named_scope(SCOPES[kind][1]):
        if kind == "sliding_attention":
            out = swa_forward(q, k, v, spec.sliding_window, spec.num_key_value_heads)
        else:
            out = full_forward(q, k, v, spec.num_key_value_heads)
    return output_gate(p, u, out) @ p["wo"]


def attention_step(p, cache, u, t, kind: str, spec: LagunaSpec):
    """One step ``[B, H]`` at position ``t`` through the layer's cache or ring -> (output, the
    new cache, the rows read: every row of either)."""
    q, k, v = _qkv(p, u[:, None], t[None], kind, spec)
    with jax.named_scope(SCOPES[kind][1]):
        if kind == "sliding_attention":
            out, cache, rows = swa_step(cache, q, k, v, t, spec.num_key_value_heads)
        else:
            (out, cache), rows = lm_layers.kv_cache_step(cache, q, k, v, t, spec.num_key_value_heads), cache[0].shape[1]
    return (output_gate(p, u[:, None], out) @ p["wo"])[:, 0], cache, rows


# -- the expert layer (`models/lm_layers.py`), under the names this trunk is known by -----
def route(p, u, spec: LagunaSpec):
    """This trunk's router: sigmoid scores, the top-k of ``s + b``, the normalised weights times
    ``routed_scaling_factor`` (``spec.router_scoring``)."""
    return lm_layers.route(p, u, spec)


def expert_layer(p, u, spec: LagunaSpec):
    """`lm_layers.expert_layer` behind this module's `route` (looked up when the layer is
    traced: a fault planted under that name is the router the layer takes)."""
    return lm_layers.expert_layer(p, u, spec, route)


# ---------------------------------------------------------------------------------
# the trunk
# ---------------------------------------------------------------------------------
def _ffn(p, u, ffn: str, spec: LagunaSpec):
    """``u`` ``[N, H]`` -> (output, chosen ids or None, counters or None)."""
    if ffn == "dense":
        with jax.named_scope("dense_ffn"):
            return swiglu(p, u), None, None
    return expert_layer(p, u, spec)


def heads(params, x, spec: LagunaSpec):
    return lm_layers.heads(params, rms_norm(x, params["norm"], spec.norm_eps))


def forward(params, spec: LagunaSpec, tokens):
    """Whole sequences ``tokens`` ``[B, T]`` -> logits ``[B, T, V]``, values ``[B, T]``, the
    chosen experts ``[B, T, expert layers, k]`` and the layers' counters. Each block is
    recomputed in a backward pass (``jax.checkpoint``), and inside it each block of queries."""
    bsz, t = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes = []
    for i, (kind, ffn, _) in enumerate(spec.layers):

        def block(p, x, kind=kind, ffn=ffn):
            u = rms_norm(x, p["op_norm"], spec.norm_eps)
            with jax.named_scope(SCOPES[kind][0]):
                x = x + attention(p["op"], u, kind, spec)
            u = rms_norm(x, p["ffn_norm"], spec.norm_eps).reshape(bsz * t, -1)
            y, ids, counters = _ffn(p["ffn"], u, ffn, spec)
            return x + y.reshape(bsz, t, -1), ids, counters

        x, ids, counters = jax.checkpoint(block)(params[f"layer_{i}"], x)
        if ids is not None:
            routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    return logits, value, None if ids is None else ids.reshape(bsz, t, *ids.shape[1:]), counters


def step(params, spec: LagunaSpec, carry, tokens):
    """One token a sequence, ``tokens`` ``[B]``, through the carried caches -> logits ``[B, V]``,
    values ``[B]``, the new carry, the chosen experts ``[B, expert layers, k]`` and the layers'
    counters, with ``swa/ring_rows_read``: the ring rows a sliding layer's step read."""
    t = carry["t"]
    new_carry: Dict[str, Any] = {"t": t + 1}
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    routes, ring_rows = [], []
    for i, (kind, ffn, _) in enumerate(spec.layers):
        p, name = params[f"layer_{i}"], f"layer_{i}"
        u = rms_norm(x, p["op_norm"], spec.norm_eps)
        with jax.named_scope(SCOPES[kind][0]):
            y, new_carry[name], rows = attention_step(p["op"], carry[name], u, t, kind, spec)
        if kind == "sliding_attention":
            ring_rows.append(rows)
        x = x + y
        y, ids, counters = _ffn(p["ffn"], rms_norm(x, p["ffn_norm"], spec.norm_eps), ffn, spec)
        x = x + y
        if ids is not None:
            routes.append((ids, counters))
    logits, value = heads(params, x, spec)
    ids, counters = stack_routes(routes)
    if counters is not None and ring_rows:
        counters["swa/ring_rows_read"] = jnp.float32(sum(ring_rows) / len(ring_rows))
    return logits, value, new_carry, ids, counters


def parameter_count(spec: LagunaSpec) -> int:
    return lm_layers.parameter_count(init_params, spec)
