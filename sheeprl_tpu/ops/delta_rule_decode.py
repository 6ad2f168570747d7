"""One decode step of the gated delta rule (``models/qwen3_next.py::delta_rule_step``, and
``models/kimi_linear.py::kda_step``) as one Pallas TPU kernel: per sequence and value head,
with ``S`` the ``[dk, dv]`` matrix state, ``S <- Diag(exp(g)) S``; ``r = S^T k``;
``S <- S + k (beta (v - r))^T``; ``o = S^T q``. The decay ``g`` is a scalar a head
(``[B, H]``: Qwen3-Next's) or a vector over the key channels (``[B, H, dk]``: Kimi delta
attention's), which then scales the state's rows; the two are separate paths of the kernel,
chosen by ``g.ndim`` when it is traced.

The state ``[B, H, dk, dv]`` (float32) stays in HBM and is the kernel's own output
(``input_output_aliases``), so a scan that carries it updates it in place. A block of
`SEQUENCES_A_BLOCK` sequences x `HEADS_A_BLOCK` heads is brought into VMEM once (double-
buffered by Pallas' pipeline), every line of the rule is made there in float32 on the
vector unit (the two reads are multiplies and sums over ``dk``, as XLA's fusions make them),
and the new state goes back to the same HBM buffer: each element crosses HBM once in and once
out a step. ``k`` and ``q`` arrive as rows ``[heads, dk]``; a sequence's rows are transposed
once into columns (``dk`` over sublanes) and a head's column is broadcast over the ``dv``
lanes, which is how the rule's products over ``dk`` and its outer product meet a state laid
out ``[dk, dv]``; a decay over the key channels arrives as rows ``[heads, dk]`` too and is
transposed with them. On the chip at the Qwen3-Next cell's shapes (a layer's ``[64, 32, 128, 128]``)
a step took 427 us with blocks of 2 x 16 (a head's row transposed on its own: 442, and 434
at its best blocks, 1 x 32; the vector unit's reads against three bf16 passes on the matrix
unit: 427 either way), the XLA form 600 (PERF.md, section 5).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SEQUENCES_A_BLOCK = (2, 1)  # the larger where it divides the batch: 4 MB of state a block at the cell's shapes
HEADS_A_BLOCK = (16, 8)  # the larger where it divides the heads; else all heads (a block's sublanes are 8 or whole)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def supports(state_shape) -> bool:
    """Whether the kernel takes a state of this shape: key and value widths whole lane tiles."""
    *_, dk, dv = state_shape
    return len(state_shape) == 4 and dk % LANES == 0 and dv % LANES == 0


def _block(size: int, sizes) -> int:
    return next((n for n in sizes if size % n == 0), size)


@partial(jax.jit, static_argnames=("interpret",))
def delta_rule_decode(state, q, k, v, g, beta, interpret: bool = False):
    """``state`` ``[B, H, dk, dv]``, ``q``, ``k`` ``[B, H, dk]``, ``v`` ``[B, H, dv]``, ``g``
    ``[B, H]`` or ``[B, H, dk]``, ``beta`` ``[B, H]`` -> (``o`` ``[B, H, dv]``, the new state in
    the input's buffer)."""
    batch, heads, dk, dv = state.shape
    if not supports(state.shape):
        raise ValueError(f"a state of {state.shape} has key or value widths that are no whole number of {LANES} lanes")
    tb, th = _block(batch, SEQUENCES_A_BLOCK), _block(heads, HEADS_A_BLOCK)
    per_channel = g.ndim == 3  # a decay a key channel: it scales the state's rows

    def kernel(state_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, new_ref):
        def one_sequence(b, _):
            q_cols, k_cols, vs = q_ref[b].T, k_ref[b].T, v_ref[b]  # [dk, th], [dk, th], [th, dv]
            if per_channel:
                decay_cols, strength = jnp.exp(g_ref[b]).T, pltpu.repeat(beta_ref[b], dv, axis=1)  # [dk, th], [th, dv]
            else:
                # a head's scalars as rows over the lanes (Mosaic broadcasts a value over lanes or sublanes, not both)
                decay, strength = (pltpu.repeat(x, dv, axis=1) for x in (jnp.exp(g_ref[b]), beta_ref[b]))
            for h in range(th):
                k = jnp.broadcast_to(k_cols[:, h:h + 1], (dk, dv))
                if per_channel:
                    s = state_ref[b, h] * jnp.broadcast_to(decay_cols[:, h:h + 1], (dk, dv))
                else:
                    s = state_ref[b, h] * decay[h:h + 1]
                read = jnp.sum(s * k, axis=0, keepdims=True)
                s = s + k * (strength[h:h + 1] * (vs[h:h + 1] - read))
                new_ref[b, h] = s
                o_ref[b, h:h + 1] = jnp.sum(s * jnp.broadcast_to(q_cols[:, h:h + 1], (dk, dv)), axis=0, keepdims=True)

        lax.fori_loop(0, tb, one_sequence, None)

    def rows(width):
        return pl.BlockSpec((tb, th, width), lambda i, j: (i, j, 0))

    matrices = pl.BlockSpec((tb, th, dk, dv), lambda i, j: (i, j, 0, 0))
    scalars = pl.BlockSpec((tb, th, 1), lambda i, j: (i, j, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((batch, heads, dv), jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid=(batch // tb, heads // th),
        in_specs=[matrices, rows(dk), rows(dk), rows(dv), rows(dk) if per_channel else scalars, scalars],
        out_specs=[rows(dv), matrices],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                                             vmem_limit_bytes=VMEM_LIMIT_BYTES),
        input_output_aliases={0: 1},  # the state is the second output
        interpret=interpret,
        name="delta_rule_decode",
    )(state, q, k, v, g if per_channel else g[..., None], beta[..., None])
