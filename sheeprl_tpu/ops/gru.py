"""Fused LayerNorm-GRU step — the RSSM's hot op — as a Pallas TPU kernel.

The recurrent core of every Dreamer world model is a LayerNorm-GRU cell stepped
sequentially (reference LayerNormGRUCell, sheeprl/models/models.py:331-411, called
per timestep in dreamer_v3.py:86-97). One step is:

    gates = LN(concat(x, h) @ W + b)         # [B, 3H]
    r, c, u = split(gates)
    h' = sigmoid(u - 1) * tanh(sigmoid(r) * c) + (1 - sigmoid(u - 1)) * h

XLA compiles this as matmul + a chain of elementwise/reduce ops; the Pallas kernel
runs the whole step in ONE VMEM-resident pass — the [B, 3H] gates tensor never
round-trips to HBM between the matmul, the layernorm reduction, and the gating —
which is exactly the fusion the memory-bound sequential scan wants. The kernel tiles
the batch over a grid and keeps the whole ``[K, 3H]`` weight block resident in VMEM,
so it applies only while that block fits the kernel's scoped VMEM next to the
activation tiles: the XS and S Dreamer presets (S: K=1024, H=512, 6 MiB in f32).
From M up (20 MiB) the XLA path runs. See :func:`pallas_gru_applicable`.

``interpret=True`` runs the same kernel on CPU for tests (numerical-parity suite in
tests/test_ops/test_gru_kernel.py); ``chip_smoke.py`` runs it compiled against
:func:`ln_gru_step_reference` at the S shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# VMEM budget for the weight block (bytes); above this the caller takes the XLA
# path. Mosaic gives a kernel 16 MiB of scoped VMEM on v5e unless told otherwise:
# the S block (6 MiB) plus 256-row tiles compiles under it, a 24 MiB block is
# refused ("exceeded scoped vmem limit", measured on v5e, JAX 0.9.0).
PALLAS_GRU_VMEM_WEIGHT_BUDGET = 8 * 1024 * 1024
# TPU vregs are 128 lanes wide. The kernel slices the normalized [B, 3H] gates
# into three [B, H] pieces, so H is held to whole lanes (and K with it, for the
# matmul operand): the shapes verified compiled on the chip are lane-aligned, and
# a toy cell (H=8) has no business in a VMEM-residency kernel.
_LANES = 128


def _ln_gru_kernel(inp_ref, hx_ref, w_ref, b_ref, scale_ref, bias_ref, out_ref, *, eps: float):
    # operands keep their storage dtype (bf16 inputs feed the MXU natively);
    # accumulation and the layernorm/gating chain run in f32. The per-feature
    # vectors arrive as (1, 3H) blocks — TPU tiling wants >=2-D operands.
    # The dot precision is pinned explicitly: Mosaic only lowers DEFAULT/HIGHEST,
    # so inheriting the repo's global jax_default_matmul_precision="high"
    # (bf16_3x) makes the WHOLE kernel fail to lower for TPU — caught by the AOT
    # suite (tests/test_ops/test_tpu_lowering.py). DEFAULT is the MXU-native
    # pass the kernel was designed around (bf16 multiply, f32 accumulate); the
    # fused win is VMEM locality, not multiply precision.
    gates = jnp.dot(
        inp_ref[...],
        w_ref[...],
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT,
    )
    gates = gates + b_ref[...].astype(jnp.float32)
    # LayerNorm over the full 3H feature axis (reference norms the stacked
    # projection before splitting into gates)
    mean = jnp.mean(gates, axis=-1, keepdims=True)
    centered = gates - mean
    var = jnp.mean(jnp.square(centered), axis=-1, keepdims=True)
    normed = centered * jax.lax.rsqrt(var + eps)
    normed = normed * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    hidden = hx_ref[...].astype(jnp.float32)
    H = hidden.shape[-1]
    reset = jax.nn.sigmoid(normed[:, :H])
    cand = jnp.tanh(reset * normed[:, H : 2 * H])
    update = jax.nn.sigmoid(normed[:, 2 * H :] - 1.0)
    out_ref[...] = (update * cand + (1.0 - update) * hidden).astype(out_ref.dtype)


def _pallas_forward(eps, block_b, interpret, inp, hx, w, b, scale, bias) -> jax.Array:
    from jax.experimental import pallas as pl

    B, K = inp.shape
    H = hx.shape[-1]
    block_b = min(block_b, B)
    grid = ((B + block_b - 1) // block_b,)
    # feature vectors ride as (1, 3H): TPU memory tiling is defined over the last
    # two dims, so every operand is kept >=2-D
    return pl.pallas_call(
        functools.partial(_ln_gru_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((B, H), hx.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, K), lambda i: (i, 0)),
            pl.BlockSpec((block_b, H), lambda i: (i, 0)),
            pl.BlockSpec((K, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, H), lambda i: (i, 0)),
        interpret=interpret,
    )(inp, hx, w, b.reshape(1, -1), scale.reshape(1, -1), bias.reshape(1, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_ln_gru(eps, block_b, interpret, inp, hx, w, b, scale, bias):
    return _pallas_forward(eps, block_b, interpret, inp, hx, w, b, scale, bias)


def _fused_fwd(eps, block_b, interpret, inp, hx, w, b, scale, bias):
    out = _pallas_forward(eps, block_b, interpret, inp, hx, w, b, scale, bias)
    return out, (inp, hx, w, b, scale, bias)


def _fused_bwd(eps, block_b, interpret, residuals, g):
    # backward through the mathematically-identical XLA path: the forward keeps the
    # fused VMEM kernel, the (train-only) backward re-derives gradients with XLA's
    # autodiff — pallas_call itself has no reverse rule
    inp, hx, w, b, scale, bias = residuals
    _, vjp = jax.vjp(
        lambda *args: ln_gru_step_reference(*args, eps=eps), inp, hx, w, b, scale, bias
    )
    return vjp(g)


_fused_ln_gru.defvjp(_fused_fwd, _fused_bwd)


def fused_ln_gru_step(
    inp: jax.Array,
    hx: jax.Array,
    w: jax.Array,
    b: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    eps: float = 1e-3,
    block_b: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """One fused LayerNorm-GRU step (differentiable: custom VJP via the XLA math).

    Args: ``inp`` [B, K] (already ``concat([x, h], -1)``), ``hx`` [B, H], ``w``
    [K, 3H], ``b``/``scale``/``bias`` [3H]. Returns the new hidden state [B, H].
    """
    return _fused_ln_gru(float(eps), int(block_b), bool(interpret), inp, hx, w, b, scale, bias)


def ln_gru_step_reference(
    inp: jax.Array,
    hx: jax.Array,
    w: jax.Array,
    b: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    eps: float = 1e-3,
) -> jax.Array:
    """Pure-XLA reference implementation (same math, used for parity tests and as
    the fallback path when the weight block exceeds the VMEM budget)."""
    # same dtype policy as the kernel: native-dtype matmul operands, f32 accumulate
    gates = (
        jax.lax.dot_general(
            inp, w, (((inp.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        + b.astype(jnp.float32)
    )
    mean = jnp.mean(gates, axis=-1, keepdims=True)
    centered = gates - mean
    var = jnp.mean(jnp.square(centered), axis=-1, keepdims=True)
    normed = centered * jax.lax.rsqrt(var + eps)
    normed = normed * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    hidden = hx.astype(jnp.float32)
    H = hidden.shape[-1]
    reset = jax.nn.sigmoid(normed[..., :H])
    cand = jnp.tanh(reset * normed[..., H : 2 * H])
    update = jax.nn.sigmoid(normed[..., 2 * H :] - 1.0)
    return (update * cand + (1.0 - update) * hidden).astype(hx.dtype)


def pallas_gru_applicable(K: int, H: int, itemsize: int = 4) -> bool:
    """Whether the fused kernel takes a ``[K, 3H]`` cell: lane-aligned ``K`` and
    ``H`` (lower bound: nothing narrower than one vreg reaches Mosaic) and a
    weight block inside the VMEM budget (upper bound). Platform selection is NOT
    decided here: LayerNormGRUCell dispatches per lowering platform via
    jax.lax.platform_dependent."""
    return (
        H % _LANES == 0
        and K % _LANES == 0
        and K * 3 * H * itemsize <= PALLAS_GRU_VMEM_WEIGHT_BUDGET
    )
