"""One decode step of multi-head latent attention over a layer's latent cache, as one Pallas
TPU kernel: the absorbed form of ``models/deepseek_v3.py::mla_step`` between the query's
absorption and the value map.

The cache ``[B, S, W]`` (``W`` = the latent's rank + the shared key's rotary width, float32)
stays in HBM and is the kernel's own output (``input_output_aliases``): a step writes back
the one 8-row tile that holds its token's row at ``t`` (the fewest rows a DMA may write: the
other seven as they were read, bit for bit) and passes over nothing else of the buffer. It
reads rows ``0 .. t`` once, a block of `CHUNKS` rows and `SEQUENCES_A_STEP` sequences at a
time, double-buffered from HBM into VMEM, and no block past the position; the row at ``t``
is taken from the VMEM copy of the new row (the read never depends on the write), and rows
past ``t`` in the last block are selected away (they may hold anything, NaN too). A
sequence's running maximum, sum and weighted latents of the softmax stay in VMEM over its
blocks. Both products take ``passes`` bf16 passes of float32 operands split in VMEM
(``ops/grouped_matmul.py::dot_passes``); the softmax is float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sheeprl_tpu.ops.grouped_matmul import dot_passes

# rows of the cache a block brings in, the larger where it divides the positions: on the chip
# at the Moonlight cell's shapes a layer's step took 142 us with 256 rows and 16 sequences a
# block, 157 with 128 and 8, the XLA form 344 (PERF.md, section 5)
CHUNKS = (256, 128)
SEQUENCES_A_STEP = (16, 8)  # sequences a block takes, the larger where it divides the batch
ROWS_A_WRITE = 8  # the fewest rows a DMA writes: the cache's sublane tile
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_SCORES = (((1,), (1,)), ((), ()))  # query [heads, W] . rows [chunk, W] -> [heads, chunk]
_WEIGHED = (((1,), (0,)), ((), ()))  # weights [heads, chunk] . rows [chunk, W] -> [heads, W]


def supports(cache_shape) -> bool:
    """Whether the kernel takes a cache of this shape: its positions whole chunks."""
    return cache_shape[1] % CHUNKS[-1] == 0


def _chunk(positions: int) -> int:
    return next(c for c in CHUNKS if positions % c == 0)


def _sequences_a_step(batch: int) -> int:
    return next((n for n in SEQUENCES_A_STEP if batch % n == 0), batch)


@partial(jax.jit, static_argnames=("passes", "interpret"))
def latent_decode(cache, t, row, query, passes: int, interpret: bool = False):
    """``cache`` ``[B, S, W]``, the position ``t`` (int32), the token's row ``[B, W]`` and the
    scaled absorbed query ``[B, heads, W]`` -> the softmax's weighed latents ``[B, heads, W]``
    and its sum ``[B, heads]`` over rows ``0 .. t`` (both relative to the same running
    maximum), and the cache with the row written at ``t``."""
    batch, positions, width = cache.shape
    heads = query.shape[1]
    if not supports(cache.shape):
        raise ValueError(f"the cache's {positions} positions are no whole number of {CHUNKS[-1]}-row chunks")
    chunk, tile = _chunk(positions), _sequences_a_step(batch)

    def kernel(t_ref, cache_ref, row_ref, query_ref, weighed_ref, total_ref, rows_ref, most, total, weighed):
        i, t = pl.program_id(1), t_ref[0]
        last = t // chunk

        @pl.when(i == 0)
        def _():
            most[...] = jnp.full(most.shape, -jnp.inf)
            total[...] = jnp.zeros(total.shape)
            weighed[...] = jnp.zeros(weighed.shape)

        @pl.when(i <= last)
        def _():
            at = i * chunk + lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            written = i * chunk + lax.broadcasted_iota(jnp.int32, (1, chunk), 1) <= t

            def one_sequence(j, _):  # the row at t from the VMEM copy; rows past t selected away, NaN or not
                block = jnp.where(at == t, row_ref[j], jnp.where(at < t, cache_ref[j], 0.0))
                scores = jnp.where(written, dot_passes(query_ref[j], block, _SCORES, passes), -jnp.inf)
                new_most = jnp.maximum(most[j], scores.max(axis=1, keepdims=True))
                kept, weights = jnp.exp(most[j] - new_most), jnp.exp(scores - new_most)
                most[j] = new_most
                total[j] = total[j] * kept + weights.sum(axis=1, keepdims=True)
                weighed[j] = weighed[j] * kept + dot_passes(weights, block, _WEIGHED, passes)

            lax.fori_loop(0, tile, one_sequence, None)

        @pl.when(i == last)
        def _():
            weighed_ref[...], total_ref[...] = weighed[...], total[...]
            off = pl.multiple_of(t % chunk // ROWS_A_WRITE * ROWS_A_WRITE, ROWS_A_WRITE)
            at = i * chunk + off + lax.broadcasted_iota(jnp.int32, (1, ROWS_A_WRITE, 1), 1)
            rows_ref[...] = jnp.where(at == t, row_ref[...], cache_ref[:, pl.ds(off, ROWS_A_WRITE), :])

    def sequences(g, i, t_ref):
        return g, 0, 0

    def rows(g, i, t_ref):  # past the last written row's chunk the block stays put: nothing is read
        return g, jnp.minimum(i, t_ref[0] // chunk), 0

    def written_rows(g, i, t_ref):
        return g, t_ref[0] // ROWS_A_WRITE, 0

    weighed, total, cache = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((batch, heads, width), jnp.float32),
                   jax.ShapeDtypeStruct((batch, heads, 1), jnp.float32),
                   jax.ShapeDtypeStruct(cache.shape, cache.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tile, chunk, width), rows),
                      pl.BlockSpec((tile, 1, width), sequences),
                      pl.BlockSpec((tile, heads, width), sequences)],
            out_specs=[pl.BlockSpec((tile, heads, width), sequences),
                       pl.BlockSpec((tile, heads, 1), sequences),
                       pl.BlockSpec((tile, ROWS_A_WRITE, width), written_rows)],
            grid=(batch // tile, positions // chunk),
            scratch_shapes=[pltpu.VMEM((tile, heads, 1), jnp.float32), pltpu.VMEM((tile, heads, 1), jnp.float32),
                            pltpu.VMEM((tile, heads, width), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT_BYTES),
        input_output_aliases={1: 2},  # the cache (after the prefetched position) is the third output
        interpret=interpret,
        name="latent_decode",
    )(jnp.reshape(t, (1,)).astype(jnp.int32), cache, row[:, None], query)
    return weighed, total[..., 0], cache
