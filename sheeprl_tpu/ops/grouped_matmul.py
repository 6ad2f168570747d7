"""Grouped matrix products of float32 operands on the TPU, at a stated number of bf16
passes: ``gmm`` (row ``i`` of group ``g`` times ``rhs[g]``, or times its transpose) and
``tgmm`` (a group's rows, transposed, times the same rows of a second matrix: the weight
gradient). The grid, the scalar-prefetched group metadata and the masked stores are
Pallas' megablox kernels' (``jax.experimental.pallas.ops.tpu.megablox``), whose
``make_group_metadata`` is used as it is; what differs is the product inside a tile.

Mosaic's own float32 dot knows one bf16 pass or six. Here a tile is loaded from HBM once,
as float32, and split in VMEM into ``hi = bf16(x)`` and ``lo = bf16(x - hi)``; three
passes are ``hi.hi + hi.lo + lo.hi`` with float32 accumulation (XLA's ``bf16_3x``, what
``jax.default_matmul_precision("high")`` gives every other product), one pass is
``hi.hi``, six is Mosaic's ``HIGHEST``. ``dot_passes`` is that sum in plain ``jax.numpy``:
the kernels call it on their tiles, and off the chip it states what they compute.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

PASSES = (1, 3, 6)
# of the chip's 128 MiB: what a kernel here is compiled with (Mosaic's own default is 16 MiB),
# and what a tiling may plan to hold of it, the rest being Mosaic's own temporaries
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
VMEM_BUDGET_BYTES = 80 * 1024 * 1024
# TPU v5e: 197 TFLOP/s in bf16 over 819 GB/s; a grid step under it waits for HBM
RIDGE_FLOPS_PER_BYTE = 197e12 / 819e9
ROW_TILE, OUT_TILE = 128, 1024


def split_bf16(x):
    """``x`` (float32) -> ``hi, lo`` (bfloat16) with ``hi + lo`` within 2^-16 of ``x``."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def dot_passes(a, b, dimension_numbers, passes: int):
    """``lax.dot_general`` of float32 ``a`` and ``b`` in ``passes`` bf16 passes, float32 out."""
    if passes not in PASSES:
        raise ValueError(f"a float32 product takes 1, 3 or 6 bf16 passes, not {passes}")
    dot = partial(lax.dot_general, dimension_numbers=dimension_numbers, preferred_element_type=jnp.float32)
    if passes == 6:
        return dot(a, b, precision=lax.Precision.HIGHEST)
    # pinned: a kernel's dot that inherits an ambient `high` is one Mosaic refuses to lower
    dot = partial(dot, precision=lax.Precision.DEFAULT)
    a_hi, a_lo = split_bf16(a)
    b_hi, b_lo = split_bf16(b)
    if passes == 1:
        return dot(a_hi, b_hi)
    return (dot(a_hi, b_lo) + dot(a_lo, b_hi)) + dot(a_hi, b_hi)  # the small terms first


def _group_metadata(group_sizes, m: int, tm: int, visit_empty_groups: bool):
    return make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0), num_nonzero_groups=group_sizes.shape[0],
        visit_empty_groups=visit_empty_groups)


def row_tiles_visited(group_sizes, tm: int):
    """How many ``tm``-row tiles ``gmm`` visits: every tile a group has rows in, so a tile
    that two groups share counts twice (the second visit is computed in full, under a mask)."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = (ends + tm - 1) // tm - starts // tm
    return jnp.sum(jnp.where(group_sizes > 0, tiles, 0))


def _rows_of_group(group_offsets, group_ids, m_tile_ids, grid_id, tm: int, width: int):
    """[tm, width] True where the tile's row belongs to the group this grid step works on."""
    group = group_ids[grid_id]
    rows = m_tile_ids[grid_id] * tm + lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return (rows >= group_offsets[group]) & (rows < group_offsets[group + 1])


def _tile(size: int, most: int) -> int:
    """The largest multiple of 128 that divides ``size`` and is at most ``most``; a size
    that no multiple of 128 divides is left whole (the kernels do not take it: off the chip)."""
    return next((t for t in range(min(most, size) // 128 * 128, 0, -128) if size % t == 0), size)


def gmm_vmem_bytes(tiling) -> int:
    """What ``gmm`` holds in VMEM at ``tiling``: both operands' float32 tiles, double-buffered,
    their bf16 halves and the float32 difference the lower half is taken from (16 bytes an
    element); the output tile double-buffered, the accumulator and the three terms (24)."""
    tm, tk, tn = tiling
    return 16 * (tm * tk + tk * tn) + 24 * tm * tn


def gmm_flops_per_byte(tiling, passes: int, whole_k: bool) -> float:
    """A grid step's MXU FLOPs over its HBM bytes. With the contraction whole in one tile
    the group's weights stay in VMEM from one row tile to the next (the block index does not
    change, so the pipeline fetches nothing), and a step moves its rows in and its result out."""
    tm, tk, tn = tiling
    return 2.0 * passes * tm * tk * tn / (4 * (tm * tk + tm * tn) + (0 if whole_k else 4 * tk * tn))


def gmm_tiling(m: int, k: int, n: int):
    """(tm, tk, tn) of ``gmm`` for [m, k] x [groups, k, n]. The contraction whole where VMEM
    allows, so that a step's bytes are the rows' tile alone; then `ROW_TILE` rows lose
    nothing to 512 and fill the tiles at the groups' ends; ``tn`` up to `OUT_TILE`, past
    which nothing was gained on the chip but seconds of compile time. A width like 1408 =
    11 x 128, whose only such divisor is 128 lanes, is taken whole where it fits with the
    contraction whole: at 128 lanes a row tile is read ``n / 128`` times and a step sits
    under the ridge (on the chip, [12288, 2048] x [8, 2048, 1408]: 1.07 ms against 0.73)."""
    tm, tn = _tile(m, ROW_TILE), _tile(n, OUT_TILE)
    if tn == 128 < n and gmm_vmem_bytes((tm, k, n)) <= VMEM_BUDGET_BYTES:
        tn = n
    fits = [tk for tk in (k, _tile(k, k // 2), _tile(k, 512)) if gmm_vmem_bytes((tm, tk, tn)) <= VMEM_BUDGET_BYTES]
    return tm, (fits[0] if fits else _tile(k, 128)), tn


def tgmm_vmem_bytes(tiling) -> int:
    """What ``tgmm`` holds: the two row tiles as ``gmm`` holds its operands (16 bytes an
    element), the output tile double-buffered, the accumulator and the three terms (24)."""
    tm, tk, tn = tiling
    return 16 * tm * (tk + tn) + 24 * tk * tn


def tgmm_flops_per_byte(tiling, passes: int) -> float:
    """A grid step's MXU FLOPs over its HBM bytes: both row tiles in; the result leaves once a group."""
    tm, tk, tn = tiling
    return 2.0 * passes * tm * tk * tn / (4 * tm * (tk + tn))


def tgmm_tiling(m: int, k: int, n: int):
    """(tm, tk, tn) of ``tgmm`` for [m, k]^T x [m, n]: a step's intensity does not depend on
    ``tm``, so the row tile is `ROW_TILE`; the rows are read once for each output tile of
    the other operand, so the output tile is as large as VMEM allows."""
    tm = _tile(m, ROW_TILE)
    fits = [(tk, tn) for tk, tn in ((k, n), (_tile(k, k // 2), n), (_tile(k, k // 2), _tile(n, n // 2)), (_tile(k, 512), _tile(n, 512)))
            if tgmm_vmem_bytes((tm, tk, tn)) <= VMEM_BUDGET_BYTES]
    return (tm, *fits[0]) if fits else (tm, _tile(k, 128), _tile(n, 128))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT_BYTES)


@partial(jax.jit, static_argnames=("tiling", "passes", "transpose_rhs", "interpret"))
def gmm(lhs, rhs, group_sizes, tiling, passes: int, transpose_rhs: bool = False, interpret: bool = False):
    """``lhs`` [m, k] sorted by group, ``rhs`` [groups, k, n] (or [groups, n, k] with
    ``transpose_rhs``), ``group_sizes`` int32 -> [m, n] float32. Rows past
    ``sum(group_sizes)`` are not computed and hold whatever the buffer held."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    if m % tm or k % tk or n % tn:
        raise ValueError(f"the tiling {tiling} does not divide [m, k, n] = {[m, k, n]}")
    tiles_k = k // tk
    (group_offsets, group_ids, m_tile_ids), num_active_tiles = _group_metadata(group_sizes, m, tm, False)
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def kernel(group_offsets, group_ids, m_tile_ids, lhs, rhs, out, acc):
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += dot_passes(lhs[...], rhs[...], dims, passes)

        @pl.when(k_i == tiles_k - 1)
        def _store():  # a tile two groups share is visited once for each: keep the other's rows
            mask = _rows_of_group(group_offsets, group_ids, m_tile_ids, grid_id, tm, tn)
            out[...] = jnp.where(mask, acc[...], out[...])

    def lhs_index(n_i, grid_id, k_i, group_offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], k_i

    def rhs_index(n_i, grid_id, k_i, group_offsets, group_ids, m_tile_ids):
        return (group_ids[grid_id], n_i, k_i) if transpose_rhs else (group_ids[grid_id], k_i, n_i)

    def out_index(n_i, grid_id, k_i, group_offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((None, tn, tk) if transpose_rhs else (None, tk, tn), rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(n // tn, num_active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="grouped_matmul",
    )(group_offsets, group_ids, m_tile_ids, lhs, rhs)


@partial(jax.jit, static_argnames=("tiling", "passes", "interpret"))
def tgmm(lhs, rhs, group_sizes, tiling, passes: int, interpret: bool = False):
    """``lhs`` [m, k] and ``rhs`` [m, n], both sorted by group -> [groups, k, n] float32:
    ``lhs[rows of g].T @ rhs[rows of g]``, zeros for an empty group."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tm, tk, tn = tiling
    if m % tm or k % tk or n % tn:
        raise ValueError(f"the tiling {tiling} does not divide [m, k, n] = {[m, k, n]}")
    groups = group_sizes.shape[0]
    (group_offsets, group_ids, m_tile_ids), num_active_tiles = _group_metadata(group_sizes, m, tm, True)
    dims = (((0,), (0,)), ((), ()))

    def kernel(group_offsets, group_ids, m_tile_ids, lhs, rhs, out, acc):
        grid_id, last = pl.program_id(2), pl.num_programs(2) - 1
        group = group_ids[grid_id]

        @pl.when((grid_id == 0) | (group_ids[jnp.maximum(grid_id - 1, 0)] != group))
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(group_offsets[group + 1] > group_offsets[group])
        def _accumulate():  # the tile's rows of other groups, and of none, count as 0
            rows = partial(_rows_of_group, group_offsets, group_ids, m_tile_ids, grid_id, tm)
            acc[...] += dot_passes(
                jnp.where(rows(tk), lhs[...], 0.0), jnp.where(rows(tn), rhs[...], 0.0), dims, passes)

        @pl.when((grid_id == last) | (group_ids[jnp.minimum(grid_id + 1, last)] != group))
        def _store():
            out[...] = acc[...]

    def lhs_index(n_i, k_i, grid_id, group_offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], k_i

    def rhs_index(n_i, k_i, grid_id, group_offsets, group_ids, m_tile_ids):
        return m_tile_ids[grid_id], n_i

    def out_index(n_i, k_i, grid_id, group_offsets, group_ids, m_tile_ids):
        return group_ids[grid_id], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index), pl.BlockSpec((tm, tn), rhs_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(n // tn, k // tk, num_active_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="grouped_matmul_transposed",
    )(group_offsets, group_ids, m_tile_ids, lhs, rhs)
