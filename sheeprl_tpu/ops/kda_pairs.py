"""Both decayed pair matrices of a chunk of Kimi delta attention (``models/kimi_linear.py::
chunk_kda``) as one Pallas TPU kernel, with a backward kernel of its own: per chunk and head, with
``G`` the decay accumulated since the chunk began (never increasing),
``kk_tj = sum_c k_tc k_jc exp(G_tc - G_jc)`` and ``qk_tj = sum_c q_tc k_jc exp(G_tc - G_jc)`` for
``j <= t`` (0 above the diagonal), ``[chunk, chunk]`` each.

The numerics are `kimi_linear._decayed_pairs`': no exponent of a positive number is formed. Inside
a sub-chunk of ``sub`` tokens a pair's exponent is taken whole and masked before the exponential;
across sub-chunks it is factored at the later sub-chunk's start ``r``:
``(x_t e^{G_t - G_r}) . (k_j e^{G_r - G_j})``, both exponents <= 0, one float32 product on the
matrix unit at ``HIGHEST`` a sub-chunk. The two matrices share ``k`` and ``G``, so each exponent
is made once for both, and each product takes both matrices' rows at once.

A block of `CHUNKS_A_BLOCK` chunk-heads of ``q``, ``k`` and ``G`` ``[chunk, dk]`` is brought into
VMEM once, and everything ``[sub, sub, dk]``-shaped stays there: the forward writes the two
``[chunk, chunk]`` matrices, the backward reads their cotangents and the same three inputs (its
only residuals), makes the exponents again and writes ``dq``, ``dk`` and ``dG``. A sub-chunk's
pairs are made a partner token ``j`` at a time over the ``[sub, dk]`` rows of every sub-chunk,
the product's sum over ``dk`` a column of the result.

The backward uses that the decay enters a pair only as ``G_t - G_j``: for a pair matrix of rows
``x`` and columns ``k``, ``dG = x dx - k dk_columns``, so ``dG`` costs no exponent of its own.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
CHUNKS_A_BLOCK = (8, 4, 2, 1)  # the largest that divides the chunk-heads
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_HIGHEST = lax.Precision.HIGHEST


def supports(shape, sub: int) -> bool:
    """Whether the kernels take inputs of ``shape`` ``[..., chunk, dk]`` in sub-chunks of ``sub``:
    ``dk`` whole lane tiles, the chunk a whole number of sub-chunks of whole sublane tiles."""
    *_, chunk, dk = shape
    return dk % LANES == 0 and sub % SUBLANES == 0 and chunk % sub == 0


def _block(size: int) -> int:
    return next(n for n in CHUNKS_A_BLOCK if size % n == 0)


def _nt(a, b):  # a [r, w] . b [s, w]^T -> [r, s]
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HIGHEST, preferred_element_type=jnp.float32)


def _nn(a, b):  # a [r, w] . b [w, s] -> [r, s]
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=_HIGHEST, preferred_element_type=jnp.float32)


def _tn(a, b):  # a [w, r]^T . b [w, s] -> [r, s]
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=_HIGHEST, preferred_element_type=jnp.float32)


class _Chunk:
    """One chunk-head's ``q``, ``k``, ``G`` ``[chunk, dk]`` as sub-chunks ``[m, sub, dk]``, and
    the exponents both kernels make from them."""

    def __init__(self, q, k, g, sub: int):
        self.chunk, self.dk = k.shape
        self.sub, self.m = sub, self.chunk // sub
        self.q, self.k, self.g = (x.reshape(self.m, sub, self.dk) for x in (q, k, g))
        self.place = lax.broadcasted_iota(jnp.int32, (self.m, sub, self.dk), 1)  # a token's place in its sub-chunk
        column = lax.broadcasted_iota(jnp.int32, (self.m, sub, self.chunk), 2)
        self.first = column - sub * lax.broadcasted_iota(jnp.int32, (self.m, sub, self.chunk), 0)
        ends = self.g[:, sub - 1:, :]
        # a sub-chunk's start: the decay accumulated before its first token (0 for the first)
        self.start = jnp.concatenate([jnp.zeros_like(ends[:1]), ends[:-1]], axis=0) if self.m > 1 else None

    def inside(self, j: int, after: int = 0):
        """``exp(G_t - G_j)`` of each sub-chunk's token ``j`` against its every token ``t``:
        ``[m, sub, dk]``, masked to 0 (before the exponential) where ``t < j + after``."""
        return jnp.exp(jnp.where(self.place >= j + after, self.g - self.g[:, j:j + 1], -jnp.inf))

    def at(self, j: int):
        """``[m, sub, chunk]``: the column of each sub-chunk's token ``j`` in its rows."""
        return self.first == j

    def from_start(self):
        """``exp(G_t - G_r)``, ``r`` the start of ``t``'s sub-chunk: ``[m, sub, dk]``."""
        return jnp.exp(self.g - self.start)

    def back_to(self, a: int):
        """``exp(G_r - G_j)`` of sub-chunk ``a``'s start ``r`` against every token ``j`` before it:
        ``[chunk, dk]``, masked to 0 (before the exponential) from ``a``'s first token on."""
        token = lax.broadcasted_iota(jnp.int32, (self.chunk, self.dk), 0)
        g = self.g.reshape(self.chunk, self.dk)
        return jnp.exp(jnp.where(token < a * self.sub, self.start[a] - g, -jnp.inf))


def _forward_one(q, k, g, sub: int):
    """One chunk-head -> (``kk``, ``qk``) ``[chunk, chunk]``."""
    x = _Chunk(q, k, g, sub)
    kk = qk = jnp.zeros((x.m, sub, x.chunk), jnp.float32)
    for j in range(sub):
        partner = x.k[:, j:j + 1] * x.inside(j)
        at = x.at(j)
        kk = jnp.where(at, jnp.sum(x.k * partner, axis=-1, keepdims=True), kk)
        qk = jnp.where(at, jnp.sum(x.q * partner, axis=-1, keepdims=True), qk)
    if x.m > 1:
        to_start = x.from_start()
        left_k, left_q = x.k * to_start, x.q * to_start
        k_rows = x.k.reshape(x.chunk, x.dk)
        kk_rows, qk_rows = [kk[0]], [qk[0]]
        for a in range(1, x.m):
            across = _nt(jnp.concatenate([left_k[a], left_q[a]]), k_rows * x.back_to(a))  # one product for both
            kk_rows.append(kk[a] + across[:sub])
            qk_rows.append(qk[a] + across[sub:])
        kk, qk = jnp.stack(kk_rows), jnp.stack(qk_rows)
    return kk.reshape(x.chunk, x.chunk), qk.reshape(x.chunk, x.chunk)


def _backward_one(q, k, g, d_kk, d_qk, sub: int):
    """One chunk-head and the cotangents of its two matrices -> (``dq``, ``dk``, ``dG``)
    ``[chunk, dk]``. A cotangent above the diagonal meets an exact 0 and is read as none."""
    x = _Chunk(q, k, g, sub)
    d_kk, d_qk = (d.reshape(x.m, sub, x.chunk) for d in (d_kk, d_qk))
    dq = dk_rows = dk_cols = jnp.zeros((x.m, sub, x.dk), jnp.float32)
    for j in range(sub - 1):  # a token's pairs with the later tokens of its sub-chunk; the diagonal below
        e = x.inside(j, after=1)
        partner = x.k[:, j:j + 1] * e
        at = x.at(j)
        # the cotangent of each row's pair with its sub-chunk's token j: [m, sub, 1]
        dkk_j = jnp.sum(jnp.where(at, d_kk, 0.0), axis=-1, keepdims=True)
        dqk_j = jnp.sum(jnp.where(at, d_qk, 0.0), axis=-1, keepdims=True)
        dq = dq + dqk_j * partner
        dk_rows = dk_rows + dkk_j * partner
        column = jnp.sum((dqk_j * x.q + dkk_j * x.k) * e, axis=1, keepdims=True)  # [m, 1, dk]
        dk_cols = jnp.where(x.place == j, column, dk_cols)
    dq_rows, dkr_rows = [dq[0]], [dk_rows[0]]
    dk_cols = dk_cols.reshape(x.chunk, x.dk)
    if x.m > 1:
        to_start = x.from_start()
        left_k, left_q = x.k * to_start, x.q * to_start
        k_rows = x.k.reshape(x.chunk, x.dk)
        for a in range(1, x.m):
            back = x.back_to(a)
            d_rows = jnp.concatenate([d_qk[a], d_kk[a]])  # [2 sub, chunk]: both matrices' rows, one product each way
            d_left = _nn(d_rows, k_rows * back)
            dq_rows.append(dq[a] + to_start[a] * d_left[:sub])
            dkr_rows.append(dk_rows[a] + to_start[a] * d_left[sub:])
            dk_cols = dk_cols + back * _tn(d_rows, jnp.concatenate([left_q[a], left_k[a]]))
    dq, dk_rows = (jnp.stack(r).reshape(x.chunk, x.dk) for r in (dq_rows, dkr_rows))
    dg = q * dq + k * dk_rows - k * dk_cols
    # a token's pair with itself decays by exp(0): it adds to dq and dk and nothing to dG, where
    # XLA's autodiff adds it to both sides of the difference and leaves their rounding
    own = x.first == lax.broadcasted_iota(jnp.int32, x.first.shape, 1)
    dkk_own, dqk_own = (jnp.sum(jnp.where(own, d, 0.0), axis=-1, keepdims=True).reshape(x.chunk, 1)
                        for d in (d_kk, d_qk))
    return dq + dqk_own * k, dk_rows + dk_cols + 2.0 * dkk_own * k + dqk_own * q, dg


def _rows(tb: int, shape):
    return pl.BlockSpec((tb, *shape), lambda i: (i, 0, 0))


def _call(kernel, inputs, outputs, tb: int, name: str, interpret: bool):
    n = inputs[0].shape[0]
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((n, *shape), jnp.float32) for shape in outputs],
        grid=(n // tb,),
        in_specs=[_rows(tb, x.shape[1:]) for x in inputs],
        out_specs=[_rows(tb, shape) for shape in outputs],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(*inputs)


def _forward(q, k, since, sub: int, interpret: bool):
    n, chunk, dk = k.shape
    tb = _block(n)

    def kernel(q_ref, k_ref, g_ref, kk_ref, qk_ref):
        def one(b, _):
            kk_ref[b], qk_ref[b] = _forward_one(q_ref[b], k_ref[b], g_ref[b], sub)

        lax.fori_loop(0, tb, one, None)

    return _call(kernel, (q, k, since), [(chunk, chunk)] * 2, tb, "kda_pairs", interpret)


def _backward(q, k, since, d_kk, d_qk, sub: int, interpret: bool):
    n, chunk, dk = k.shape
    tb = _block(n)

    def kernel(q_ref, k_ref, g_ref, dkk_ref, dqk_ref, dq_ref, dk_ref, dg_ref):
        def one(b, _):
            dq_ref[b], dk_ref[b], dg_ref[b] = _backward_one(q_ref[b], k_ref[b], g_ref[b], dkk_ref[b], dqk_ref[b], sub)

        lax.fori_loop(0, tb, one, None)

    return _call(kernel, (q, k, since, d_kk, d_qk), [(chunk, dk)] * 3, tb, "kda_pairs_bwd", interpret)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def decayed_pairs(q, k, since, sub: int, interpret: bool = False):
    """``q``, ``k``, ``since`` ``[..., chunk, dk]`` (float32) -> (``kk``, ``qk``) ``[..., chunk,
    chunk]``, each lower triangular with its diagonal."""
    *lead, chunk, dk = k.shape
    if not supports(k.shape, sub):
        raise ValueError(f"inputs of {k.shape} in sub-chunks of {sub}: dk is no whole number of {LANES} lanes, "
                         f"or the chunk no whole number of sub-chunks of a multiple of {SUBLANES}")
    flat = [x.reshape(-1, chunk, dk) for x in (q, k, since)]
    return tuple(x.reshape(*lead, chunk, chunk) for x in _forward(*flat, sub, interpret))


def _decayed_pairs_fwd(q, k, since, sub, interpret):
    return decayed_pairs(q, k, since, sub, interpret), (q, k, since)


def _decayed_pairs_bwd(sub, interpret, residuals, cotangents):
    q, k, since = residuals
    *lead, chunk, dk = k.shape
    flat = [x.reshape(-1, chunk, dk) for x in residuals] + [d.reshape(-1, chunk, chunk) for d in cotangents]
    return tuple(x.reshape(*lead, chunk, dk) for x in _backward(*flat, sub, interpret))


decayed_pairs.defvjp(_decayed_pairs_fwd, _decayed_pairs_bwd)
