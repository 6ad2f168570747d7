"""Sequence/context parallelism: ring-chained scan over a mesh axis.

The reference has no sequence parallelism (SURVEY §5.7 — its temporal backbone is a
GRU RSSM unrolled per-rank); this module is the TPU-native long-context extension
hook: shard the TIME axis of a recurrent scan across a mesh axis, each device
scanning its contiguous chunk after receiving the carry from the previous device
over a `ppermute` ring (ICI). A single sequence stays inherently sequential — the
win is MEMORY: a T-step sequence holds only T/S steps of inputs and activations per
device, so sequences that cannot fit one device's HBM become trainable, and
backward-pass activation memory shrinks by the same factor.

Used by ``DV3Agent.dynamic_scan_sp`` for the Dreamer world-model unroll; the
primitive is model-agnostic (any ``f(carry, x) -> (carry, y)`` scan body).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ring_sequence_scan(
    f: Callable[[Any, Any], Tuple[Any, Any]],
    init: Any,
    xs: Any,
    mesh: Mesh,
    axis: str = "seq",
) -> Tuple[Any, Any]:
    """``lax.scan(f, init, xs)`` with the leading (time) axis of ``xs`` sharded over
    ``axis``. Device ``s`` owns steps ``[s*T/S, (s+1)*T/S)``; carries hop the ring
    via ``ppermute``. Returns ``(final_carry, ys)`` with ``ys`` time-sharded like
    ``xs``. Semantics identical to the unsharded scan (parity-tested).
    """
    S = mesh.shape[axis]
    if S == 1:
        return jax.lax.scan(f, init, xs)

    fwd = [(i, (i + 1) % S) for i in range(S)]

    def _local(init_rep, xs_local):
        my = jax.lax.axis_index(axis)
        zero_carry = jax.tree_util.tree_map(jnp.zeros_like, init_rep)

        def stage(s, state):
            carry, ys = state
            is_my_turn = my == s
            # stage 0 seeds device 0 with the true init; later stages use the carry
            # received from the ring
            carry_in = jax.lax.cond(
                s == 0,
                lambda: init_rep,
                lambda: carry,
            )

            def run(c):
                return jax.lax.scan(f, c, xs_local)

            def skip(c):
                return c, ys

            new_carry, new_ys = jax.lax.cond(is_my_turn, run, skip, carry_in)
            # hand the produced carry to the next device; devices that did not run
            # this stage forward zeros, which the receiver ignores unless it is the
            # next stage's owner
            send = jax.tree_util.tree_map(
                lambda a: jnp.where(is_my_turn, a, jnp.zeros_like(a)), new_carry
            )
            received = jax.tree_util.tree_map(
                lambda a: jax.lax.ppermute(a, axis, fwd), send
            )
            ys = jax.tree_util.tree_map(
                lambda old, new: jnp.where(is_my_turn, new, old), ys, new_ys
            )
            # the final device's carry survives the wrap-around for the return value
            carry = jax.tree_util.tree_map(
                lambda r, c: jnp.where(my == (s + 1) % S, r, c), received, carry
            )
            return carry, ys

        ys0 = jax.eval_shape(lambda c, x: jax.lax.scan(f, c, x), init_rep, xs_local)[1]
        ys_init = jax.tree_util.tree_map(lambda s_: jnp.zeros(s_.shape, s_.dtype), ys0)
        carry, ys = jax.lax.fori_loop(0, S, stage, (zero_carry, ys_init))
        # after S stages the last device's carry has hopped to device 0: that is the
        # global final carry, broadcast to everyone for a replicated return
        final = jax.tree_util.tree_map(
            lambda a: jax.lax.psum(jnp.where(my == 0, a, jnp.zeros_like(a)), axis), carry
        )
        return final, ys

    in_specs = (P(), P(axis))
    out_specs = (P(), P(axis))
    # relaxed body checking: bodies may contain ops without varying-axis types
    # (e.g. a pallas_call's out_shape); the ring's collectives are explicitly
    # paired here.
    shmapped = shard_map(
        _local, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return shmapped(init, xs)


def seq_sharding(mesh: Mesh, axis: str = "seq") -> NamedSharding:
    """Leading-(time-)axis sharding for ring_sequence_scan inputs."""
    return NamedSharding(mesh, P(axis))
