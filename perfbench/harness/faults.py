"""Faults planted in the program's train call, for the readings that set a limit's
upper end and for the test that sees `correct` come out false. Neither is part of a
benchmark run."""

from __future__ import annotations

import contextlib

KINDS = ("state_unchanged", "half_batch")


@contextlib.contextmanager
def planted(kind: str):
    """`state_unchanged`: the train call returns its state as it got it.
    `half_batch`: the second half of the batch's rows is left out and the first half
    stands in for it, which is the mean over the first half at the same shapes."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3

    original = dv3._InlineTrainer.train
    if kind == "state_unchanged":

        def train(self, data, *args, **kwargs):
            saved = jax.tree_util.tree_map(jnp.copy, (self.params, self.opt_state, self.moments_state))
            out = original(self, data, *args, **kwargs)
            self.params, self.opt_state, self.moments_state = saved
            return out

    elif kind == "half_batch":

        def train(self, data, *args, **kwargs):
            def first_half_twice(a):  # [G, T, B, ...]
                half = a[:, :, : a.shape[2] // 2]
                return jnp.concatenate([half, half], axis=2)

            return original(self, jax.tree_util.tree_map(first_half_twice, data), *args, **kwargs)

    else:
        raise ValueError(f"unknown fault {kind!r}; there are {KINDS}")
    dv3._InlineTrainer.train = train
    try:
        yield
    finally:
        dv3._InlineTrainer.train = original
