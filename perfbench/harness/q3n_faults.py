"""Faults planted in the sequence-policy PPO program on the `qwen3_next` trunk
(`models/qwen3_next.py`, `algos/ppo/anakin.py`), for the readings that set the upper end of
a limit of `qwen3_next_80b_a3b_ep16` and for the test that sees `correct` come out false.
Not part of a benchmark run. Each is a wrong program that still runs at the same shapes:

`top9`: the tenth chosen expert gets weight 0 and the weights are normalised over nine.
`sigmoid_scores`: the router's scores are sigmoids, not a softmax over the experts.
`no_shared_gate`: the shared expert's sigmoid gate is left out (the gate reads 1).
`no_decay`: the delta rule's decay is left out (`g` = 0).
`beta_one`: the delta rule's write strength is 1.
`chunk_state_dropped`: the update's chunked rule starts every chunk from `S` = 0.
`rollout_state_zeroed`: the rollout's step form starts every step from `S` = 0.
`rope_whole_head`: rotary embedding on the whole head, not on its first quarter.
`no_output_gate`: the attention's output gate is left out (the gate reads 1).
`expert_dropped`: what one held expert (the fourth held) computes is dropped.
`half_sequences`, `state_unchanged`: `lm_faults`'s (the loss's forward reads the first half
of a minibatch's sequences twice; the fused call returns the parameters as it got them).
"""

from __future__ import annotations

import contextlib

from perfbench.harness import lm_faults

OF_THE_LOOP = ("half_sequences", "state_unchanged")
KINDS = ("top9", "sigmoid_scores", "no_shared_gate", "no_decay", "beta_one", "chunk_state_dropped",
         "rollout_state_zeroed", "rope_whole_head", "no_output_gate", "expert_dropped", *OF_THE_LOOP)


@contextlib.contextmanager
def planted(kind: str):
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import lm_layers
    from sheeprl_tpu.models import qwen3_next as trunk

    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; there are {KINDS}")
    if kind in OF_THE_LOOP:
        with lm_faults.planted(kind):
            yield
        return
    names = ("route", "expert_layer", "_linear_inputs", "chunk_delta_rule", "delta_rule_step", "rope", "_qkv_gate")
    sound = {name: getattr(trunk, name) for name in names}

    def route(p, u, spec):
        if kind == "sigmoid_scores":
            s = jax.nn.sigmoid((u @ p["router"]).astype(jnp.float32))
            ids = jax.lax.top_k(s, spec.num_experts_per_tok)[1]
            w = jnp.take_along_axis(s, ids, axis=-1)
            return ids, w / (w.sum(axis=-1, keepdims=True) + lm_layers.WEIGHT_SUM_EPS)
        ids, w = sound["route"](p, u, spec)
        if kind == "top9":
            w = w.at[:, -1].set(0.0)
            w = w / w.sum(axis=-1, keepdims=True)
        elif kind == "expert_dropped":
            w = jnp.where(ids == spec.experts_held[0] + min(3, spec.experts_held[1] - 1), 0.0, w)
        return ids, w

    def expert_layer(p, u, spec):  # gate 1: what the gate held back is added
        y, ids, counters = sound["expert_layer"](p, u, spec)
        return y + (1.0 - jax.nn.sigmoid(u @ p["shared_gate"])) * lm_layers.swiglu(p["shared"], u), ids, counters

    def linear_inputs(p, u, spec):
        mixed, z, beta, g = sound["_linear_inputs"](p, u, spec)
        return (mixed, z, beta, jnp.zeros_like(g)) if kind == "no_decay" else (mixed, z, jnp.ones_like(beta), g)

    def chunk_delta_rule(q, k, v, g, beta, chunk):  # every chunk a sequence of its own
        bsz, t = q.shape[:2]
        pad = (-t) % chunk
        apart = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)).reshape(-1, chunk, *x.shape[2:])  # noqa: E731
        out = sound["chunk_delta_rule"](*map(apart, (q, k, v, g, beta)), chunk)
        return out.reshape(bsz, t + pad, *out.shape[2:])[:, :t]

    def delta_rule_step(state, q, k, v, g, beta):
        return sound["delta_rule_step"](jnp.zeros_like(state), q, k, v, g, beta)

    def rope(x, positions, theta, rotary_dim=None):
        return sound["rope"](x, positions, theta)

    def qkv_gate(p, u, positions, spec):
        q, k, v, gate = sound["_qkv_gate"](p, u, positions, spec)
        return q, k, v, jnp.full_like(gate, 40.0)  # sigmoid(40) is 1 in float32

    faulty = {"top9": ("route", route), "sigmoid_scores": ("route", route), "expert_dropped": ("route", route),
              "no_shared_gate": ("expert_layer", expert_layer), "no_decay": ("_linear_inputs", linear_inputs),
              "beta_one": ("_linear_inputs", linear_inputs), "chunk_state_dropped": ("chunk_delta_rule", chunk_delta_rule),
              "rollout_state_zeroed": ("delta_rule_step", delta_rule_step), "rope_whole_head": ("rope", rope),
              "no_output_gate": ("_qkv_gate", qkv_gate)}
    name, wrong = faulty[kind]
    setattr(trunk, name, wrong)
    try:
        yield
    finally:
        setattr(trunk, name, sound[name])
