"""Faults planted in the sequence-policy PPO program (`models/lfm2.py`, `algos/ppo/anakin.py`),
for the readings that set the upper end of a limit of `lfm2_8b_a1b_ep4` and for the test
that sees `correct` come out false. Not part of a benchmark run. Each is a wrong program
that still runs at the same shapes:

`top3`: the fourth chosen expert gets weight 0 and the weights are normalised over three.
`softmax_scores`: the router's scores are a softmax over the experts, not sigmoids.
`no_expert_bias`: the bias `b` is left out of the choice.
`expert_dropped`: what one held expert (the fourth held) computes is dropped.
`half_sequences`: the loss's forward reads the first half of a minibatch's sequences in
the place of the second half too.
`state_unchanged`: the fused call returns the parameters as it got them.
`prompt_unmasked`: the env's mask is 1 on the prompt's steps too, so the loss counts steps
whose action the env ignored (the reference is fed the program's own rollout: only the
env's recomputation sees this one).
"""

from __future__ import annotations

import contextlib

KINDS = ("top3", "softmax_scores", "no_expert_bias", "expert_dropped", "half_sequences", "state_unchanged",
         "prompt_unmasked")


@contextlib.contextmanager
def planted(kind: str):
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.ppo import anakin, sequence_policy
    from sheeprl_tpu.envs.jax import tokens
    from sheeprl_tpu.models import lfm2

    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; there are {KINDS}")
    route, forward, make_program = lfm2.route, sequence_policy.SequencePolicy.forward, anakin.make_anakin_program
    env_step = tokens.TokenCopy.step

    def faulty_route(p, u, spec):
        if kind in ("no_expert_bias", "softmax_scores"):
            logits = u @ p["router"]
            if kind == "no_expert_bias":
                sel = s = jax.nn.sigmoid(logits)
            else:
                s = jax.nn.softmax(logits, axis=-1)
                sel = s + jax.lax.stop_gradient(p["bias"])
            ids = jax.lax.top_k(sel, spec.num_experts_per_tok)[1]
            w = jnp.take_along_axis(s, ids, axis=-1)
            return ids, w / (w.sum(axis=-1, keepdims=True) + lfm2.WEIGHT_SUM_EPS)
        ids, w = route(p, u, spec)
        if kind == "top3":
            w = w.at[:, -1].set(0.0)
            w = w / w.sum(axis=-1, keepdims=True)
        elif kind == "expert_dropped":
            w = jnp.where(ids == spec.experts_held[0] + min(3, spec.experts_held[1] - 1), 0.0, w)
        return ids, w

    def faulty_forward(self, params, tokens):
        half = tokens.shape[0] // 2
        return forward(self, params, jnp.concatenate([tokens[:half], tokens[:half]], axis=0))

    def faulty_program(*args, **kwargs):
        fused, rollout_only, updates = make_program(*args, **kwargs)

        def unchanged(params, *rest):
            kept = jax.tree_util.tree_map(jnp.copy, params)  # the call donates its own
            out = fused(params, *rest)
            return (kept, *out[1:])

        unchanged.lower = fused.lower
        return unchanged, rollout_only, updates

    def faulty_env_step(self, state, action):
        state, obs, reward, done, info = env_step(self, state, action)
        return state, obs, reward, done, {"action_mask": jnp.ones_like(info["action_mask"])}

    if kind == "prompt_unmasked":
        tokens.TokenCopy.step = faulty_env_step
    elif kind == "half_sequences":
        sequence_policy.SequencePolicy.forward = faulty_forward
    elif kind == "state_unchanged":
        anakin.make_anakin_program = faulty_program
    else:
        lfm2.route = faulty_route
    try:
        yield
    finally:
        lfm2.route, sequence_policy.SequencePolicy.forward, anakin.make_anakin_program = route, forward, make_program
        tokens.TokenCopy.step = env_step
