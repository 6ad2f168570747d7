"""Faults planted in the sequence-policy PPO program on the `deepseek_v3` trunk
(`models/deepseek_v3.py`, `algos/ppo/anakin.py`), for the readings that set the upper end of
a limit of `moonlight_16b_a3b_ep8` and for the test that sees `correct` come out false.
Not part of a benchmark run. Each is a wrong program that still runs at the same shapes:

`top5`: the sixth chosen expert gets weight 0 and the weights are normalised over five.
`no_expert_bias`: the bias `b` is left out of the choice.
`scale_one`: the normalised weights are not multiplied by `routed_scaling_factor`.
`no_shared_expert`: the shared experts' SwiGLU is left out.
`no_latent_norm`: the latent goes un-normed into the cache and into `W_kvb`.
`score_scale_128`: the scores are over `sqrt(nope)` and not `sqrt(nope + rope)`.
`no_rope_on_k`: rotary embedding is left off the shared key `k_pe`, in both forms.
`cache_k_unrotated`: the ROLLOUT's cache holds `k_pe` unrotated (the update is sound).
`rollout_rows_zeroed`: the rollout's step form starts every step from an empty cache.
`expert_dropped`: what one held expert (the fourth held) computes is dropped.
`half_sequences`, `state_unchanged`: `lm_faults`'s (the loss's forward reads the first half
of a minibatch's sequences twice; the fused call returns the parameters as it got them).
"""

from __future__ import annotations

import contextlib
import math

from perfbench.harness import lm_faults

OF_THE_LOOP = ("half_sequences", "state_unchanged")
KINDS = ("top5", "no_expert_bias", "scale_one", "no_shared_expert", "no_latent_norm", "score_scale_128",
         "no_rope_on_k", "cache_k_unrotated", "rollout_rows_zeroed", "expert_dropped", *OF_THE_LOOP)


@contextlib.contextmanager
def planted(kind: str):
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import deepseek_v3 as trunk
    from sheeprl_tpu.models import lm_layers

    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; there are {KINDS}")
    if kind in OF_THE_LOOP:
        with lm_faults.planted(kind):
            yield
        return
    names = ("route", "expert_layer", "_latent_inputs", "_score_scale", "mla_step")
    sound = {name: getattr(trunk, name) for name in names}

    def route(p, u, spec):
        if kind == "no_expert_bias":
            s = jax.nn.sigmoid(u @ p["router"])
            ids = jax.lax.top_k(s, spec.num_experts_per_tok)[1]
            w = jnp.take_along_axis(s, ids, axis=-1)
            return ids, w / (w.sum(axis=-1, keepdims=True) + lm_layers.WEIGHT_SUM_EPS) * spec.routed_scaling_factor
        ids, w = sound["route"](p, u, spec)
        if kind == "top5":
            w = w.at[:, -1].set(0.0)
            w = w / w.sum(axis=-1, keepdims=True) * spec.routed_scaling_factor
        elif kind == "scale_one":
            w = w / spec.routed_scaling_factor
        elif kind == "expert_dropped":
            w = jnp.where(ids == spec.experts_held[0] + min(3, spec.experts_held[1] - 1), 0.0, w)
        return ids, w

    def expert_layer(p, u, spec):
        y, ids, counters = sound["expert_layer"](p, u, spec)
        return y - lm_layers.swiglu(p["shared"], u), ids, counters

    def latent_inputs(p, u, positions, spec, which=kind):
        q_nope, q_pe, c, k_pe = sound["_latent_inputs"](p, u, positions, spec)
        raw = u @ p["w_kva"]
        if which == "no_latent_norm":
            return q_nope, q_pe, raw[..., :spec.kv_lora_rank], k_pe
        return q_nope, q_pe, c, raw[..., spec.kv_lora_rank:]  # the key as projected, never turned

    def score_scale(spec):
        return 1.0 / math.sqrt(spec.qk_nope_head_dim)

    def mla_step(p, cache, u, t, spec):
        if kind == "rollout_rows_zeroed":
            return sound["mla_step"](p, jnp.zeros_like(cache), u, t, spec)
        trunk._latent_inputs = lambda *args: latent_inputs(*args, which="no_rope_on_k")  # for this step's trace alone
        try:
            return sound["mla_step"](p, cache, u, t, spec)
        finally:
            trunk._latent_inputs = sound["_latent_inputs"]

    faulty = {"top5": ("route", route), "no_expert_bias": ("route", route), "scale_one": ("route", route),
              "expert_dropped": ("route", route), "no_shared_expert": ("expert_layer", expert_layer),
              "no_latent_norm": ("_latent_inputs", latent_inputs), "no_rope_on_k": ("_latent_inputs", latent_inputs),
              "score_scale_128": ("_score_scale", score_scale), "cache_k_unrotated": ("mla_step", mla_step),
              "rollout_rows_zeroed": ("mla_step", mla_step)}
    name, wrong = faulty[kind]
    setattr(trunk, name, wrong)
    try:
        yield
    finally:
        setattr(trunk, name, sound[name])
