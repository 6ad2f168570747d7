"""Faults planted in the sequence-policy PPO program on the `kimi_linear` trunk
(`models/kimi_linear.py`, the latent attention of `models/deepseek_v3.py`, `algos/ppo/anakin.py`),
for the readings that set the upper end of a limit of `kimi_linear_48b_a3b_ep32` and for the test
that sees `correct` come out false. Not part of a benchmark run. Each is a wrong program that
still runs at the same shapes, planted by swapping one module-level name that the trunk looks up
when the program is traced:

`scalar_decay`: the decay averaged over the key channels, a head's one value (the scalar rule of
  `qwen3_next` in Kimi delta attention's place), in both forms.
`mla_rope`: rotary embedding applied to `q_pe` and `k_pe` in the latent attention, in both forms.
`gate_silu`: the output gate as SiLU and not a sigmoid.
`no_beta`: `beta` left out (1), in both forms.
`top7`: the eighth chosen expert gets weight 0 and the weights are normalised over seven.
`no_expert_bias`: the bias `b` is left out of the choice.
`no_shared_expert`: the shared expert's SwiGLU is left out.
`rollout_state_zeroed`: the ROLLOUT's KDA steps start every step from `S = 0` (the update is sound).
`half_sequences`, `state_unchanged`: `lm_faults`'s (the loss's forward reads the first half
of a minibatch's sequences twice; the fused call returns the parameters as it got them).
"""

from __future__ import annotations

import contextlib
import dataclasses

from perfbench.harness import lm_faults

OF_THE_LOOP = ("half_sequences", "state_unchanged")
KINDS = ("scalar_decay", "mla_rope", "gate_silu", "no_beta", "top7", "no_expert_bias", "no_shared_expert",
         "rollout_state_zeroed", *OF_THE_LOOP)


@contextlib.contextmanager
def planted(kind: str):
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import deepseek_v3, lm_layers
    from sheeprl_tpu.models import kimi_linear as trunk

    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; there are {KINDS}")
    if kind in OF_THE_LOOP:
        with lm_faults.planted(kind):
            yield
        return
    sound = {name: getattr(trunk, name) for name in ("_kda_inputs", "_kda_output", "kda_step", "route", "expert_layer")}
    sound["_latent_inputs"] = deepseek_v3._latent_inputs

    def kda_inputs(p, u, spec):
        mixed, gate, beta, g = sound["_kda_inputs"](p, u, spec)
        if kind == "no_beta":
            return mixed, gate, jnp.ones_like(beta), g
        return mixed, gate, beta, jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)

    def kda_output(p, out, gate, spec):
        gated = p["norm"] * lm_layers.rms_core(out, spec.norm_eps) * jax.nn.silu(gate.reshape(out.shape))
        return gated.reshape(*gated.shape[:-2], spec.linear_width) @ p["wo"]

    def kda_step(state, q, k, v, g, beta):
        return sound["kda_step"](jnp.zeros_like(state), q, k, v, g, beta)

    def latent_inputs(p, u, positions, spec):
        return sound["_latent_inputs"](p, u, positions, dataclasses.replace(spec, mla_use_nope=False))

    def route(p, u, spec):
        if kind == "no_expert_bias":
            s = jax.nn.sigmoid(u @ p["router"])
            ids = jax.lax.top_k(s, spec.num_experts_per_tok)[1]
            w = jnp.take_along_axis(s, ids, axis=-1)
            return ids, w / (w.sum(axis=-1, keepdims=True) + lm_layers.WEIGHT_SUM_EPS) * spec.routed_scaling_factor
        ids, w = sound["route"](p, u, spec)
        w = w.at[:, -1].set(0.0)
        return ids, w / w.sum(axis=-1, keepdims=True) * spec.routed_scaling_factor

    def expert_layer(p, u, spec):
        y, ids, counters = sound["expert_layer"](p, u, spec)
        return y - lm_layers.swiglu(p["shared"], u), ids, counters

    module, name, wrong = {
        "scalar_decay": (trunk, "_kda_inputs", kda_inputs), "no_beta": (trunk, "_kda_inputs", kda_inputs),
        "gate_silu": (trunk, "_kda_output", kda_output), "rollout_state_zeroed": (trunk, "kda_step", kda_step),
        "mla_rope": (deepseek_v3, "_latent_inputs", latent_inputs), "top7": (trunk, "route", route),
        "no_expert_bias": (trunk, "route", route), "no_shared_expert": (trunk, "expert_layer", expert_layer),
    }[kind]
    setattr(module, name, wrong)
    try:
        yield
    finally:
        setattr(module, name, sound[name])
