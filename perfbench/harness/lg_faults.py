"""Faults planted in the sequence-policy PPO program on the `laguna` trunk (`models/laguna.py`,
`models/lm_layers.py`, `algos/ppo/anakin.py`), for the readings that set the upper end of a limit of
`laguna_xs2_ep16` and for the test that sees `correct` come out false. Not part of a benchmark run.
Each is a wrong program that still runs, planted by swapping module-level names that the trunk
looks up when the program is traced:

`no_window`: the sliding layers read every earlier key, in both forms (the band over the whole
  sequence, the ring as long as the episode).
`window_513`: the sliding layers read 513 keys and not 512, in both forms (a reading of how near a
  wrong window `correct` sees; it is not required to).
`ring_slot_rotation`: the ring's rows written and its query rotated at the SLOT (`t mod rows`), not
  at the absolute position: the rollout's sliding layers go wrong once the ring wraps.
`yarn_default`: the full layers rotate by the default law (`rope_theta` over the rotated channels,
  no attention factor) and not by YaRN, in both forms.
`no_attention_factor`: YaRN's attention factor left out (1), in both forms.
`full_rope_whole_head`: the full layers rotate the whole head (YaRN over 128 channels), in both forms.
`no_gate`: the gate a head left out, in both forms.
`softmax_scores`: the router's scores a float32 softmax over the experts, not sigmoids.
`top7`: the eighth chosen expert gets weight 0 and the weights are normalised over seven.
`no_shared_expert`: the shared expert's SwiGLU is left out.
`half_sequences`, `state_unchanged`: `lm_faults`'s (the loss's forward reads the first half
of a minibatch's sequences twice; the fused call returns the parameters as it got them).
"""

from __future__ import annotations

import contextlib
import dataclasses

from perfbench.harness import lm_faults

OF_THE_LOOP = ("half_sequences", "state_unchanged")
KINDS = ("no_window", "window_513", "ring_slot_rotation", "yarn_default", "no_attention_factor",
         "full_rope_whole_head", "no_gate", "softmax_scores", "top7", "no_shared_expert", *OF_THE_LOOP)
# the faults whose program `correct` has to refuse: every one but the reading of a window one key wider
REFUSED = tuple(kind for kind in KINDS if kind != "window_513")


@contextlib.contextmanager
def planted(kind: str):
    from sheeprl_tpu.models import laguna as trunk
    from sheeprl_tpu.models import lm_layers

    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; there are {KINDS}")
    if kind in OF_THE_LOOP:
        with lm_faults.planted(kind):
            yield
        return
    sound = {name: getattr(trunk, name) for name in ("swa_forward", "init_carry", "attention_step", "rope_frequencies",
                                                     "output_gate", "route", "expert_layer")}
    sound["yarn_frequencies"] = lm_layers.yarn_frequencies

    def window(spec):
        return spec.max_seq_len if kind == "no_window" else spec.sliding_window + 1

    def swa_forward(q, k, v, width, num_kv_heads):
        return sound["swa_forward"](q, k, v, q.shape[1] if kind == "no_window" else width + 1, num_kv_heads)

    def init_carry(spec, batch):
        return sound["init_carry"](dataclasses.replace(spec, sliding_window=window(spec)), batch)

    def attention_step(p, cache, u, t, kind_, spec):
        if kind_ != "sliding_attention":
            return sound["attention_step"](p, cache, u, t, kind_, spec)
        q, k, v = trunk._qkv(p, u[:, None], (t % cache[0].shape[1])[None], kind_, spec)
        out, cache, rows = trunk.swa_step(cache, q, k, v, t, spec.num_key_value_heads)
        return (trunk.output_gate(p, u[:, None], out) @ p["wo"])[:, 0], cache, rows

    def yarn_frequencies(rope, dim):
        if kind == "yarn_default":
            return lm_layers.default_frequencies(float(rope["rope_theta"]), dim), 1.0
        return sound["yarn_frequencies"](rope, dim)[0], 1.0

    def rope_frequencies(spec, kind_):
        if kind_ != "full_attention":
            return sound["rope_frequencies"](spec, kind_)
        return lm_layers.yarn_frequencies(spec.rope_of(kind_), spec.head_dim)

    def output_gate(p, u, out):
        return out

    def route(p, u, spec):
        if kind == "softmax_scores":
            return lm_layers.route(p, u, dataclasses.replace(spec, router_scoring="softmax"))
        ids, w = sound["route"](p, u, spec)
        w = w.at[:, -1].set(0.0)
        return ids, w / w.sum(axis=-1, keepdims=True) * spec.routed_scaling_factor

    def expert_layer(p, u, spec):
        y, ids, counters = sound["expert_layer"](p, u, spec)
        return y - lm_layers.swiglu(p["shared"], u), ids, counters

    swaps = {
        "no_window": [(trunk, "swa_forward", swa_forward), (trunk, "init_carry", init_carry)],
        "window_513": [(trunk, "swa_forward", swa_forward), (trunk, "init_carry", init_carry)],
        "ring_slot_rotation": [(trunk, "attention_step", attention_step)],
        "yarn_default": [(lm_layers, "yarn_frequencies", yarn_frequencies)],
        "no_attention_factor": [(lm_layers, "yarn_frequencies", yarn_frequencies)],
        "full_rope_whole_head": [(trunk, "rope_frequencies", rope_frequencies)],
        "no_gate": [(trunk, "output_gate", output_gate)],
        "softmax_scores": [(trunk, "route", route)], "top7": [(trunk, "route", route)],
        "no_shared_expert": [(trunk, "expert_layer", expert_layer)],
    }[kind]
    for module, name, wrong in swaps:
        setattr(module, name, wrong)
    try:
        yield
    finally:
        for module, name, _ in swaps:
            setattr(module, name, sound[name])
