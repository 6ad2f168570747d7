"""The sequence-model policy of the fused on-device PPO loop (``algos/ppo/anakin.py``).

Where the MLP agent maps one observation to an action, a sequence policy reads ONE token
a step and keeps its own state over the episode. The fused loop asks three things of it:

- ``initial_carry(batch)``: the state a batch of fresh episodes starts from;
- ``step(params, carry, tokens) -> (logits, value, carry, aux)``: one token a sequence,
  inside the rollout's ``lax.scan``;
- ``forward(params, tokens) -> (logits, values, aux)``: whole sequences ``[B, T]``,
  teacher-forced, inside the loss.

``aux`` is ``{"route_ids", "counters"}`` where the trunk has expert layers (the experts
each token chose, and the layers' counters), else empty.

There are five trunks, and ``algo.lm.model_type`` names one (`TRUNKS`): ``lfm2_moe`` (the default; ``models/lfm2.py``,
LFM2-8B-A1B's block: gated short convolutions and grouped-query attention, a sigmoid router with a bias), ``qwen3_next``
(Qwen3-Next-80B-A3B's: gated delta-rule linear attention 3:1 with gated attention, a softmax router, a gated shared
expert), ``deepseek_v3`` (Moonlight-16B-A3B's: latent attention decoded absorbed through a latent cache, a sigmoid router
with a bias and a scale, ungated shared experts), ``kimi_linear`` (Kimi-Linear-48B-A3B's: Kimi delta attention, a gated
delta rule with a decay a key channel, 3:1 with NoPE latent attention) and ``laguna`` (Laguna-XS.2's: grouped-query
attention over a sliding window, decoded through a ring cache, 3:1 with YaRN full attention, a gate a head). A trunk is
a module of ``models/`` (``lfm2.py``, ``qwen3_next.py``, ``deepseek_v3.py``, ``kimi_linear.py``, ``laguna.py``) with
``init_params``, ``init_carry``, ``step`` and ``forward`` and a spec class with ``from_cfg``; ``algo.lm`` holds its sizes
(the published ones are in ``perfbench/configs/``). They share ``models/lm_layers.py``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax

from sheeprl_tpu.models import deepseek_v3, kimi_linear, laguna, lfm2, qwen3_next
# `algo.lm.model_type` -> (the trunk's module, its spec)
TRUNKS = {"lfm2_moe": (lfm2, lfm2.LFM2Spec), "qwen3_next": (qwen3_next, qwen3_next.Qwen3NextSpec),
          "deepseek_v3": (deepseek_v3, deepseek_v3.DeepseekV3Spec), "kimi_linear": (kimi_linear, kimi_linear.KimiLinearSpec),
          "laguna": (laguna, laguna.LagunaSpec)}


@dataclass(frozen=True)
class SequencePolicy:
    spec: Any
    trunk: Any = lfm2

    def init(self, key: jax.Array) -> Dict[str, Any]:
        return self.trunk.init_params(self.spec, key)

    def initial_carry(self, batch: int) -> Dict[str, Any]:
        return self.trunk.init_carry(self.spec, batch)

    def step(self, params, carry, tokens):
        logits, value, carry, ids, counters = self.trunk.step(params, self.spec, carry, tokens)
        return logits, value, carry, _aux(ids, counters)

    def forward(self, params, tokens):
        logits, values, ids, counters = self.trunk.forward(params, self.spec, tokens)
        return logits, values, _aux(ids, counters)


def _aux(ids, counters) -> Dict[str, Any]:
    return {} if ids is None else {"route_ids": ids, "counters": counters}


def build_sequence_policy(cfg: Any, vocab_size: int, key: jax.Array) -> Tuple[SequencePolicy, Dict[str, Any]]:
    """The policy ``algo.lm`` describes, over the ``vocab_size`` ids the env feeds, for
    episodes of ``algo.rollout_steps`` tokens, and its freshly drawn parameters."""
    lm = cfg.algo.lm
    if int(lm.vocab_size) != int(vocab_size):
        raise ValueError(f"algo.lm.vocab_size ({lm.vocab_size}) is not the env's ({vocab_size})")
    model_type = str(lm.get("model_type", "lfm2_moe"))
    if model_type not in TRUNKS:
        raise ValueError(f"algo.lm.model_type={model_type!r} names no trunk; there are {sorted(TRUNKS)}")
    trunk, spec = TRUNKS[model_type]
    policy = SequencePolicy(spec.from_cfg(lm, vocab_size, int(cfg.algo.rollout_steps)), trunk)
    return policy, policy.init(key)
