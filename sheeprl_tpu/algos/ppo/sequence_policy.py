"""The sequence-model policy of the fused on-device PPO loop (``algos/ppo/anakin.py``).

Where the MLP agent maps one observation to an action, a sequence policy reads ONE token
a step and keeps its own state over the episode. The fused loop asks three things of it:

- ``initial_carry(batch)``: the state a batch of fresh episodes starts from;
- ``step(params, carry, tokens) -> (logits, value, carry, aux)``: one token a sequence,
  inside the rollout's ``lax.scan``;
- ``forward(params, tokens) -> (logits, values, aux)``: whole sequences ``[B, T]``,
  teacher-forced, inside the loss.

``aux`` is ``{"route_ids", "counters"}`` where the trunk has expert layers (the experts
each token chose, and the layers' counters), else empty. The one trunk there is so far
is ``models/lfm2.py`` (LFM2-8B-A1B's block, ``lfm2_moe``): ``algo.lm`` holds its sizes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax

from sheeprl_tpu.models import lfm2


@dataclass(frozen=True)
class SequencePolicy:
    spec: lfm2.LFM2Spec

    def init(self, key: jax.Array) -> Dict[str, Any]:
        return lfm2.init_params(self.spec, key)

    def initial_carry(self, batch: int) -> Dict[str, Any]:
        return lfm2.init_carry(self.spec, batch)

    def step(self, params, carry, tokens):
        logits, value, carry, ids, counters = lfm2.step(params, self.spec, carry, tokens)
        return logits, value, carry, _aux(ids, counters)

    def forward(self, params, tokens):
        logits, values, ids, counters = lfm2.forward(params, self.spec, tokens)
        return logits, values, _aux(ids, counters)


def _aux(ids, counters) -> Dict[str, Any]:
    return {} if ids is None else {"route_ids": ids, "counters": counters}


def build_sequence_policy(cfg: Any, vocab_size: int, key: jax.Array) -> Tuple[SequencePolicy, Dict[str, Any]]:
    """The policy ``algo.lm`` describes, over the ``vocab_size`` ids the env feeds, for
    episodes of ``algo.rollout_steps`` tokens, and its freshly drawn parameters."""
    lm = cfg.algo.lm
    if int(lm.vocab_size) != int(vocab_size):
        raise ValueError(f"algo.lm.vocab_size ({lm.vocab_size}) is not the env's ({vocab_size})")
    policy = SequencePolicy(lfm2.LFM2Spec.from_cfg(lm, vocab_size, int(cfg.algo.rollout_steps)))
    return policy, policy.init(key)
