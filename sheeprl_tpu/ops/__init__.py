"""The one Pallas TPU kernel of the tree and its XLA reference: the fused LayerNorm-GRU
step (``ops/gru.py``; XS and S cells only). The convolutions are ``flax.linen.Conv`` /
``lax.conv_transpose`` at their call sites."""

from sheeprl_tpu.ops.gru import (
    fused_ln_gru_step,
    ln_gru_step_reference,
    pallas_gru_applicable,
)

__all__ = [
    "fused_ln_gru_step",
    "ln_gru_step_reference",
    "pallas_gru_applicable",
]
