"""The Pallas TPU kernels of the tree: the fused LayerNorm-GRU step and its XLA reference
(``ops/gru.py``; XS and S cells only), and the grouped matrix products of the sequence-model
policy's expert layers (``ops/grouped_matmul.py``, which ``models/lm_layers.py`` imports from
there). The convolutions are ``flax.linen.Conv`` / ``lax.conv_transpose`` at their call sites."""

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
