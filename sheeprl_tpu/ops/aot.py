"""AOT registry entries for the lowering-sensitive custom ops (ROADMAP item 5).

Every ``jax.lax.platform_dependent`` branch in the tree must produce a VALID
TPU lowering path — verified off-chip by the fused-program contract sweep
(``sheeprl_tpu/analysis/programs.py``): ``.trace(...).lower(lowering_platforms=
("tpu",))`` runs the full jaxpr→StableHLO pipeline for the TPU platform on the
CPU mesh (the Pallas GRU lowers through Mosaic to a ``tpu_custom_call``). A
branch that only ever lowered on CPU could hide a TPU-side trace error until
the first paid chip window. These registrations generalize
``tests/test_ops/test_tpu_lowering.py``'s hand-written programs: the fused Pallas
LayerNorm-GRU step and the ``platform_dependent`` dispatch the models build
(tpu=Pallas / default=XLA reference) lower for TPU with the Mosaic custom call
present — and gradients THROUGH the dispatch lower too (the train programs
differentiate these ops).

None of these programs donate (they are op-level, not train-state programs),
so their contracts assert lowering validity + custom-call hygiene only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sheeprl_tpu import ops
from sheeprl_tpu.analysis.programs import register_fused_program


def _gru_args(B: int = 16, K: int = 128, H: int = 128):
    return (
        jnp.ones((B, K), jnp.float32),
        jnp.ones((B, H), jnp.float32),
        jnp.ones((K, 3 * H), jnp.float32),
        jnp.ones((3 * H,), jnp.float32),
        jnp.ones((3 * H,), jnp.float32),
        jnp.ones((3 * H,), jnp.float32),
    )


@register_fused_program(
    "ops.gru_pallas_step",
    donated=False,
    platforms=("tpu",),
    allow_custom_calls=("tpu_custom_call",),
    expect_custom_calls=("tpu_custom_call",),
    doc="fused Pallas LayerNorm-GRU step lowers for TPU with the Mosaic kernel",
)
def _aot_gru_pallas_step():
    def step(inp, hx, w, b, scale, bias):
        return ops.fused_ln_gru_step(inp, hx, w, b, scale, bias, eps=1e-3)

    return jax.jit(step), _gru_args()


@register_fused_program(
    "ops.gru_platform_dispatch",
    donated=False,
    platforms=("tpu",),
    allow_custom_calls=("tpu_custom_call",),
    expect_custom_calls=("tpu_custom_call",),
    doc="the exact tpu=Pallas/default=reference dispatch LayerNormGRUCell builds",
)
def _aot_gru_platform_dispatch():
    # the exact dispatch LayerNormGRUCell builds: the tpu branch is the Pallas
    # kernel, every other platform the XLA reference. A jit lowers for ONE
    # platform and keeps only that platform's branch (both one-platform
    # lowerings are pinned in tests/test_ops/test_tpu_lowering.py). Registered
    # tpu-only because the sweep's ("cpu", "tpu") form is a single
    # multi-platform module, which lowers every branch for every platform —
    # and Mosaic has no CPU lowering.
    def dispatch(inp, hx, w, b, scale, bias):
        return jax.lax.platform_dependent(
            tpu=lambda: ops.fused_ln_gru_step(inp, hx, w, b, scale, bias, eps=1e-3),
            default=lambda: ops.ln_gru_step_reference(inp, hx, w, b, scale, bias, eps=1e-3),
        )

    return jax.jit(dispatch), _gru_args()


@register_fused_program(
    "ops.gru_step_grad",
    donated=False,
    platforms=("tpu",),
    allow_custom_calls=("tpu_custom_call",),
    doc="gradient THROUGH the fused GRU step lowers for TPU (custom-VJP backward)",
)
def _aot_gru_step_grad():
    args = _gru_args()

    def loss(w):
        inp, hx, _, b, scale, bias = args
        return ops.fused_ln_gru_step(inp, hx, w, b, scale, bias, eps=1e-3).sum()

    # the custom-VJP backward recomputes in reference math — the property that
    # matters is that the WHOLE gradient program lowers cleanly for TPU
    return jax.jit(jax.grad(loss)), (args[2],)
