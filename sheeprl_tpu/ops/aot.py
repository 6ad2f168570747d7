"""AOT registry entries for the lowering-sensitive custom ops (ROADMAP item 5).

Every ``jax.lax.platform_dependent`` branch in the tree must produce a VALID
TPU lowering path — verified off-chip by the fused-program contract sweep
(``sheeprl_tpu/analysis/programs.py``): ``.trace(...).lower(lowering_platforms=
("tpu",))`` runs the full jaxpr→StableHLO pipeline for the TPU platform on the
CPU mesh (the Pallas GRU lowers through Mosaic to a ``tpu_custom_call``). A
branch that only ever lowered on CPU could hide a TPU-side trace error until
the first paid chip window. These registrations generalize
``tests/test_ops/test_tpu_lowering.py``'s hand-written programs:

- the fused Pallas LayerNorm-GRU step and the ``platform_dependent`` dispatch
  the models build (tpu=Pallas / default=XLA reference) lower for TPU with the
  Mosaic custom call present — and gradients THROUGH the dispatch lower too
  (the train programs differentiate these ops);
- the s2d fast-conv gate (``ops/conv.py``) and the im2col/phase deconv gate
  (``ops/deconv.py``) lower for cpu AND tpu in one multi-platform lowering.

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


@register_fused_program(
    "ops.fast_conv",
    donated=False,
    platforms=("cpu", "tpu"),
    doc="s2d fast-conv gate (cpu=s2d decomposition / default=native) lowers for both platforms",
)
def _aot_fast_conv():
    from sheeprl_tpu.ops.conv import FastConv2x

    module = FastConv2x(features=8, kernel_size=4, max_fast_cin=8)
    x = jnp.ones((2, 16, 16, 3), jnp.float32)
    params = module.init(jax.random.PRNGKey(0), x)
    return jax.jit(lambda p, x: module.apply(p, x)), (params, x)


@register_fused_program(
    "ops.fast_conv_grad",
    donated=False,
    platforms=("cpu", "tpu"),
    doc="gradient through the conv gate lowers for both platforms",
)
def _aot_fast_conv_grad():
    from sheeprl_tpu.ops.conv import FastConv2x

    module = FastConv2x(features=8, kernel_size=4, max_fast_cin=8)
    x = jnp.ones((2, 16, 16, 3), jnp.float32)
    params = module.init(jax.random.PRNGKey(0), x)

    def loss(p):
        return module.apply(p, x).sum()

    return jax.jit(jax.grad(loss)), (params,)


@register_fused_program(
    "ops.fast_deconv",
    donated=False,
    platforms=("cpu", "tpu"),
    doc="im2col/phase deconv gate (cpu=phase form / default=native) lowers for both platforms",
)
def _aot_fast_deconv():
    from sheeprl_tpu.ops.deconv import FusedConvTranspose4x4S2

    module = FusedConvTranspose4x4S2(features=6)
    x = jnp.ones((2, 8, 8, 4), jnp.float32)
    params = module.init(jax.random.PRNGKey(0), x)
    return jax.jit(lambda p, x: module.apply(p, x)), (params, x)
