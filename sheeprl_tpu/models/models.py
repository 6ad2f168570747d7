"""Model building blocks, Flax-native.

Re-provides the reference block library (sheeprl/models/models.py: MLP:16, CNN:122,
DeCNN:205, NatureCNN:288, LayerNormGRUCell:331, MultiEncoder:413, MultiDecoder:478,
LayerNormChannelLast:507) as Flax linen modules designed for the TPU:

- images flow **NHWC** internally (XLA's preferred TPU layout; the host side keeps the
  reference's channel-first arrays and encoders transpose on entry);
- every block takes a ``dtype`` so bf16-mixed runs keep params in fp32 and compute in
  bf16 on the MXU;
- the GRU cell is a single fused step usable under ``lax.scan`` (the reference calls it
  per-timestep from a Python loop, sheeprl/algos/dreamer_v3/dreamer_v3.py:86-97).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu import ops

ModuleType = Optional[str]
ArgType = Union[Tuple[Any, ...], Dict[str, Any], None]

_ACTIVATIONS: Dict[str, Callable] = {
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "leaky_relu": jax.nn.leaky_relu,
    "leakyrelu": jax.nn.leaky_relu,
    "elu": jax.nn.elu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,
    "softplus": jax.nn.softplus,
    "identity": lambda x: x,
    "none": lambda x: x,
}


def resolve_activation(act: Union[None, str, Callable]) -> Callable:
    """Accept jax-style names ("tanh"), torch-style names ("torch.nn.Tanh") and plain
    callables, so reference config trees run unmodified."""
    if act is None:
        return lambda x: x
    if callable(act):
        return act
    name = str(act).split(".")[-1].lower()
    if name in _ACTIVATIONS:
        return _ACTIVATIONS[name]
    raise ValueError(f"unknown activation {act!r}")


class MLP(nn.Module):
    """Per-layer [Dense → dropout? → norm? → act?] stack with optional flatten of the
    input (reference models.py:16-119; layer ordering per its miniblock contract:
    dropout before the normalization, both before the activation)."""

    hidden_sizes: Sequence[int] = ()
    output_dim: Optional[int] = None
    activation: Union[None, str, Callable] = "relu"
    layer_norm: bool = False
    dropout: float = 0.0
    flatten_dim: Optional[int] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        act = resolve_activation(self.activation)
        if self.flatten_dim is not None:
            x = jnp.reshape(x, (*x.shape[: self.flatten_dim], -1))
        x = x.astype(self.dtype)
        for size in self.hidden_sizes:
            x = nn.Dense(size, dtype=self.dtype)(x)
            if self.dropout > 0.0:
                x = nn.Dropout(rate=self.dropout, deterministic=deterministic)(x)
            if self.layer_norm:
                x = nn.LayerNorm(dtype=self.dtype, epsilon=1e-5)(x)
            x = act(x)
        if self.output_dim is not None:
            x = nn.Dense(self.output_dim, dtype=self.dtype)(x)
        return x


class CNN(nn.Module):
    """Conv stack over NHWC inputs; accepts NCHW and transposes on entry
    (reference models.py:122-202 with torch's NCHW)."""

    channels: Sequence[int]
    kernel_sizes: Sequence[int]
    strides: Sequence[int]
    paddings: Union[str, Sequence[int]] = "VALID"
    activation: Union[None, str, Callable] = "relu"
    layer_norm: bool = False
    input_channel_first: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = resolve_activation(self.activation)
        if self.input_channel_first:
            x = jnp.moveaxis(x, -3, -1)  # NCHW -> NHWC
        x = x.astype(self.dtype)
        for i, (ch, k, s) in enumerate(zip(self.channels, self.kernel_sizes, self.strides)):
            if isinstance(self.paddings, str):
                padding = self.paddings
            else:
                p = self.paddings[i] if not isinstance(self.paddings, int) else self.paddings
                padding = [(p, p), (p, p)]
            x = nn.Conv(ch, (k, k), strides=(s, s), padding=padding, dtype=self.dtype, name=f"Conv_{i}")(x)
            if self.layer_norm:
                x = nn.LayerNorm(dtype=self.dtype, epsilon=1e-3)(x)  # NHWC: normalize channels
            x = act(x)
        return x


class DeCNN(nn.Module):
    """Transposed-conv stack over NHWC latents, producing NCHW outputs to match the
    buffer layout (reference models.py:205-285)."""

    channels: Sequence[int]
    kernel_sizes: Sequence[int]
    strides: Sequence[int]
    paddings: Union[str, Sequence[int]] = "VALID"
    activation: Union[None, str, Callable] = "relu"
    layer_norm: bool = False
    output_channel_first: bool = True
    final_activation: Union[None, str, Callable] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        act = resolve_activation(self.activation)
        n = len(self.channels)
        for i, (ch, k, s) in enumerate(zip(self.channels, self.kernel_sizes, self.strides)):
            if isinstance(self.paddings, str):
                padding = self.paddings
            else:
                p = self.paddings[i] if not isinstance(self.paddings, int) else self.paddings
                padding = [(p, p), (p, p)]
            x = nn.ConvTranspose(ch, (k, k), strides=(s, s), padding=padding, dtype=self.dtype)(x)
            last = i == n - 1
            if not last:
                if self.layer_norm:
                    x = nn.LayerNorm(dtype=self.dtype, epsilon=1e-3)(x)
                x = act(x)
            elif self.final_activation is not None:
                x = resolve_activation(self.final_activation)(x)
        if self.output_channel_first:
            x = jnp.moveaxis(x, -1, -3)  # NHWC -> NCHW
        return x


class NatureCNN(nn.Module):
    """The classic DQN encoder (reference models.py:288-328): 32/64/64 convs + dense."""

    features_dim: int
    screen_size: int = 64
    in_channels: int = 4
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = CNN(
            channels=(32, 64, 64),
            kernel_sizes=(8, 4, 3),
            strides=(4, 2, 1),
            paddings="VALID",
            activation="relu",
            dtype=self.dtype,
        )(x)
        x = jnp.reshape(x, (*x.shape[:-3], -1))
        x = nn.Dense(self.features_dim, dtype=self.dtype)(x)
        return jax.nn.relu(x)


def tapped(module: nn.Module, name: str, x: jax.Array, y: jax.Array) -> jax.Array:
    """``y``, what ``module`` adds to or takes from a product of ``x`` with one of its
    kernels, made reachable from outside ``apply``: plus the variable ``taps/<name>``
    where the caller passed one, and with ``x`` kept as ``tap_inputs/<name>`` where the
    caller made that collection mutable. A zero tap's cotangent is the product's, so
    a caller that steps the module in a ``lax.scan`` can form the kernel's gradient
    once, after the loop (``DV3Agent.dynamic_scan``). With neither, ``y`` itself."""
    module.sow("tap_inputs", name, x, reduce_fn=lambda _, new: new, init_fn=lambda: None)
    tap = module.get_variable("taps", name, None)
    return y if tap is None else y + tap


class LayerNormGRUCell(nn.Module):
    """GRU cell with layer-norm applied to the stacked input/recurrent projection
    (reference models.py:331-411: norm after the input projection, before gating).

    One fused matmul computes all three gates — the shape the MXU wants. Usable as a
    ``lax.scan`` body for full-sequence unrolls.

    ``fused_step``: whether a TPU lowering of a step the Pallas kernel compiles for
    takes the kernel. A Pallas kernel does not partition, so whoever builds the module
    says whether its programs run on one device (the agents' ``build_agent``, from the
    fabric they are handed); the XLA step is the default.
    """

    hidden_size: int
    bias: bool = True
    batch_first: bool = False
    layer_norm: bool = True
    layer_norm_eps: float = 1e-3
    kernel_init: Optional[Callable] = None
    fused_step: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, hx: jax.Array, x: jax.Array) -> jax.Array:
        inp = jnp.concatenate([x, hx], axis=-1).astype(self.dtype)
        kernel_init = self.kernel_init or nn.initializers.lecun_normal()
        # params stay float32 (flax's param_dtype convention — bf16-mixed keeps f32
        # master weights); self.dtype only selects the COMPUTE dtype
        w = self.param("kernel", kernel_init, (inp.shape[-1], 3 * self.hidden_size), jnp.float32)
        b = (
            self.param("bias", nn.initializers.zeros_init(), (3 * self.hidden_size,), jnp.float32)
            if self.bias
            else jnp.zeros((3 * self.hidden_size,), jnp.float32)
        )
        w = w.astype(self.dtype)
        # the bias is added to the product before the norm, so a [B, 3H] tap rides on it
        b = tapped(self, "gates", inp, b.astype(self.dtype))
        if self.layer_norm:
            scale = self.param(
                "ln_scale", nn.initializers.ones_init(), (3 * self.hidden_size,), jnp.float32
            )
            offset = self.param(
                "ln_bias", nn.initializers.zeros_init(), (3 * self.hidden_size,), jnp.float32
            )
            # the fused Pallas step (matmul + layernorm + gating in one VMEM pass)
            # is the branch a TPU lowering takes when the shape is one the kernel
            # compiles for; platform_dependent chooses by LOWERING platform, so
            # an act program placed on the host CPU of a TPU process (ActPlacement)
            # lowers the XLA reference and never sees Mosaic, while the coupled
            # Dreamer-V3 player, which runs on the chip, takes the kernel at
            # num_envs rows (chip_smoke.py checks one row) — same math,
            # parity-tested in tests/test_ops.
            hx_d = hx.astype(self.dtype)
            if (
                self.fused_step
                and inp.ndim == 2
                and b.ndim == 1  # the kernel takes a per-feature bias: a tapped step is XLA's
                and ops.pallas_gru_applicable(inp.shape[-1], self.hidden_size)
            ):
                return jax.lax.platform_dependent(
                    tpu=lambda: ops.fused_ln_gru_step(
                        inp, hx_d, w, b, scale, offset, eps=self.layer_norm_eps
                    ),
                    default=lambda: ops.ln_gru_step_reference(
                        inp, hx_d, w, b, scale, offset, eps=self.layer_norm_eps
                    ),
                ).astype(self.dtype)
            return ops.ln_gru_step_reference(
                inp, hx_d, w, b, scale, offset, eps=self.layer_norm_eps
            ).astype(self.dtype)
        gates = inp @ w + b
        reset, cand, update = jnp.split(gates, 3, axis=-1)
        reset = jax.nn.sigmoid(reset)
        cand = jnp.tanh(reset * cand)
        update = jax.nn.sigmoid(update - 1)
        return update * cand + (1 - update) * hx


class MultiEncoder(nn.Module):
    """Fuse per-key cnn/mlp encoders over a dict observation
    (reference models.py:413-475): outputs are concatenated feature vectors."""

    cnn_encoder: Optional[nn.Module]
    mlp_encoder: Optional[nn.Module]

    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        outs = []
        if self.cnn_encoder is not None:
            outs.append(self.cnn_encoder(obs))
        if self.mlp_encoder is not None:
            outs.append(self.mlp_encoder(obs))
        if not outs:
            raise ValueError("there must be at least one encoder (cnn or mlp)")
        return jnp.concatenate(outs, axis=-1)


class MultiDecoder(nn.Module):
    """Per-key cnn/mlp decoders from a shared latent (reference models.py:478-504)."""

    cnn_decoder: Optional[nn.Module]
    mlp_decoder: Optional[nn.Module]

    def __call__(self, latents: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(latents))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(latents))
        return out
