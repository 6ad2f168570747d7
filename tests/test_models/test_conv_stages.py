"""What an encoder or a decoder stage computes, held against the definitions evaluated in
numpy. The stages are the stock operations (``nn.Conv``, ``nn.ConvTranspose``, and
``lax.conv_transpose`` under the Dreamer-V3 decoder's head), so nothing else in the tree
says what they have to give; the parameter trees below are the checkpoint contract.

The definitions, NHWC inputs and HWIO kernels, stride 2 throughout:

- convolution, ``VALID`` after a symmetric zero pad ``p`` (``xp`` is the padded input):

      y[n, i, j, o] = sum_{a, b, c} xp[n, 2i + a, 2j + b, c] * w[a, b, c, o] + bias[o]

- transposed convolution (jax's, ``transpose_kernel=False``: the kernel is not flipped):
  the input dilated by 2 (a zero between neighbours) and zero-padded by ``(lo, hi)`` is
  correlated at stride 1 with the same kernel,

      y[n, i, j, o] = sum_{a, b, c} xdp[n, i + a, j + b, c] * w[a, b, c, o] + bias[o]

  with ``(lo, hi) = (k - 1, k - 1)`` for ``VALID`` (output ``2 (h - 1) + k``) and
  ``(2, 2)`` for kernel 4 ``SAME`` (output ``2 h``). Read per output phase ``(r, c)`` of
  the kernel-4 ``SAME`` form that is

      y[n, 2i + r, 2j + c, o] = sum_{a, b} x[n, i + r - 1 + a, j + c - 1 + b] * w[r + 2a, c + 2b, :, o]

  (``x`` zero outside its extent), and for ``VALID`` phase ``r`` takes the taps
  ``w[m0_r::2]``, ``m0_r = (k - 1 + r) % 2``, read at base offset
  ``(r + m0_r - (k - 1)) / 2``.

Both are one correlation of a prepared input, so the reference below is one function and
its transpose; no ``lax`` call is made on the reference's side.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.algos.dreamer_v2 import agent as dv2
from sheeprl_tpu.algos.dreamer_v3 import agent as dv3
from sheeprl_tpu.algos.sac_ae.agent import CNNDecoderAE
from sheeprl_tpu.models.models import CNN


def correlate(xp, w, stride):
    """``y[n, i, j, :] = sum_{a, b} xp[n, s i + a, s j + b, :] @ w[a, b]``, VALID."""
    k = w.shape[0]
    ho, wo = (xp.shape[1] - k) // stride + 1, (xp.shape[2] - k) // stride + 1
    y = np.zeros((xp.shape[0], ho, wo, w.shape[3]), np.float64)
    for a in range(k):
        for b in range(k):
            y += xp[:, a : a + stride * ho : stride, b : b + stride * wo : stride] @ w[a, b]
    return y


def correlate_grads(xp, w, stride, g):
    """The cotangents of ``xp`` and ``w`` under ``correlate``, given ``y``'s."""
    k = w.shape[0]
    ho, wo = g.shape[1], g.shape[2]
    dxp, dw = np.zeros_like(xp, np.float64), np.zeros_like(w, np.float64)
    for a in range(k):
        for b in range(k):
            rows, cols = slice(a, a + stride * ho, stride), slice(b, b + stride * wo, stride)
            dw[a, b] = np.einsum("nijc,nijo->co", xp[:, rows, cols], g)
            dxp[:, rows, cols] += g @ w[a, b].T
    return dxp, dw


def conv_s2(x, w, bias, pad, g=None):
    """The stride-2 convolution's value, or with a cotangent its (dx, dw, dbias)."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    xp = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    if g is None:
        return correlate(xp, w, 2) + bias
    dxp, dw = correlate_grads(xp, w, 2, np.asarray(g, np.float64))
    return dxp[:, pad : pad + x.shape[1], pad : pad + x.shape[2]], dw, g.sum((0, 1, 2))


def deconv_s2(x, w, bias, padding, g=None):
    """The stride-2 transposed convolution's value, or its (dx, dw, dbias)."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    n, h, wd, c = x.shape
    lo = 2 if padding == "SAME" else w.shape[0] - 1
    xdp = np.zeros((n, 2 * (h - 1) + 1 + 2 * lo, 2 * (wd - 1) + 1 + 2 * lo, c), np.float64)
    inner = (slice(None), slice(lo, lo + 2 * h - 1, 2), slice(lo, lo + 2 * wd - 1, 2))
    xdp[inner] = x
    if g is None:
        return correlate(xdp, w, 1) + bias
    dxdp, dw = correlate_grads(xdp, w, 1, np.asarray(g, np.float64))
    return dxdp[inner], dw, g.sum((0, 1, 2))


def _x(seed, shape):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape).astype(np.float32))


def _cotangent(y):
    # not uniform, so that errors in a gradient cannot cancel
    return jnp.cos(jnp.arange(y.size, dtype=jnp.float32).reshape(y.shape))


def _check_grads(got, want, atol=2e-4, rtol=1e-4):
    for name, a, b in zip(("dx", "dkernel", "dbias"), got, want):
        if a is not None:
            np.testing.assert_allclose(np.asarray(a), b, atol=atol, rtol=rtol, err_msg=name)


CONV_SHAPES = [
    (64, 4, 3, 8),  # DV1/DV2 encoder stage 1
    (31, 4, 8, 16),  # stage 2: odd extent, last row unused by VALID
    (14, 4, 16, 4),  # stage 3
    (6, 4, 8, 2),  # stage 4
    (10, 6, 2, 3),  # larger even kernel
    (9, 3, 4, 6),  # odd kernel
]


@pytest.mark.parametrize("h,k,ci,co", CONV_SHAPES)
def test_conv_stage_values_and_gradients(h, k, ci, co):
    """One stage of ``models.CNN``, as the Dreamer-V1/V2 encoders are built: VALID, bias."""
    x = _x(h * 100 + k, (5, h, h, ci))
    stage = CNN([co], [k], [2], activation=None, input_channel_first=False)
    params = stage.init(jax.random.PRNGKey(1), x)
    p = params["params"]["Conv_0"]
    p = {**p, "bias": p["bias"] + 0.5}  # the init is zero: a bias that is dropped has to show
    params = {"params": {"Conv_0": p}}
    y = stage.apply(params, x)
    np.testing.assert_allclose(y, conv_s2(x, p["kernel"], np.asarray(p["bias"]), 0), atol=1e-5, rtol=1e-5)
    cot = _cotangent(y)
    gp, gx = jax.grad(lambda q, x: (stage.apply(q, x) * cot).sum(), argnums=(0, 1))(params, x)
    gp = gp["params"]["Conv_0"]
    _check_grads((gx, gp["kernel"], gp["bias"]), conv_s2(x, p["kernel"], 0.0, 0, np.asarray(cot)))


@pytest.mark.parametrize("h,ci,co", [(64, 3, 4), (32, 4, 8), (8, 8, 16)])
def test_dreamer_v3_encoder_stage_pads_one(h, ci, co):
    """The Dreamer-V3 encoder's first stage as the encoder itself computes it: k=4, s=2,
    a zero pad of 1 on each side, no bias (what ``Conv_0`` returns, before the norm)."""
    x = _x(h, (5, ci, h, h))  # the encoder takes channel-first frames
    enc = dv3.CNNEncoder(keys=("rgb",), channels_multiplier=co, stages=1)
    params = enc.init(jax.random.PRNGKey(1), {"rgb": x})
    kernel = params["params"]["Conv_0"]["kernel"]
    assert set(params["params"]["Conv_0"]) == {"kernel"}

    def stage(params, x):
        _, state = enc.apply(params, {"rgb": x}, capture_intermediates=lambda m, _: m.name == "Conv_0",
                             mutable=["intermediates"])
        return state["intermediates"]["Conv_0"]["__call__"][0]

    y = stage(params, x)
    x_nhwc = np.moveaxis(np.asarray(x), 1, -1)
    np.testing.assert_allclose(y, conv_s2(x_nhwc, kernel, 0.0, 1), atol=1e-5, rtol=1e-5)
    cot = _cotangent(y)
    gp, gx = jax.grad(lambda q, x: (stage(q, x) * cot).sum(), argnums=(0, 1))(params, x)
    dx, dw, _ = conv_s2(x_nhwc, kernel, 0.0, 1, np.asarray(cot))
    _check_grads((gx, gp["params"]["Conv_0"]["kernel"], None), (np.moveaxis(dx, -1, 1), dw, None))


def _same_stage(features, use_bias, dtype=jnp.float32):
    """A kernel-4 ``SAME`` stage as ``dreamer_v3.CNNDecoder`` builds it: the head is the
    one stage with a bias."""
    if use_bias:
        return dv3.ConvTransposeHead(features, kernel_init=dv3.hafner_init, dtype=dtype)
    return nn.ConvTranspose(features, (4, 4), strides=(2, 2), padding="SAME", use_bias=False,
                            kernel_init=dv3.hafner_init, dtype=dtype)


def _with_bias(params, seed=3):
    """The parameters with a bias that is not the zero it starts as."""
    p = dict(params["params"])
    if "bias" in p:
        p["bias"] = _x(seed, p["bias"].shape)
    return {"params": p}, np.asarray(p.get("bias", 0.0))


@pytest.mark.parametrize("shape", [(2, 4, 4, 8), (3, 8, 8, 3), (1, 5, 7, 2)])
@pytest.mark.parametrize("use_bias", [True, False])
def test_same_deconv_stage_forward(shape, use_bias):
    x = _x(0, shape)
    stage = _same_stage(6, use_bias)
    params, bias = _with_bias(stage.init(jax.random.PRNGKey(0), x))
    y = stage.apply(params, x)
    assert y.shape == (shape[0], 2 * shape[1], 2 * shape[2], 6)
    np.testing.assert_allclose(y, deconv_s2(x, params["params"]["kernel"], bias, "SAME"), atol=1e-5, rtol=1e-5)


def _deconv_gradient_case(stage, x, padding):
    params, _ = _with_bias(stage.init(jax.random.PRNGKey(1), x))
    cot = _cotangent(stage.apply(params, x))
    gp, gx = jax.grad(lambda q, x: (stage.apply(q, x) * cot).sum(), argnums=(0, 1))(params, x)
    want = deconv_s2(x, params["params"]["kernel"], 0.0, padding, np.asarray(cot))
    _check_grads((gx, gp["params"]["kernel"], gp["params"]["bias"]), want)


def test_same_deconv_stage_gradients():
    _deconv_gradient_case(_same_stage(3, True), _x(1, (2, 6, 6, 4)), "SAME")


def _valid_stage(features, k):
    """A ``VALID`` stage as the Dreamer-V1/V2 decoders (k = 5, 5, 6, 6) and SAC-AE's (k = 4) build it."""
    return nn.ConvTranspose(features, (k, k), strides=(2, 2), padding="VALID")


@pytest.mark.parametrize("k", [4, 5, 6])
@pytest.mark.parametrize("shape", [(2, 1, 1, 8), (2, 5, 7, 3), (1, 13, 13, 4)])
def test_valid_deconv_stage_forward(k, shape):
    x = _x(0, shape)
    stage = _valid_stage(4, k)
    params, bias = _with_bias(stage.init(jax.random.PRNGKey(0), x))
    y = stage.apply(params, x)
    assert y.shape == (shape[0], 2 * (shape[1] - 1) + k, 2 * (shape[2] - 1) + k, 4)
    np.testing.assert_allclose(y, deconv_s2(x, params["params"]["kernel"], bias, "VALID"), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("k", [4, 5, 6])
def test_valid_deconv_stage_gradients(k):
    _deconv_gradient_case(_valid_stage(2, k), _x(1, (2, 5, 5, 3)), "VALID")


def test_conv_stage_bf16_compute_dtype():
    x = _x(0, (2, 16, 16, 3))
    stage = CNN([4], [4], [2], activation=None, input_channel_first=False, dtype=jnp.bfloat16)
    params = stage.init(jax.random.PRNGKey(0), x)
    assert params["params"]["Conv_0"]["kernel"].dtype == jnp.float32
    y = stage.apply(params, x)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y, np.float32), conv_s2(x, params["params"]["Conv_0"]["kernel"], 0.0, 0), atol=0.1, rtol=0.1
    )
    g = jax.grad(lambda p: stage.apply(p, x).astype(jnp.float32).sum())(params)
    assert jnp.isfinite(g["params"]["Conv_0"]["kernel"]).all()


def test_deconv_head_bf16_tracks_fp32():
    x = _x(2, (2, 4, 4, 3))
    params, bias = _with_bias(_same_stage(4, True).init(jax.random.PRNGKey(2), x))
    assert params["params"]["kernel"].dtype == jnp.float32
    y = _same_stage(4, True, dtype=jnp.bfloat16).apply(params, x)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y, np.float32), deconv_s2(x, params["params"]["kernel"], bias, "SAME"), atol=0.1, rtol=0.1
    )


def _tree(params):
    return jax.tree_util.tree_map(lambda a: (tuple(a.shape), str(a.dtype)), params["params"])


def _f32(*shape):
    return (shape, "float32")


# what a checkpoint, perfbench's reference and the adapters read: names, HWIO kernels, float32
PARAMETER_TREES = {
    "dreamer_v3.CNNEncoder": (
        lambda: (dv3.CNNEncoder(keys=("rgb",), channels_multiplier=2, stages=2), {"rgb": jnp.zeros((1, 3, 16, 16))}),
        {
            "Conv_0": {"kernel": _f32(4, 4, 3, 2)},
            "LayerNorm_0": {"scale": _f32(2), "bias": _f32(2)},
            "Conv_1": {"kernel": _f32(4, 4, 2, 4)},
            "LayerNorm_1": {"scale": _f32(4), "bias": _f32(4)},
        },
    ),
    "dreamer_v3.CNNDecoder": (
        lambda: (
            dv3.CNNDecoder(keys=("rgb",), output_channels=(3,), channels_multiplier=2, image_size=(16, 16), stages=2),
            jnp.zeros((1, 5)),
        ),
        {
            "Dense_0": {"kernel": _f32(5, 64), "bias": _f32(64)},
            "ConvTranspose_0": {"kernel": _f32(4, 4, 4, 2)},
            "LayerNorm_0": {"scale": _f32(2), "bias": _f32(2)},
            "ConvTranspose_1": {"kernel": _f32(4, 4, 2, 3), "bias": _f32(3)},
        },
    ),
    "dreamer_v2.CNNEncoder": (
        lambda: (dv2.CNNEncoder(keys=("rgb",), channels_multiplier=2), {"rgb": jnp.zeros((1, 3, 64, 64))}),
        {
            f"Conv_{i}": {"kernel": _f32(4, 4, ci, co), "bias": _f32(co)}
            for i, (ci, co) in enumerate([(3, 2), (2, 4), (4, 8), (8, 16)])
        },
    ),
    "dreamer_v2.CNNDecoder": (
        lambda: (
            dv2.CNNDecoder(keys=("rgb",), output_channels=(3,), channels_multiplier=2, cnn_encoder_output_dim=8),
            jnp.zeros((1, 5)),
        ),
        {
            "Dense_0": {"kernel": _f32(5, 8), "bias": _f32(8)},
            **{
                f"ConvTranspose_{i}": {"kernel": _f32(k, k, ci, co), "bias": _f32(co)}
                for i, (k, ci, co) in enumerate([(5, 8, 8), (5, 8, 4), (6, 4, 2), (6, 2, 3)])
            },
        },
    ),
    "sac_ae.CNNDecoderAE": (
        lambda: (CNNDecoderAE(keys=("rgb",), output_channels=(3,), conv_shape=(3, 3, 32)), jnp.zeros((1, 5))),
        {
            "Dense_0": {"kernel": _f32(5, 288), "bias": _f32(288)},
            **{f"ConvTranspose_{i}": {"kernel": _f32(3, 3, 32, 32), "bias": _f32(32)} for i in range(3)},
            "ConvTranspose_3": {"kernel": _f32(4, 4, 32, 3), "bias": _f32(3)},
        },
    ),
    "models.CNN": (
        lambda: (CNN([2, 4, 6], [4, 3, 4], [2, 1, 2], paddings=[1, 0, 0]), jnp.zeros((1, 3, 16, 16))),
        {
            "Conv_0": {"kernel": _f32(4, 4, 3, 2), "bias": _f32(2)},
            "Conv_1": {"kernel": _f32(3, 3, 2, 4), "bias": _f32(4)},
            "Conv_2": {"kernel": _f32(4, 4, 4, 6), "bias": _f32(6)},
        },
    ),
}


@pytest.mark.parametrize("name", sorted(PARAMETER_TREES))
def test_parameter_tree_is_the_checkpoint_contract(name):
    build, expected = PARAMETER_TREES[name]
    module, x = build()
    assert _tree(module.init(jax.random.PRNGKey(0), x)) == expected
