"""The grouped matmul kernels compiled for a v5e that is described and not attached, at the
LFM2 and Moonlight cells' shapes and the tilings `ops/grouped_matmul.py` picks for them, and the
latent-cache decode kernel (`ops/latent_decode.py`) at the Moonlight cell's and the delta-rule
decode kernel (`ops/delta_rule_decode.py`) at the Qwen3-Next cell's: what Pallas'
interpreter cannot show (a tile Mosaic refuses, more VMEM than the kernels are given, a DMA of
a slice that is not whole tiles).
The topology is described inside a fixture, so every worker collects the same tests and
only the one that is given this file loads the TPU's library."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sheeprl_tpu.ops import grouped_matmul as gm

M, GROUPS = 32768, 8
# [rows of the update's buffers, hidden, expert width] of the three trunks' cells (`lm_layers.dispatch_rows`)
CELLS = {"lfm2": (16384, 2048, 1792), "qwen3_next": (10240, 2048, 512), "moonlight": (12288, 2048, 1408)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a chip that is not attached cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiles(product, m, k, n, one_chip):
    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    sizes = shape(GROUPS, dtype=jnp.int32)
    if product == "forward":
        fn, args = (lambda l, r, s: gm.gmm(l, r, s, gm.gmm_tiling(m, k, n), 3)), (shape(m, k), shape(GROUPS, k, n), sizes)
    elif product == "input_gradient":
        fn = lambda g, r, s: gm.gmm(g, r, s, gm.gmm_tiling(m, n, k), 3, transpose_rhs=True)  # noqa: E731
        args = (shape(m, n), shape(GROUPS, k, n), sizes)
    else:
        fn, args = (lambda l, g, s: gm.tgmm(l, g, s, gm.tgmm_tiling(m, k, n), 3)), (shape(m, k), shape(m, n), sizes)
    with jax.default_matmul_precision("high"):  # what a run's program is traced under
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k, n", [(2048, 1792), (1792, 2048)], ids=["w1_w3", "w2"])
@pytest.mark.parametrize("product", ["forward", "input_gradient", "weight_gradient"])
def test_the_cells_products_compile_for_the_chip_at_their_tilings(product, k, n, one_chip):
    _compiles(product, M, k, n, one_chip)


@pytest.mark.parametrize("k, n", [(2048, 1408), (1408, 2048)], ids=["w1_w3", "w2"])
@pytest.mark.parametrize("product", ["forward", "input_gradient", "weight_gradient"])
def test_the_moonlight_cells_products_compile_for_the_chip_at_the_whole_width(product, k, n, one_chip):
    """Width 1408 = 11 x 128: an output tile of the whole width, 54.7 MB of the kernels' VMEM."""
    _compiles(product, CELLS["moonlight"][0], k, n, one_chip)


# (gmm forward, gmm input gradient, tgmm) of the `w1`/`w3` products, then of `w2`'s: LFM2's and Qwen3-Next's as
# on the commit before the third trunk (841ea4e), Moonlight's as measured on the chip (PERF.md, section 5)
TILINGS = {
    "lfm2": (((128, 2048, 896), (128, 1792, 1024), (128, 1024, 1792)), ((128, 1792, 1024), (128, 2048, 896), (128, 896, 2048))),
    "qwen3_next": (((128, 2048, 512), (128, 512, 1024), (128, 2048, 512)), ((128, 512, 1024), (128, 2048, 512), (128, 512, 2048))),
    "moonlight": (((128, 2048, 1408), (128, 1408, 1024), (128, 2048, 1408)), ((128, 1408, 1024), (128, 2048, 1408), (128, 1408, 2048))),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("product", ["w1_w3", "w2"])
def test_the_tilings_of_the_three_trunks_grouped_shapes(cell, product):
    m, hidden, width = CELLS[cell]
    k, n = (hidden, width) if product == "w1_w3" else (width, hidden)
    tilings = gm.gmm_tiling(m, k, n), gm.gmm_tiling(m, n, k), gm.tgmm_tiling(m, k, n)
    assert tilings == TILINGS[cell][product == "w2"]
    assert all(gm.gmm_vmem_bytes(t) <= gm.VMEM_BUDGET_BYTES for t in tilings[:2]) and gm.tgmm_vmem_bytes(tilings[2]) <= gm.VMEM_BUDGET_BYTES
    if cell == "moonlight":  # the whole width: a row tile is read once, and a step sits over the ridge
        whole = gm.gmm_tiling(m, hidden, width)
        assert whole[2] == width and gm.gmm_vmem_bytes(whole) == 54_657_024
        assert gm.gmm_flops_per_byte(whole, 3, True) > gm.RIDGE_FLOPS_PER_BYTE > gm.gmm_flops_per_byte((128, 2048, 128), 3, True)


@pytest.mark.parametrize("passes, precision", [(3, "high"), (6, "highest"), (1, "default")])
def test_the_latent_decode_kernel_compiles_for_the_chip_in_the_decode_loop(passes, precision, one_chip):
    """A ``[64, 512, 576]`` latent cache (the 576 floats a row are no whole number of lane tiles),
    16 heads, in the loop over 512 decode steps that carries the cache: inside the loop's body
    nothing but the kernel's aliased output is a whole cache."""
    from sheeprl_tpu.ops import latent_decode

    batch, positions, width, heads = 64, 512, 576, 16

    def decode(cache, rows, queries):
        def body(cache, x):
            t, row, query = x
            weighed, total, cache = latent_decode.latent_decode(cache, t, row, query, passes)
            return cache, weighed / total[..., None]

        return jax.lax.scan(body, cache, (jnp.arange(positions, dtype=jnp.int32), rows, queries))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in
              ((batch, positions, width), (positions, batch, width), (positions, batch, heads, width))]
    with jax.default_matmul_precision(precision):
        text = jax.jit(decode, donate_argnums=0).lower(*shapes).compile().as_text()
    body = next(block for block in text.split("\n\n") if "custom_call_target=\"tpu_custom_call\"" in block)
    whole = [line for line in body.splitlines() if " = f32[64,512,576]{" in line]
    assert whole and all("get-tuple-element(" in line for line in whole), whole


@pytest.mark.parametrize("batch, heads", [(64, 32), (16, 4)], ids=["cell", "smoke"])
def test_the_delta_rule_decode_kernel_compiles_for_the_chip_in_the_decode_loop(batch, heads, one_chip):
    """A layer's matrix state ``[batch, heads, 128, 128]`` (the Qwen3-Next cell's, and
    `chip_smoke.py`'s four value heads) in the loop over 512 decode steps that carries it: inside
    the loop's body nothing but the kernel's aliased output is a whole state."""
    from sheeprl_tpu.ops import delta_rule_decode

    width, steps = 128, 512

    def decode(state, q, k, v, g, beta):
        def body(state, x):
            out, state = delta_rule_decode.delta_rule_decode(state, *x)
            return state, out

        return jax.lax.scan(body, state, (q, k, v, g, beta))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in
              [(batch, heads, width, width)] + [(steps, batch, heads, width)] * 3 + [(steps, batch, heads)] * 2]
    with jax.default_matmul_precision("high"):
        text = jax.jit(decode, donate_argnums=0).lower(*shapes).compile().as_text()
    body = next(block for block in text.split("\n\n") if "custom_call_target=\"tpu_custom_call\"" in block)
    whole = [line for line in body.splitlines() if f" = f32[{batch},{heads},{width},{width}]{{" in line]
    assert whole and all("get-tuple-element(" in line for line in whole), whole
