"""The grouped matmul kernels compiled for a v5e that is described and not attached, at the
LFM2 cell's shapes and the tilings `ops/grouped_matmul.py` picks for them: what Pallas'
interpreter cannot show (a tile Mosaic refuses, more VMEM than the kernels are given).
The topology is described inside a fixture, so every worker collects the same tests and
only the one that is given this file loads the TPU's library."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sheeprl_tpu.ops import grouped_matmul as gm

M, GROUPS = 32768, 8


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


@pytest.mark.parametrize("k, n", [(2048, 1792), (1792, 2048)], ids=["w1_w3", "w2"])
@pytest.mark.parametrize("product", ["forward", "input_gradient", "weight_gradient"])
def test_the_cells_products_compile_for_the_chip_at_their_tilings(product, k, n, one_chip):
    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    sizes = shape(GROUPS, dtype=jnp.int32)
    if product == "forward":
        fn, args = (lambda l, r, s: gm.gmm(l, r, s, gm.gmm_tiling(M, k, n), 3)), (shape(M, k), shape(GROUPS, k, n), sizes)
    elif product == "input_gradient":
        fn = lambda g, r, s: gm.gmm(g, r, s, gm.gmm_tiling(M, n, k), 3, transpose_rhs=True)  # noqa: E731
        args = (shape(M, n), shape(GROUPS, k, n), sizes)
    else:
        fn, args = (lambda l, g, s: gm.tgmm(l, g, s, gm.tgmm_tiling(M, k, n), 3)), (shape(M, k), shape(M, n), sizes)
    with jax.default_matmul_precision("high"):  # what a run's program is traced under
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
