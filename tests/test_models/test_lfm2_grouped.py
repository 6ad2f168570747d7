"""The update's grouped expert products (`sheeprl_tpu/ops/grouped_matmul.py`, and
`models/lfm2.py`'s use of them): the bf16 split, the three-term product against float64,
the kernels (in Pallas' interpreter, on the CPU) and their `custom_vjp` against
`lax.ragged_dot`'s own values and gradients, the tilings, the tile-fill counter and how
the number of passes follows the ambient matmul precision."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import lfm2, lm_layers
from sheeprl_tpu.ops import grouped_matmul as gm

# [M, K, N] of the cell's three products (w1 and w3: hidden -> expert width; w2: back), 8 groups
CELL_SHAPES = {"w1_w3": (32768, 2048, 1792), "w2": (32768, 1792, 2048)}
# the three-pass product of unit normals against float64, over the largest entry: the dropped
# `lo.lo` term and the split's own residue are 2^-16 of each term and add up like a random walk
THREE_PASS_BOUND = 2e-5


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


def test_the_split_restores_x_to_2_to_the_minus_16():
    keys = jax.random.split(jax.random.PRNGKey(0))
    x = np.asarray(jax.random.normal(keys[0], (64, 512)) * jnp.exp(3.0 * jax.random.normal(keys[1], (64, 512))), np.float64)
    hi, lo = gm.split_bf16(jnp.asarray(x, jnp.float32))
    assert hi.dtype == lo.dtype == jnp.bfloat16
    assert np.all(np.abs(np.asarray(hi, np.float64) + np.asarray(lo, np.float64) - x) <= 2.0 ** -16 * np.abs(x))
    assert np.any(np.abs(np.asarray(hi, np.float64) - x) > 2.0 ** -10 * np.abs(x))  # and one half alone does not


@pytest.mark.parametrize("k", [2048, 1792])
def test_three_passes_sit_between_one_and_six_at_the_cells_contraction(k):
    a, b = jax.random.normal(jax.random.PRNGKey(2), (96, k)), jax.random.normal(jax.random.PRNGKey(3), (k, 160))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    dims = (((1,), (0,)), ((), ()))
    one, three, six = (rel(gm.dot_passes(a, b, dims, passes), exact) for passes in (1, 3, 6))
    assert six < 1e-6 and three < THREE_PASS_BOUND and three - six < THREE_PASS_BOUND
    assert one > 50 * three  # a pass is 2^-8 a term, three are 2^-16


def test_a_product_refuses_a_number_of_passes_it_does_not_know():
    with pytest.raises(ValueError, match="1, 3 or 6"):
        gm.dot_passes(jnp.ones((8, 8)), jnp.ones((8, 8)), (((1,), (0,)), ((), ())), 2)


def through_the_kernels(monkeypatch):
    """`grouped_matmul` as a TPU run takes it, with the kernels in Pallas' interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(lm_layers, "_interpret", lambda: True)


@pytest.mark.parametrize("passes, precision, bound", [(3, "high", THREE_PASS_BOUND), (6, "highest", 2e-6)])
@pytest.mark.parametrize("sizes", [[100, 0, 215, 60], [128, 128, 128, 0], [0, 0, 0, 300], [0, 0, 0, 0], [383, 0, 1, 0]],
                         ids=["uneven", "aligned", "one_group", "no_pairs", "a_row_alone"])
def test_the_products_and_their_gradients_agree_with_ragged_dots_own(sizes, passes, precision, bound, monkeypatch):
    """Forward, input gradient and weight gradient of the kernels' `custom_vjp` against
    `lax.ragged_dot` and ITS gradients at `highest`, with uneven and with empty groups;
    rows past `sum(group_sizes)` read 0 both ways."""
    m, k, n, groups = 384, 256, 128, 4
    keys = jax.random.split(jax.random.PRNGKey(sum(sizes)), 3)
    rows, weights = jax.random.normal(keys[0], (m, k)), jax.random.normal(keys[1], (groups, k, n))
    cotangent = jax.random.normal(keys[2], (m, n))
    group_sizes, landed = jnp.array(sizes, jnp.int32), sum(sizes)
    valid = jnp.arange(m) < landed

    def loss(rows, weights):
        return jnp.sum(lfm2.grouped_matmul(rows, weights, group_sizes, valid) * cotangent)

    with jax.default_matmul_precision("highest"):
        expected = jax.lax.ragged_dot(jnp.where(valid[:, None], rows, 0.0), weights, group_sizes)
        expected_grads = jax.grad(loss, argnums=(0, 1))(rows, weights)  # the CPU path: `ragged_dot`
    through_the_kernels(monkeypatch)
    with jax.default_matmul_precision(precision):
        assert lfm2.matmul_passes() == passes
        out = lfm2.grouped_matmul(rows, weights, group_sizes, valid)
        d_rows, d_weights = jax.grad(loss, argnums=(0, 1))(rows, weights)
    scale = float(np.abs(expected).max()) or 1.0
    assert np.abs(np.asarray(out) - np.asarray(expected)).max() <= bound * scale
    assert not np.any(np.asarray(out[landed:])) and not np.any(np.asarray(d_rows[landed:]))
    for got, want in ((d_rows, expected_grads[0]), (d_weights, expected_grads[1])):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() <= bound * (float(np.abs(want).max()) or 1.0)
    empty = np.asarray(sizes) == 0
    assert not np.any(np.asarray(d_weights)[empty])  # an expert no pair landed on gets a zero gradient


def test_the_expert_layer_through_the_kernels_is_the_layer_through_ragged_dot(monkeypatch):
    """The whole layer (sort, three grouped products, combine) at widths the kernels tile,
    values, gradients and counters: the kernels' path against the CPU's."""
    spec = lfm2.LFM2Spec(
        vocab_size=32, hidden_size=128, intermediate_size=128, moe_intermediate_size=256, num_attention_heads=2,
        num_key_value_heads=1, layer_types=("conv",), num_dense_layers=0, num_experts=8, num_experts_per_tok=2,
        experts_held=(2, 4), max_seq_len=8)
    p = lfm2.init_params(spec, jax.random.PRNGKey(4))["layer_0"]["ffn"]
    p = {**p, **{name: 4.0 * p[name] for name in ("w1", "w3", "w2")}}
    u = jax.random.normal(jax.random.PRNGKey(5), (200, spec.hidden_size))

    def layer(p, u):
        y, ids, counters = lfm2.expert_layer(p, u, spec)
        return jnp.sum(jnp.sin(y)), (y, counters)

    with jax.default_matmul_precision("highest"):
        expected_grads, (expected, expected_counters) = jax.grad(layer, argnums=(0, 1), has_aux=True)(p, u)
    through_the_kernels(monkeypatch)
    with jax.default_matmul_precision("high"):
        grads, (y, counters) = jax.grad(layer, argnums=(0, 1), has_aux=True)(p, u)
    assert rel(y, expected) < 1e-4
    for got, want in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(expected_grads)):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-4 * max(float(np.abs(want).max()), 1e-6)
    assert counters["pairs_dropped"] == 0 and counters["pairs_held"] == expected_counters["pairs_held"] > 0
    assert 0 < float(counters["tile_fill"]) <= 1 and counters["tile_fill"] == expected_counters["tile_fill"]
    assert counters["grouped_product_passes"] == 3 and expected_counters["grouped_product_passes"] == 0


@pytest.mark.parametrize("precision, passes", [("default", 1), ("high", 3), ("highest", 6)])
def test_the_passes_follow_the_ambient_matmul_precision(precision, passes, monkeypatch):
    """`jax.default_matmul_precision` decides, as for every `@` of the model, when the product
    is traced; the layer returns the count among its counters (0 where `ragged_dot` takes it)."""
    took = []
    monkeypatch.setattr(lm_layers, "_gmm_tpu", lambda rows, w, sizes, passes: took.append(passes) or jax.lax.ragged_dot(rows, w, sizes))
    rows, weights = jnp.ones((128, 128)), jnp.ones((2, 128, 128))
    step = jax.jit(lambda rows: lfm2.grouped_matmul(rows, weights, jnp.array([100, 20], jnp.int32), jnp.arange(128) < 120))
    with jax.default_matmul_precision(precision):
        assert lfm2.kernel_passes(128, 128, 128) == 0 and step(rows).shape == (128, 128) and took == []  # the CPU's path
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        jax.clear_caches()
        assert lfm2.matmul_passes() == lfm2.kernel_passes(128, 128, 128) == passes and lfm2.kernel_passes(128, 100, 128) == 0
        step(rows), step(rows)
    assert took == [passes]


def test_an_unset_precision_is_one_pass_and_an_unknown_one_is_refused():
    with jax.default_matmul_precision(None):  # JAX's own default: XLA:TPU then takes one pass
        assert lfm2.matmul_passes() == 1
    with jax.default_matmul_precision("BF16_BF16_F32_X6"):
        with pytest.raises(ValueError, match="BF16_BF16_F32_X6"):
            lfm2.matmul_passes()


@pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
@pytest.mark.parametrize("product", ["forward", "input_gradient", "weight_gradient"])
def test_the_tilings_of_the_cells_products_divide_fit_and_sit_over_the_ridge(product, shape):
    """Each of the cell's products: the tiles divide the shape, the kernel's VMEM (operand
    tiles double-buffered, their bf16 halves, the accumulator and the output tile) stays
    under the budget the kernels are compiled with, and a grid step's FLOPs over its HBM
    bytes sit over the chip's ridge (197 TFLOP/s over 819 GB/s) at three passes."""
    m, k, n = CELL_SHAPES[shape]
    if product == "weight_gradient":
        tiling = gm.tgmm_tiling(m, k, n)
        vmem, intensity = gm.tgmm_vmem_bytes(tiling), gm.tgmm_flops_per_byte(tiling, 3)
    else:
        if product == "input_gradient":  # g [m, n] times weights^T: the contraction is n
            k, n = n, k
        tiling = gm.gmm_tiling(m, k, n)
        vmem, intensity = gm.gmm_vmem_bytes(tiling), gm.gmm_flops_per_byte(tiling, 3, whole_k=tiling[1] == k)
    assert all(size % tile == 0 and tile % 128 == 0 for size, tile in zip((m, k, n), tiling))
    assert vmem <= gm.VMEM_BUDGET_BYTES < gm.VMEM_LIMIT_BYTES
    assert intensity > gm.RIDGE_FLOPS_PER_BYTE == pytest.approx(197e12 / 819e9)


def test_a_tiling_falls_to_128_where_nothing_larger_divides():
    assert gm.gmm_tiling(384, 128, 384) == (128, 128, 384) and gm.tgmm_tiling(384, 128, 384) == (128, 128, 384)


@pytest.mark.parametrize("sizes, tm, visited", [([256, 256, 0, 512], 256, 4), ([100, 60, 0, 0], 256, 2), ([300, 300, 300, 124], 256, 7),
                                                ([0, 0, 0, 0], 256, 0), ([1, 1, 1, 1], 128, 4), ([1024], 128, 8)])
def test_tile_fill_counts_a_shared_tile_once_for_each_group(sizes, tm, visited):
    group_sizes = jnp.array(sizes, jnp.int32)
    assert int(gm.row_tiles_visited(group_sizes, tm)) == visited
    fill = float(lfm2.tile_fill(group_sizes, tm))
    assert fill == pytest.approx(sum(sizes) / (visited * tm) if visited else 0.0)
    # what megablox' own metadata schedules, which the kernels' grids are made from
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    _, scheduled = make_group_metadata(group_sizes=group_sizes, m=1024, tm=tm, start_group=jnp.int32(0),
                                       num_nonzero_groups=len(sizes), visit_empty_groups=False)
    assert int(scheduled) == visited


@pytest.mark.parametrize("precision", ["default", "high", "highest"])
def test_the_kernels_lower_for_the_tpu_under_every_ambient_precision(precision):
    """Mosaic refuses a dot that inherits `high`: every dot of the kernels pins its own."""
    m, k, n = 512, 256, 384
    rows, weights, g = jnp.ones((m, k)), jnp.ones((4, k, n)), jnp.ones((m, n))
    sizes = jnp.array([100, 0, 215, 60], jnp.int32)

    def products(rows, weights, g, sizes):
        passes = lfm2.matmul_passes()
        return (gm.gmm(rows, weights, sizes, (128, 256, 128), passes), gm.gmm(g, weights, sizes, (128, 128, 256), passes, transpose_rhs=True),
                gm.tgmm(rows, g, sizes, (128, 256, 128), passes))

    with jax.default_matmul_precision(precision):
        text = jax.jit(products).trace(rows, weights, g, sizes).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 3


_LOWERED_TEXT_SHA = """
import hashlib, jax, jax.numpy as jnp
from sheeprl_tpu.models import lfm2
spec = lfm2.LFM2Spec(vocab_size=32, hidden_size=16, intermediate_size=24, moe_intermediate_size=8, num_attention_heads=2,
                     num_key_value_heads=1, layer_types=("conv", "full_attention"), num_dense_layers=0, num_experts=4,
                     num_experts_per_tok=2, experts_held=(0, 2), max_seq_len=40)
params = jax.eval_shape(lambda: lfm2.init_params(spec, jax.random.PRNGKey(0)))
tokens = jax.ShapeDtypeStruct((4, 40), jnp.int32)  # 160 tokens: the grouped form, whose counters carry `tile_fill`
print(hashlib.sha256(jax.jit(lambda p, t: lfm2.forward(p, spec, t)).lower(params, tokens).as_text().encode()).hexdigest())
"""


def test_the_program_is_one_text_whatever_the_process_hashes_its_strings_to():
    """Nothing orders ops by a set of names: a program whose text changed with `PYTHONHASHSEED`
    misses the compile cache in every other run (the counters' means did, in PR 34's first chip call)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    texts = set()
    for seed in ("1", "2", "3", "4"):
        done = subprocess.run([sys.executable, "-c", _LOWERED_TEXT_SHA], capture_output=True, text=True, timeout=120,
                              env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": root, "PYTHONHASHSEED": seed})
        assert done.returncode == 0, done.stderr[-1500:]
        texts.add(done.stdout.strip().splitlines()[-1])
    assert len(texts) == 1
