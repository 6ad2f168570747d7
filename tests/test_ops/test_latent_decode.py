"""The latent-cache decode kernel (`sheeprl_tpu/ops/latent_decode.py`) in Pallas' interpreter
against the XLA form it replaces on the chip (`models/deepseek_v3.py::_attend_written`): the
softmax's weighed latents and sum to rounding at each pass count, the written row bit for bit
as `dynamic_update_slice` writes it, and every other row of the cache, NaN past the position
included, bit for bit as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import deepseek_v3
from sheeprl_tpu.ops import latent_decode as ld

S, W, HEADS = 2 * ld.CHUNKS[0], 20, 4
CHUNK = ld.CHUNKS[0]
# the largest gap to the XLA form at `highest`, over the largest weighed latent: one bf16 pass
# rounds the operands to 8 bits, three leave the products' error at float32's scale
TOLERANCE = {1: 5e-2, 3: 5e-4, 6: 1e-5}


@pytest.mark.parametrize("passes", [1, 3, 6])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("t", [0, CHUNK - 1, CHUNK, CHUNK + 45, S - 1],
                         ids=["first", "chunk_end", "chunk_start", "mid_block", "last"])
def test_the_kernel_is_the_xla_form_and_writes_only_its_row(t, batch, passes):
    keys = jax.random.split(jax.random.PRNGKey(t + 7 * batch), 3)
    cache = jax.random.normal(keys[0], (batch, S, W))
    cache = jnp.where(jnp.arange(S)[None, :, None] > t, jnp.nan, cache)  # rows never written hold anything
    row, query = jax.random.normal(keys[1], (batch, W)), jax.random.normal(keys[2], (batch, HEADS, W))
    with jax.default_matmul_precision("highest"):
        weighed, total, written = deepseek_v3._attend_written(cache, jnp.int32(t), row, query)
    got_weighed, got_total, got_cache = ld.latent_decode(cache, jnp.int32(t), row, query, passes, interpret=True)

    assert np.all(np.isfinite(got_weighed)) and np.all(np.isfinite(got_total))
    mean, got_mean = weighed / total[..., None], got_weighed / got_total[..., None]
    scale = float(jnp.max(jnp.abs(mean)))
    assert float(jnp.max(jnp.abs(got_mean - mean))) <= TOLERANCE[passes] * scale
    np.testing.assert_allclose(got_total, total, rtol=TOLERANCE[passes])
    bits, got_bits = np.asarray(written).view(np.uint32), np.asarray(got_cache).view(np.uint32)
    assert np.array_equal(got_bits[:, t], bits[:, t])  # the row, as dynamic_update_slice writes it
    before = np.asarray(cache).view(np.uint32)
    others = np.arange(S) != t
    assert np.array_equal(got_bits[:, others], before[:, others])  # every other row as it was
    assert np.all(np.isnan(np.asarray(got_cache)[:, t + 1:]))


def test_the_kernel_refuses_a_cache_of_no_whole_chunks():
    assert ld.supports((4, 3 * ld.CHUNKS[-1], W)) and not ld.supports((4, ld.CHUNKS[-1] + 8, W))
    with pytest.raises(ValueError, match="chunks"):
        ld.latent_decode(jnp.zeros((1, ld.CHUNKS[-1] + 8, W)), jnp.int32(0), jnp.zeros((1, W)), jnp.zeros((1, HEADS, W)), 3,
                         interpret=True)
