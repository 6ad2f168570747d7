"""TPU-readiness AOT lowering tests (ROADMAP item 5 off-chip prep).

The per-platform lowering assertions this file used to hand-write (Pallas GRU
step / dispatch / gradients) now run as the fused-program registry sweep —
``sheeprl_tpu/ops/aot.py`` registers the programs,
``tests/test_analysis/test_aot_contracts.py`` (and ``python sheeprl.py lint
--aot``) lowers and asserts each contract. What stays HERE is
what the registry deliberately does not encode:

- the matmul-precision parametrization: Mosaic only lowers DEFAULT/HIGHEST
  dots, and the repo's DEFAULT CONFIG is "high" (bf16_3x) — an unpinned kernel
  dot inherited it and failed to lower for TPU at all (the bug this suite
  caught; the kernel now pins its own precision, and the graftlint
  ``pallas-dot-precision`` rule polices new kernels);
- what ``platform_dependent`` does under the installed JAX: a one-platform
  lowering carries ONLY that platform's branch (so the CPU-placed act program
  of a TPU process never meets Mosaic, and models.py needs no backend gate);
- the lower-only contract: the suite (and the sweep) must never backend-compile
  the TPU programs on a real chip's clock.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu import ops
from sheeprl_tpu.analysis.programs import FUSED_PROGRAMS, ensure_registry
from sheeprl_tpu.ops.aot import _gru_args

ensure_registry()


def _lower(fn, *args, platforms=("tpu",)):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=tuple(platforms))


def test_ops_lowering_contracts_are_registered():
    """The registry sweep covers every program this file used to lower by hand
    — pin the entries and the contracts so the sweep can never lose them."""
    for name in ("ops.gru_pallas_step", "ops.gru_platform_dispatch", "ops.gru_step_grad"):
        spec = FUSED_PROGRAMS[name]
        assert spec.contract.platforms == ("tpu",)
        assert "tpu_custom_call" in spec.contract.allow_custom_calls


@pytest.mark.parametrize("matmul_precision", ["default", "high", "highest"])
def test_pallas_gru_lowers_for_tpu_under_every_precision_config(matmul_precision):
    # parametrized over the global matmul-precision knob: Mosaic only lowers
    # DEFAULT/HIGHEST dots, and the repo's DEFAULT CONFIG is "high" (bf16_3x) —
    # an unpinned kernel dot inherited it and failed to lower for TPU at all
    # (the bug this suite caught; the kernel now pins its own precision)
    def step(inp, hx, w, b, scale, bias):
        return ops.fused_ln_gru_step(inp, hx, w, b, scale, bias, eps=1e-3)

    with jax.default_matmul_precision(matmul_precision):
        lowered = _lower(step, *_gru_args())
    assert "tpu_custom_call" in lowered.as_text(), "the Pallas GRU must lower to a Mosaic custom call"


def test_gru_dispatch_lowers_the_branch_of_the_lowering_platform():
    # the dispatch LayerNormGRUCell builds, lowered for one platform at a time:
    # the CPU program (tests, and the CPU-placed act program of a TPU process)
    # is the XLA reference with no Mosaic call; the TPU program has the kernel
    fn, args = FUSED_PROGRAMS["ops.gru_platform_dispatch"].builder()
    traced = fn.trace(*args)
    assert "tpu_custom_call" not in traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" in traced.lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("fused_step", [False, True])
def test_gru_cell_takes_the_kernel_only_where_its_builder_says_one_device(fused_step):
    # the module field is the whole gate: off by default, and nothing in the process
    # (a Fabric set up earlier, an environment variable) changes what a cell lowers
    from sheeprl_tpu.models.models import LayerNormGRUCell

    cell = LayerNormGRUCell(hidden_size=128, fused_step=fused_step)
    hx, x = jnp.ones((16, 128)), jnp.ones((16, 128))
    params = jax.eval_shape(cell.init, jax.random.PRNGKey(0), hx, x)
    hlo = _lower(lambda p, hx, x: cell.apply(p, hx, x), params, hx, x).as_text()
    assert ("tpu_custom_call" in hlo) is fused_step


def test_dreamer_v3_encoder_and_decoder_lower_for_tpu_to_convolutions_and_compile_nothing():
    # one convolution a stage and no fork on the platform: the chip runs what every
    # other backend runs, and lowering the real modules compiles nothing either
    from sheeprl_tpu.algos.dreamer_v3.agent import CNNDecoder, CNNEncoder
    from sheeprl_tpu.obs.compile_monitor import compile_snapshot, install_compile_monitor

    enc = CNNEncoder(keys=("rgb",), channels_multiplier=4, stages=3)
    dec = CNNDecoder(keys=("rgb",), output_channels=(3,), channels_multiplier=4, image_size=(32, 32), stages=3)
    obs, latent = {"rgb": jnp.ones((2, 3, 32, 32))}, jnp.ones((2, 16))
    programs = [(enc, obs, jax.eval_shape(enc.init, jax.random.PRNGKey(0), obs)),
                (dec, latent, jax.eval_shape(dec.init, jax.random.PRNGKey(0), latent))]
    install_compile_monitor()
    before = compile_snapshot()["count"]
    for module, x, params in programs:
        hlo = _lower(lambda p, x: module.apply(p, x), params, x).as_text()
        assert hlo.count("stablehlo.convolution") == 3
        assert "stablehlo.case" not in hlo and "custom_call" not in hlo
    assert compile_snapshot()["count"] == before


def test_tpu_lowering_compiles_nothing(monkeypatch):
    # the suite's contract: .lower() alone — no backend compile, no execution
    # (a compile would need a TPU client and would burn minutes on a real one)
    from sheeprl_tpu.obs.compile_monitor import compile_snapshot, install_compile_monitor

    install_compile_monitor()
    x = jnp.ones((4,))  # materialized BEFORE the snapshot (its fill compiles)
    before = compile_snapshot()["count"]
    _lower(lambda x: x * 2, x)
    assert compile_snapshot()["count"] == before


def test_the_latent_decode_kernel_lowers_for_tpu_in_a_scan_that_carries_its_cache():
    """The latent-cache kernel at the Moonlight cell's shapes (a ``[64, 512, 576]`` cache, 16
    heads, three passes under `high`) inside a jitted ``lax.scan`` over 512 decode steps that
    carries the cache: a Mosaic call whose cache operand is aliased to its third output, so
    that the loop writes its row in place."""
    import re

    from sheeprl_tpu.ops import latent_decode

    batch, positions, width, heads = 64, 512, 576, 16

    def decode(cache, rows, queries):
        def body(cache, x):
            t, row, query = x
            weighed, total, cache = latent_decode.latent_decode(cache, t, row, query, 3)
            return cache, weighed / total[..., None]

        return jax.lax.scan(body, cache, (jnp.arange(positions, dtype=jnp.int32), rows, queries))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in
              ((batch, positions, width), (positions, batch, width), (positions, batch, heads, width))]
    with jax.default_matmul_precision("high"):
        text = jax.jit(decode, donate_argnums=0).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(r"output_operand_alias<output_tuple_indices = \[2\], operand_index = 1,", text)


@pytest.mark.parametrize("decay", ["a_head", "a_key_channel"])
def test_the_delta_rule_decode_kernel_lowers_for_tpu_in_a_scan_that_carries_its_state(decay):
    """The delta-rule decode kernel at the Qwen3-Next and Kimi-Linear cells' shapes (a
    ``[64, 32, 128, 128]`` state, a layer's; the decay a head's scalar or a key channel's) inside
    a jitted ``lax.scan`` over 512 decode steps that carries the state: a Mosaic call whose state
    operand is aliased to its second output, so that the loop updates the state in place."""
    import re

    from sheeprl_tpu.ops import delta_rule_decode

    batch, heads, width, steps = 64, 32, 128, 512
    decays = (steps, batch, heads, width) if decay == "a_key_channel" else (steps, batch, heads)

    def decode(state, q, k, v, g, beta):
        def body(state, x):
            out, state = delta_rule_decode.delta_rule_decode(state, *x)
            return state, out

        return jax.lax.scan(body, state, (q, k, v, g, beta))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in
              [(batch, heads, width, width)] + [(steps, batch, heads, width)] * 3 + [decays, (steps, batch, heads)]]
    with jax.default_matmul_precision("high"):
        text = jax.jit(decode, donate_argnums=0).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(r"output_operand_alias<output_tuple_indices = \[1\], operand_index = 0,", text)


def test_the_kda_pairs_kernels_lower_for_tpu_under_checkpoint_and_grad_in_the_rules_scope(monkeypatch):
    """The chunked KDA rule at the Kimi-Linear cell's minibatch (16 sequences of 512 tokens, 32
    heads of 128, chunks of 64 in sub-chunks of 16) under `jax.checkpoint` and `jax.grad`, as the
    update runs it, with the backend the chip's (so `kimi_linear.pairs_kernel_taken` holds): one
    Mosaic call for the recomputed pairs and one for their backward, each named under the rule's
    `kda_rule` scope (what `perfbench/harness/kl_spans.py` files its device time by), and nothing
    compiled."""
    import re

    from sheeprl_tpu.models import kimi_linear
    from sheeprl_tpu.obs.compile_monitor import compile_snapshot, install_compile_monitor

    batch, tokens, heads, width = 16, 512, 32, 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kimi_linear.pairs_kernel_taken((64, width), 16)

    def loss(*x):
        def rule(*x):
            with jax.named_scope("kda_rule"):
                return kimi_linear.chunk_kda(*x, 64, 16)

        return jnp.sum(jax.checkpoint(rule)(*x))

    install_compile_monitor()
    before = compile_snapshot()["count"]
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in [(batch, tokens, heads, width)] * 4 + [(batch, tokens, heads)]]
    with jax.default_matmul_precision("high"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert compile_snapshot()["count"] == before
    names = [re.search(r'#%s = loc\("([^"]*)"' % ref, text).group(1)
             for ref in re.findall(r"tpu_custom_call.*loc\(#(loc\d+)\)\s*$", text, re.M)]
    assert len(names) == 2
    assert sum("/kda_rule/kda_pairs/" in n for n in names) == 1 and sum("/kda_rule/kda_pairs_bwd/" in n for n in names) == 1
