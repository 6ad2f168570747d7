"""The named parts of the Dreamer-V3 train program (`jax.named_scope` in
`make_train_phase`): on the tiny AOT program, every scope reaches the lowered text on
forward and backward ops, every matmul and convolution sits under one, and the scopes
changed nothing but names. And the rule that goes with them: turning tracing on or off
changes no jitted program and no compile-cache key, and writes no cache entry."""

from __future__ import annotations

import contextlib
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _aot_train_step

SCOPES = ("encoder", "rssm", "decoder", "heads", "imagine", "actor", "critic", "optimizer")


def scope_of(stack: str):
    """The innermost scope on a name stack: `transpose(jvp(rssm))` counts to `rssm`."""
    for part in reversed(stack.split("/")):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part in SCOPES:
            return part
    return None


@pytest.fixture(scope="module")
def lowered():
    fn, args = _aot_train_step()
    return fn.lower(*args)


def _name_stacks(text: str):
    """(ops, calls) of the lowered module: ops as (function, op, name stack), calls as
    (caller, callee, name stack). A sub-function lowered once (a scan body's
    `closed_call`, an inner jit) names its ops relative to itself; XLA prefixes the
    call site's stack when it inlines, so an op counts to its own scope or else to its
    callers'."""
    table = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, flags=re.M))
    ops, calls, function = [], [], None
    for line in text.splitlines():
        opened = re.match(r"\s*func\.func (?:public |private )?@([\w.]+)\(", line)
        if opened:
            function = opened.group(1)
        ref = re.search(r"loc\((#loc\d+)\)$", line)
        stack = table.get(ref.group(1), "") if ref else ""
        called = re.search(r"(?:func\.)?call @([\w.]+)\(", line)
        if called:
            calls.append((function, called.group(1), stack))
        op = re.search(r"stablehlo\.(dot_general|convolution)\b", line)
        if op:
            ops.append((function, op.group(1), stack))
    return ops, calls


def test_every_scope_names_forward_and_backward_ops(lowered):
    text = lowered.as_text(debug_info=True)
    stacks = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in SCOPES:
        forward = [s for s in stacks if f"/jvp({scope})/" in s or f"/{scope}/" in s]
        assert forward, f"no forward op under `{scope}`"
        # not differentiated: the optimizers' updates, and the imagination of a discrete
        # actor (REINFORCE: latents and actions reach the losses through stop_gradient)
        if scope not in ("optimizer", "imagine"):
            assert [s for s in stacks if f"/transpose(jvp({scope}))/" in s], f"no backward op under `{scope}`"
    assert all(s.startswith("jit(train_step)/") for s in stacks if scope_of(s))


def test_every_matmul_and_convolution_sits_under_a_scope(lowered):
    ops, calls = _name_stacks(lowered.as_text(debug_info=True))
    assert len(ops) > 50 and {op for _, op, _ in ops} == {"dot_general", "convolution"}

    def scopes_of_function(function, seen=()):
        sites = [(caller, stack) for caller, callee, stack in calls if callee == function]
        found = set()
        for caller, stack in sites:
            own = scope_of(stack)
            found |= {own} if own else (scopes_of_function(caller, (*seen, function)) if caller not in seen else {None})
        return found or {None}

    for function, op, stack in ops:
        where = {scope_of(stack)} if scope_of(stack) else scopes_of_function(function)
        assert None not in where, f"a {op} in @{function} under no scope: {stack!r}"


def test_the_scopes_changed_nothing_but_names(lowered, monkeypatch):
    with_scopes = lowered.compiler_ir().operation.get_asm(enable_debug_info=False)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    fn, args = _aot_train_step()
    unscoped = fn.lower(*args)
    assert not any(f"({s})" in unscoped.as_text(debug_info=True) for s in ("rssm", "imagine", "heads"))
    assert unscoped.compiler_ir().operation.get_asm(enable_debug_info=False) == with_scopes
    assert lowered.args_info == unscoped.args_info  # donation included


# ---------------------------------------------------------------------------------
# the rule: tracing on or off is the same program under the same cache key
# ---------------------------------------------------------------------------------
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_TWO_RUNS_ONE_CACHE = """
import contextlib, json, os, sys
import jax
cache, capture = sys.argv[1], sys.argv[2]
jax.config.update("jax_compilation_cache_dir", cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _aot_train_step
from sheeprl_tpu.utils.timer import timer

def run(disabled, profiled):
    jax.clear_caches()  # nothing is left in memory: every program asks the directory
    timer.disabled = disabled
    fn, args = _aot_train_step()
    session = contextlib.nullcontext()
    if profiled:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the tracer of Python calls slows the lowering tenfold
        session = jax.profiler.trace(capture, profiler_options=options)
    with session:
        with timer("Time/train_time"):
            with timer("train_dispatch"):
                out = fn(*args)
            jax.block_until_ready(out)
    return sorted(name for name in os.listdir(cache) if name.endswith("-cache"))

untraced = run(disabled=True, profiled=False)
traced = run(disabled=False, profiled=True)
spans = [record[0] for record in timer.ring]
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)  # what PR 26 did
keyed_by_names = run(disabled=False, profiled=False)
print(json.dumps({"untraced": untraced, "traced": traced, "keyed_by_names": keyed_by_names, "spans": spans}))
"""


def test_a_traced_run_after_an_untraced_one_writes_no_second_cache_entry(tmp_path):
    """The tiny train step once with `timer.disabled` and once with the spans on inside a
    profiler session, every program persisted: the second run finds all of them in the
    directory. The third run is the control that the count can tell: names in the key,
    as PR 26 had them for traced runs, write every program a second time."""
    done = subprocess.run(
        [sys.executable, "-c", _TWO_RUNS_ONE_CACHE, str(tmp_path / "cache"), str(tmp_path / "capture")],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=280,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    read = json.loads(done.stdout.strip().splitlines()[-1])
    assert any(name.startswith("jit_train_step-") for name in read["untraced"])
    assert read["traced"] == read["untraced"]
    assert read["spans"] == ["train_dispatch", "Time/train_time"]  # and the traced run did record
    assert list(tmp_path.glob("capture/**/*.xplane.pb"))  # inside a session that did capture
    assert len(read["keyed_by_names"]) > len(read["untraced"])


def _lowered_expert_layer(counting: bool, trunk: str = "lfm2") -> str:
    """The grouped expert layer's value and gradient, lowered for the TPU (its kernels
    and all) with the timer, and so the counters, on or off: LFM2's on buffers of
    ``tokens x k`` rows, or the `qwen3_next` trunk's bounded dispatch (rounds and all)."""
    import jax.numpy as jnp

    from sheeprl_tpu.models import deepseek_v3, lfm2, qwen3_next
    from sheeprl_tpu.utils.timer import timer

    if trunk == "deepseek_v3":  # a sigmoid router with a scale, an ungated shared expert, the bounded dispatch
        model = deepseek_v3
        spec = deepseek_v3.DeepseekV3Spec(
            vocab_size=32, hidden_size=128, intermediate_size=128, moe_intermediate_size=128, num_attention_heads=2,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, num_hidden_layers=1, first_k_dense_replace=0,
            num_experts=16, num_experts_per_tok=2, experts_held=(0, 4), n_shared_experts=2, routed_scaling_factor=2.446, max_seq_len=8)
    elif trunk == "lfm2":
        model = lfm2
        spec = lfm2.LFM2Spec(
            vocab_size=32, hidden_size=128, intermediate_size=128, moe_intermediate_size=128, num_attention_heads=2,
            num_key_value_heads=1, layer_types=("conv",), num_dense_layers=0, num_experts=4, num_experts_per_tok=2,
            experts_held=(0, 2), max_seq_len=8)
    else:
        model = qwen3_next
        spec = qwen3_next.Qwen3NextSpec(
            vocab_size=32, hidden_size=128, moe_intermediate_size=128, shared_expert_intermediate_size=128,
            num_attention_heads=2, num_key_value_heads=1, head_dim=16, linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=8, linear_value_head_dim=8, layer_types=("linear_attention",), num_experts=16,
            num_experts_per_tok=2, experts_held=(0, 4), max_seq_len=8)
    p = jax.eval_shape(lambda: model.init_params(spec, jax.random.PRNGKey(0))["layer_0"]["ffn"])
    u = jax.ShapeDtypeStruct((192, spec.hidden_size), jnp.float32)

    def layer(p, u):
        y, _, counters = model.expert_layer(p, u, spec)
        return jnp.sum(y), counters

    was, timer.disabled = timer.disabled, not counting
    try:
        lowered = jax.jit(jax.grad(layer, has_aux=True)).trace(p, u).lower(lowering_platforms=("tpu",))
    finally:
        timer.disabled = was
    return lowered.as_text()


@pytest.mark.parametrize("exp, telemetry", [("dreamer_v3", "true"), ("dreamer_v3", "false"),
                                            ("ppo_anakin_lfm2", "true"), ("ppo_anakin_lfm2", "false"),
                                            ("ppo_anakin_qwen3_next", "true"), ("ppo_anakin_qwen3_next", "false"),
                                            ("ppo_anakin_deepseek_v3", "true"), ("ppo_anakin_deepseek_v3", "false")])
def test_composing_with_telemetry_leaves_the_cache_key_alone(exp, telemetry, monkeypatch):
    from sheeprl_tpu import cli
    from sheeprl_tpu.config import compose

    knobs = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_default_matmul_precision", "jax_compilation_cache_include_metadata_in_key")
    before = {knob: getattr(jax.config, knob) for knob in knobs}
    assert before["jax_compilation_cache_include_metadata_in_key"] is False  # JAX's default
    try:
        cfg = compose([f"exp={exp}", f"metric.telemetry.enabled={telemetry}",
                       "metric.profiler.mode=" + ("window" if telemetry == "true" else "off")])
        cli._setup_xla_env(cfg)
        assert jax.config.jax_compilation_cache_include_metadata_in_key is False
        assert jax.config.jax_compilation_cache_dir == before["jax_compilation_cache_dir"]
        if exp == "ppo_anakin_lfm2":
            # the sequence program's two new counters, `moe/update_tile_fill` and
            # `moe/update_grouped_product_passes`, are scalars the program returns either way
            # (the loop hands them to the timer only while it is on): one program, one cache key
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            text = _lowered_expert_layer(counting=telemetry == "true")
            assert "tpu_custom_call" in text and text == _lowered_expert_layer(counting=telemetry != "true")
        if exp == "ppo_anakin_qwen3_next":  # and so are the bounded dispatch's, `moe/update_dispatch_fill` among them
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            text = _lowered_expert_layer(telemetry == "true", "qwen3_next")
            assert "tpu_custom_call" in text and "while" in text  # the kernels, inside the rounds' loops
            assert text == _lowered_expert_layer(telemetry != "true", "qwen3_next")
        if exp == "ppo_anakin_deepseek_v3":  # the third trunk's layer: the same counters, returned either way
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            text = _lowered_expert_layer(telemetry == "true", "deepseek_v3")
            assert "tpu_custom_call" in text and "while" in text
            assert text == _lowered_expert_layer(telemetry != "true", "deepseek_v3")
    finally:
        for knob, value in before.items():
            jax.config.update(knob, value)


def test_no_source_file_names_the_switch():
    """`grep -rn include_metadata_in_key sheeprl_tpu perfbench` finds nothing."""
    found = []
    for top in ("sheeprl_tpu", "perfbench"):
        for folder, _dirs, files in os.walk(os.path.join(ROOT, top)):
            for name in files:
                if name.endswith((".py", ".yaml", ".json", ".md")):
                    with open(os.path.join(folder, name), errors="replace") as fh:
                        if "include_metadata_in_key" in fh.read():
                            found.append(os.path.join(folder, name))
    assert found == []
