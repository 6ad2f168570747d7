"""The sequence-policy cell `lfm2_8b_a1b_ep4.ppo_64x256`: its whole run at tiny widths on
the CPU, a traced run that reports its span and counter metrics, a fault that `correct`
catches, the configuration's file against the composed configuration and the published
widths, and the functions that count its FLOPs and read its capture."""

import json
import os

import pytest

from perfbench.harness import bench, lm_faults, lm_flops, lm_spans
from perfbench.harness import program_spans as ps

CELL = "lfm2_8b_a1b_ep4.ppo_64x256"
TINY_LM = [
    "algo.lm.vocab_size=64", "algo.lm.hidden_size=32", "algo.lm.intermediate_size=48",
    "algo.lm.moe_intermediate_size=24", "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=2",
    "algo.lm.num_experts=8", "algo.lm.num_experts_per_tok=2", "algo.lm.experts_held=[2,4]",
    "env.num_envs=8", "algo.rollout_steps=24", "algo.per_rank_batch_size=4",
    "env.tokens.prompt_min=4", "env.tokens.prompt_max=8", "algo.optimizer.lr=1e-3",
]
ARITHMETIC = ("rollout_logprob_gap", "rollout_value_gap", "policy_loss_gap", "value_loss_gap", "entropy_loss_gap",
              "grad_gap", "update_gap")


@pytest.fixture
def config(repo_root):
    with open(os.path.join(repo_root, "perfbench", "configs", "lfm2_8b_a1b_ep4.json")) as fh:
        return json.load(fh)


@pytest.mark.timeout(600)
def test_cell_runs_and_agrees_with_the_reference_at_tiny_widths():
    result = bench.run_cell(CELL, 2**31 + 77, 0.5, False, platform="cpu", extra_overrides=TINY_LM)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 6
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"} and result["metrics"]["env_steps_per_s"]["value"] > 0
    compared = result["compared"]
    # float32 on both sides here: decoding through the caches agrees with the full forward,
    # and the update with `jax.grad` of the reference, to rounding; no choice of experts differs
    for name in ARITHMETIC:
        assert compared[name]["value"] < 1e-4, name
    assert compared["route_mismatch_share"]["value"] == 0.0 and compared["route_flip_margin"]["value"] == 0.0
    # the env's rule recomputed from the prompts and the recorded actions: exact
    assert compared["env_mismatch_count"] == {"value": 0.0, "limit": 0}


@pytest.mark.timeout(600)
def test_traced_run_reports_the_span_and_counter_metrics_it_can_read():
    result = bench.run_cell(CELL, 5, 0.5, True, platform="cpu", extra_overrides=TINY_LM)
    assert result["correct"] is True
    metrics = result["metrics"]
    # the host's spans and the program's counters are read on any platform; there is no TPU
    # capture here, and a reader that finds nothing to read returns nothing
    assert {"compile_s", "compiles_in_window", "host_other_share", "train_call_ms", "moe_max_expert_load"} <= set(metrics)
    assert metrics["train_call_ms"]["value"] > 0 and metrics["moe_max_expert_load"]["value"] >= 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    for absent in ("train_step_mfu", "lm_rollout_device_ms", "moe_experts_roofline_share", "replay_wait_share",
                   "host_env_act_share", "env_steps_per_s"):
        assert absent not in metrics


@pytest.mark.timeout(600)
@pytest.mark.parametrize("kind", ["top3", "no_expert_bias", "prompt_unmasked"])
def test_a_fault_comes_out_as_not_correct(kind, config):
    with lm_faults.planted(kind):
        result = bench.run_cell(CELL, 11, 0.2, False, platform="cpu", extra_overrides=TINY_LM)
    assert result["correct"] is False and result["failed"] >= 1
    over = {k for k, v in result["compared"].items() if v["limit"] is not None and v["value"] > v["limit"]}
    caught_by = {"top3": {"rollout_logprob_gap", "grad_gap", "update_gap"}, "no_expert_bias": {"route_mismatch_share"},
                 "prompt_unmasked": {"env_mismatch_count"}}[kind]
    assert over & caught_by
    if kind == "prompt_unmasked":  # the reference is fed the program's own rollout: nothing else sees a wrong mask
        assert over == caught_by


def test_the_configuration_file_says_what_is_run(repo_root, monkeypatch):
    from sheeprl_tpu.config import compose

    monkeypatch.setenv("SHEEPRL_SEARCH_PATH", os.path.join(repo_root, "perfbench", "sheeprl_configs"))
    data = bench.load_cell(CELL, repo_root)
    cfg = compose([f"exp={data['config']['exp']}", *data["config"]["overrides"], *data["traffic"]["overrides"]])
    adapter = bench.load_adapter(data["config"], repo_root)
    model = data["config"]["model"]
    assert adapter.spec(cfg) == model
    assert adapter.cycle(cfg) == (1, 2, 16384)
    assert cfg.metric.log_level == 0 and cfg.checkpoint.every == 0 and cfg.algo.run_test is False
    assert data["cell"]["chips"] == 1 and data["traffic"]["warmup_cycles"] == 3 and data["traffic"]["trace_cycles"] == 2


def test_the_configuration_keeps_the_published_widths(config):
    """LFM2-8B-A1B's config.json: no width differs; depth, experts held and vocabulary are
    the cut, each under `reduced` with the published count beside it."""
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
                 "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
                 "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
                 "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True}
    for key, value in published.items():
        assert config[key] == value and key not in config["reduced"], key
    cut = {"num_hidden_layers": (5, 24), "num_dense_layers": (1, 2), "num_experts": (8, 32), "vocab_size": (16384, 65536)}
    for key, (held, whole) in cut.items():
        assert config[key] == held and config["published"][key] == whole and key in config["reduced"], key
    assert config["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"] and "layer_types" in config["reduced"]
    assert config["published"]["chips_sharing_a_layer"] == 4
    model = config["model"]
    assert model["num_experts_routed"] == 32 and model["experts_held"] == [0, 8] and model["head_dim"] == 64
    assert {"embedding and head", "value head", "expert bias", "weight-sum epsilon"} <= set(config["assumed"])


def test_flops_and_parameters_from_the_model_block(config):
    from sheeprl_tpu.models import lfm2

    m = config["model"]
    spec = lfm2.LFM2Spec(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"], intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"], num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"], layer_types=tuple(m["layer_types"]),
        num_dense_layers=m["num_dense_layers"], num_experts=m["num_experts_routed"],
        num_experts_per_tok=m["num_experts_per_tok"], experts_held=tuple(m["experts_held"]))
    assert lfm2.parameter_count(spec) == 541_376_768  # 8.7 GB with Adam's moments and the gradients
    tokens = m["rollout_steps"] * m["num_envs"]
    assert lm_flops.expected_pairs(m, tokens) == tokens * 4  # 4 layers x 4 choices x 8/32 held: a pair a token a layer
    expected = lm_flops.iteration_flops(m)
    assert 2.0e13 < expected < 3.0e13  # 26 TFLOP an iteration, the update three quarters of it
    counted = lm_flops.iteration_flops(m, {"rollout_pairs_held": 300.0, "update_pairs_held": 40000.0})
    assert counted > expected  # more pairs on the held experts, more FLOPs: counted, not padded
    flops, nbytes = lm_flops.update_experts_flops_bytes(m)
    assert flops / 197e12 > nbytes / 819e9  # 1,024 tokens an expert a step: compute-bound


def test_the_scope_reader_on_a_made_up_capture():
    capture = ps.ProgramCapture()
    capture.modules["/device:TPU:0"] = [("jit_anakin_step", 0.0, 10.0), ("jit_anakin_step", 10.0, 20.0),
                                        ("jit_rollout_phase", 20.0, 25.0)]
    stacks = {
        "a": "jit(anakin_step)/jit(main)/rollout/while/body/experts/pallas_call",
        "b": "jit(anakin_step)/jit(main)/update/while/body/transpose(jvp(experts))/pallas_call",
        "c": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/router/sort",
        "d": "jit(anakin_step)/jit(main)/update/optimizer/add",
        "e": "jit(anakin_step)/jit(main)/copy",
        "f": "jit(rollout_phase)/jit(main)/rollout/while/body/experts/pallas_call",
    }
    capture.scopes = dict(stacks)
    ops = []
    for start in (0.0, 10.0):
        ops += [("a", start + 1, start + 3), ("b", start + 3, start + 6), ("c", start + 6, start + 7),
                ("d", start + 7, start + 8), ("e", start + 8, start + 8.5)]
    ops.append(("f", 21.0, 24.0))  # another program's ops are not the fused program's
    capture.ops["/device:TPU:0"] = ops
    assert lm_spans.place_of(stacks["b"]) == ("update", "experts") and lm_spans.place_of(stacks["e"]) == (None, None)
    assert lm_spans.part_ms(capture, phase="rollout") == pytest.approx(2000.0)
    assert lm_spans.part_ms(capture, phase="update") == pytest.approx(5000.0)
    assert lm_spans.part_ms(capture, ("experts",)) == pytest.approx(5000.0)
    assert lm_spans.part_ms(capture, ("experts",), phase="update") == pytest.approx(3000.0)
    assert lm_spans.part_ms(capture, ("router",)) == pytest.approx(1000.0)
    assert lm_spans.part_ms(capture, ("optimizer",)) == pytest.approx(1000.0)  # `lm_optimizer_device_ms`
    # `lm_other_device_ms` takes what no part's metric reads (here the op under no scope), so the parts add up
    assert lm_spans.part_ms(capture, ("embed", "gae", "ppo_loss", None)) == pytest.approx(500.0)
    named = ("router", "experts", "short_conv", "attention", "lm_head", "value_head", "dense_ffn", "optimizer")
    assert lm_spans.part_ms(capture, named) + 500.0 == pytest.approx(lm_spans.part_ms(capture))
    assert lm_spans.unscoped_share(capture) == pytest.approx(100 * 0.5 / 7.5)
    assert lm_spans.program_parts(ps.ProgramCapture()) is None  # a capture without the program: nothing, not 0
