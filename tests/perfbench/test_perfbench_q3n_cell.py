"""The sequence-policy cell `qwen3_next_80b_a3b_ep16.ppo_64x512`: its whole run at tiny
widths on the CPU, a traced run that reports its counter metrics, faults that `correct`
catches, the configuration's file against the composed configuration and the published
widths, and the functions that count its FLOPs and read its capture."""

import json
import os

import pytest

from perfbench.harness import bench, lm_spans, q3n_faults, q3n_flops, q3n_spans
from perfbench.harness import program_spans as ps

CELL = "qwen3_next_80b_a3b_ep16.ppo_64x512"
TINY_LM = [
    "algo.lm.vocab_size=64", "algo.lm.hidden_size=32", "algo.lm.moe_intermediate_size=24",
    "algo.lm.shared_expert_intermediate_size=16", "algo.lm.num_attention_heads=4", "algo.lm.num_key_value_heads=2",
    "algo.lm.head_dim=16", "algo.lm.linear_num_key_heads=2", "algo.lm.linear_num_value_heads=4",
    "algo.lm.linear_key_head_dim=8", "algo.lm.linear_value_head_dim=8", "algo.lm.chunk_size=8",
    "algo.lm.layer_types=[linear_attention,full_attention]", "algo.lm.num_experts=16",
    "algo.lm.num_experts_per_tok=4", "algo.lm.experts_held=[4,4]",
    "env.num_envs=8", "algo.rollout_steps=36", "algo.per_rank_batch_size=4",
    "env.tokens.prompt_min=4", "env.tokens.prompt_max=8", "algo.optimizer.lr=1e-3",
]
ARITHMETIC = ("rollout_logprob_gap", "rollout_value_gap", "policy_loss_gap", "value_loss_gap", "entropy_loss_gap",
              "grad_gap", "update_gap")


@pytest.fixture
def config(repo_root):
    with open(os.path.join(repo_root, "perfbench", "configs", "qwen3_next_80b_a3b_ep16.json")) as fh:
        return json.load(fh)


@pytest.mark.timeout(600)
def test_cell_runs_and_agrees_with_the_reference_at_tiny_widths():
    result = bench.run_cell(CELL, 2**31 + 77, 0.5, False, platform="cpu", extra_overrides=TINY_LM)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"} and result["metrics"]["env_steps_per_s"]["value"] > 0
    compared = result["compared"]
    # float32 on both sides here: decoding through the three kinds of state agrees with the
    # recurrent reference's full forward, and the chunked update with `jax.grad` of it, to
    # rounding; no choice of experts differs
    for name in ARITHMETIC:
        assert compared[name]["value"] < 2e-4, name
    assert compared["route_mismatch_share"]["value"] == 0.0 and compared["route_flip_margin"]["value"] == 0.0
    assert compared["env_mismatch_count"] == {"value": 0.0, "limit": 0}


@pytest.mark.timeout(600)
def test_traced_run_reports_the_counter_metrics_it_can_read():
    result = bench.run_cell(CELL, 5, 0.5, True, platform="cpu", extra_overrides=TINY_LM)
    assert result["correct"] is True
    metrics = result["metrics"]
    # the host's spans and the program's counters are read on any platform; there is no TPU
    # capture here, and a reader that finds nothing to read returns nothing
    assert {"compile_s", "compiles_in_window", "host_other_share", "train_call_ms", "q3n_max_expert_load",
            "q3n_dispatch_fill"} <= set(metrics)
    assert metrics["q3n_max_expert_load"]["value"] >= 1.0 and 0.0 < metrics["q3n_dispatch_fill"]["value"] <= 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    for absent in ("train_step_mfu", "q3n_rollout_device_ms", "q3n_delta_rule_roofline_share", "q3n_experts_roofline_share",
                   "lm_rollout_device_ms", "moe_max_expert_load", "env_steps_per_s"):
        assert absent not in metrics


@pytest.mark.timeout(600)
@pytest.mark.parametrize("kind", ["no_decay", "rollout_state_zeroed", "chunk_state_dropped"])
def test_a_fault_comes_out_as_not_correct(kind, config):
    limits = {name: 1e-3 for name in ARITHMETIC}  # the file's are the chip's: at tiny widths in float32, rounding is far below
    with q3n_faults.planted(kind):
        result = bench.run_cell(CELL, 11, 0.2, False, platform="cpu", extra_overrides=TINY_LM)
    assert result["correct"] is False and result["failed"] >= 1  # under the file's own limits, the chip's, too
    over = {k for k, v in result["compared"].items() if k in limits and v["value"] > limits[k]}
    caught_by = {"no_decay": {"rollout_logprob_gap", "grad_gap"}, "rollout_state_zeroed": {"rollout_logprob_gap", "rollout_value_gap"},
                 "chunk_state_dropped": {"grad_gap", "update_gap", "policy_loss_gap", "value_loss_gap"}}[kind]
    assert over & caught_by, result["compared"]
    if kind == "rollout_state_zeroed":  # the update's chunked form is sound: only the decode's side sees it
        assert not over & {"policy_loss_gap", "value_loss_gap", "grad_gap"}
    if kind == "chunk_state_dropped":  # and the other way round
        assert not over & {"rollout_logprob_gap", "rollout_value_gap"}


def test_every_fault_is_planted_and_taken_out_again():
    from sheeprl_tpu.models import qwen3_next

    names = ("route", "expert_layer", "_linear_inputs", "chunk_delta_rule", "delta_rule_step", "rope", "_qkv_gate")
    sound = {name: getattr(qwen3_next, name) for name in names}
    for kind in q3n_faults.KINDS:
        with q3n_faults.planted(kind):
            changed = [name for name in names if getattr(qwen3_next, name) is not sound[name]]
            assert len(changed) == (0 if kind in q3n_faults.OF_THE_LOOP else 1), kind
        assert all(getattr(qwen3_next, name) is sound[name] for name in names)
    with pytest.raises(ValueError, match="unknown fault"):
        with q3n_faults.planted("top3"):
            pass


def test_the_configuration_file_says_what_is_run(repo_root, monkeypatch):
    from sheeprl_tpu.config import compose

    monkeypatch.setenv("SHEEPRL_SEARCH_PATH", os.path.join(repo_root, "perfbench", "sheeprl_configs"))
    data = bench.load_cell(CELL, repo_root)
    cfg = compose([f"exp={data['config']['exp']}", *data["config"]["overrides"], *data["traffic"]["overrides"]])
    adapter = bench.load_adapter(data["config"], repo_root)
    model = data["config"]["model"]
    assert adapter.spec(cfg) == model
    assert adapter.cycle(cfg) == (1, 64 // model["minibatch_sequences"], 32768)
    assert cfg.metric.log_level == 0 and cfg.checkpoint.every == 0 and cfg.algo.run_test is False
    assert data["cell"]["chips"] == 1 and data["traffic"]["warmup_cycles"] == 3 and data["traffic"]["trace_cycles"] == 2
    # a limit for each compared name and for no other
    assert set(data["config"]["limits"]) == set(adapter.compared)


def test_the_cell_lists_only_metrics_a_traced_window_can_read(repo_root):
    """A metric with no `workloads` list has to be in every traced line. `cycle_p90_ms` reads
    nothing under ten cycles, and a traced window of this cell holds five (one fused call is
    5.6 s of the 51, and the capture's stop stalls one cycle), so that metric lists the cells
    that can report it and this cell is not among them."""
    manifest = bench.load_cell(CELL, repo_root)["manifest"]
    listed = [entry["name"] for entry in bench.metrics_for(manifest, CELL, "per_layer")]
    assert "cycle_p90_ms" not in listed
    assert [name for name in listed if not name.startswith("q3n_")] == [
        "compile_s", "compiles_in_window", "host_other_share", "train_call_ms", "train_device_ms", "train_step_mfu",
        "mxu_op_share", "device_idle_share", "hbm_peak_gb"]
    tail = next(entry for entry in manifest["per_layer"] if entry["name"] == "cycle_p90_ms")
    assert tail["workloads"] == [cell["name"] for cell in manifest["workloads"] if cell["name"] != CELL]


def test_the_configuration_keeps_the_published_widths(config):
    """Qwen3-Next-80B-A3B-Instruct's config.json: no width differs; depth, experts held and
    vocabulary are the cut, each under `reduced` with the published count beside it."""
    published = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
                 "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
                 "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_value_head_dim": 128,
                 "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
                 "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts_per_tok": 10,
                 "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 10000000, "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
                 "use_sliding_window": False}
    for key, value in published.items():
        assert config[key] == value and key not in config["reduced"], key
    cut = {"num_hidden_layers": (4, 48), "num_experts": (32, 512), "vocab_size": (18992, 151936)}
    for key, (held, whole) in cut.items():
        assert config[key] == held and config["published"][key] == whole and key in config["reduced"], key
    assert config["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]  # one whole period, 3:1
    assert config["published"]["chips_sharing_a_layer"] == 16 and config["vocab_size"] * 8 == 151936
    model = config["model"]
    assert model["num_experts_routed"] == 512 and model["experts_held"] == [0, 32] and model["rotary_dim"] == 64
    assert {"projection layout", "A_log and dt_bias", "value head", "chunk", "multi-token prediction",
            "embedding and head", "dispatch bound"} <= set(config["assumed"])


def test_flops_bytes_and_parameters_from_the_model_block(config):
    from sheeprl_tpu.models import lm_layers, qwen3_next

    m = config["model"]
    spec = qwen3_next.Qwen3NextSpec(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"], moe_intermediate_size=m["moe_intermediate_size"],
        shared_expert_intermediate_size=m["shared_expert_intermediate_size"], num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"], head_dim=m["head_dim"], linear_num_key_heads=m["linear_num_key_heads"],
        linear_num_value_heads=m["linear_num_value_heads"], linear_key_head_dim=m["linear_key_head_dim"],
        linear_value_head_dim=m["linear_value_head_dim"], layer_types=tuple(m["layer_types"]),
        num_experts=m["num_experts_routed"], num_experts_per_tok=m["num_experts_per_tok"], experts_held=tuple(m["experts_held"]))
    assert qwen3_next.parameter_count(spec) == config["parameters_held"] == 625_669_184  # 10.0 GB at 16 B
    assert spec.rotary_dim == m["rotary_dim"] and spec.linear_state_bytes_per_sequence == 6_586_368
    assert lm_layers.dispatch_rows(spec, m["minibatch_sequences"] * m["rollout_steps"]) == 10240
    tokens = m["rollout_steps"] * m["num_envs"]
    # a linear-attention layer outside the routed experts: 33.7M of projections, 1.57M of delta rule
    assert q3n_flops.delta_rule_macs(m) == 3 * 32 * 128 * 128
    expected = q3n_flops.iteration_flops(m)
    assert 4.0e13 < expected < 6.0e13  # the update (3 x 2 x 32,768 tokens x ~190M) is three quarters of it
    counted = q3n_flops.iteration_flops(m, {"rollout_pairs_held": 300.0, "update_pairs_held": 40000.0})
    assert counted > expected  # more pairs on the held experts, more FLOPs: counted, not padded
    flops, nbytes = q3n_flops.update_experts_flops_bytes(m)
    assert flops / 197e12 < nbytes / 819e9  # 160 tokens an expert a step: the weights' bytes bound it
    flops, nbytes = q3n_flops.update_delta_rule_flops_bytes(m)
    assert flops == 3 * 2 * tokens * 3 * 3 * 32 * 128 * 128 and flops / 197e12 < nbytes / 819e9


def test_the_scope_reader_on_a_made_up_capture():
    capture = ps.ProgramCapture()
    capture.modules["/device:TPU:0"] = [("jit_anakin_step", 0.0, 10.0), ("jit_anakin_step", 10.0, 20.0)]
    stacks = {
        "a": "jit(anakin_step)/jit(main)/rollout/while/body/linear_attention/delta_rule/mul",
        "b": "jit(anakin_step)/jit(main)/update/while/body/transpose(jvp(linear_attention))/transpose(jvp(delta_rule))/while/body/dot",
        "c": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/linear_attention/dot_general",
        "d": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/shared_expert/dot_general",
        "e": "jit(anakin_step)/jit(main)/update/optimizer/add",
        "f": "jit(anakin_step)/jit(main)/copy",
        "g": "jit(anakin_step)/jit(main)/update/while/body/add",
    }
    capture.scopes = dict(stacks)
    ops = []
    for start in (0.0, 10.0):
        ops += [("a", start + 1, start + 3), ("b", start + 3, start + 6), ("c", start + 6, start + 7),
                ("d", start + 7, start + 7.5), ("e", start + 7.5, start + 8), ("f", start + 8, start + 8.5),
                ("g", start + 8.5, start + 9)]
    capture.ops["/device:TPU:0"] = ops
    assert q3n_spans.place_of(stacks["b"]) == ("update", "delta_rule") and q3n_spans.place_of(stacks["f"]) == (None, None)
    assert q3n_spans.part_ms(capture, ("delta_rule",)) == pytest.approx(5000.0)
    assert q3n_spans.part_ms(capture, ("delta_rule",), phase="update") == pytest.approx(3000.0)
    assert q3n_spans.part_ms(capture, ("linear_attention", "delta_rule")) == pytest.approx(6000.0)  # the whole mixer
    assert q3n_spans.part_ms(capture, ("shared_expert",)) == pytest.approx(500.0)
    other = q3n_spans.part_ms(capture, ("embed", "gae", "ppo_loss", None))
    assert other == pytest.approx(1000.0)  # the op under no scope and the one under a phase and no part
    named = ("linear_attention", "delta_rule", "attention", "router", "experts", "shared_expert", "lm_head", "value_head", "optimizer")
    assert q3n_spans.part_ms(capture, named) + other == pytest.approx(q3n_spans.part_ms(capture))
    assert q3n_spans.unscoped_share(capture) == pytest.approx(100 * 0.5 / 8.0)
    assert q3n_spans.program_parts(ps.ProgramCapture()) is None  # a capture without the program: nothing, not 0
    # the reader is `lm_spans.py`'s own, by other parts: the two read one capture each by its parts
    assert lm_spans.part_ms(capture, ("delta_rule",)) == 0.0 and lm_spans.part_ms(capture) == pytest.approx(q3n_spans.part_ms(capture))
    assert q3n_spans.part_ms(capture, ("delta_rule",)) == pytest.approx(5000.0)
