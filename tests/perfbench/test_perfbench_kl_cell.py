"""The sequence-policy cell `kimi_linear_48b_a3b_ep32.ppo_64x512`: its whole run at tiny widths
on the CPU, a traced run that reports its counter metrics, faults that `correct` catches, the
configuration's file against the composed configuration and the published widths, and the
functions that count its FLOPs and read its capture."""

import json
import os

import pytest

from perfbench.harness import bench, kl_faults, kl_flops, kl_spans, lm_spans
from perfbench.harness import program_spans as ps

CELL = "kimi_linear_48b_a3b_ep32.ppo_64x512"
TINY_LM = [
    "algo.lm.vocab_size=64", "algo.lm.hidden_size=32", "algo.lm.intermediate_size=48", "algo.lm.moe_intermediate_size=24",
    "algo.lm.num_attention_heads=4", "algo.lm.qk_nope_head_dim=8", "algo.lm.qk_rope_head_dim=4", "algo.lm.v_head_dim=8",
    "algo.lm.kv_lora_rank=16", "algo.lm.linear_attn_config.num_heads=2", "algo.lm.linear_attn_config.head_dim=8",
    "algo.lm.linear_attn_config.kda_layers=[1,2,4]", "algo.lm.linear_attn_config.full_attn_layers=[3]",
    "algo.lm.num_hidden_layers=4", "algo.lm.num_experts=16", "algo.lm.num_experts_per_tok=3", "algo.lm.experts_held=[4,8]",
    "algo.lm.chunk_size=32",
    "env.num_envs=8", "algo.rollout_steps=36", "algo.per_rank_batch_size=4",
    "env.tokens.prompt_min=4", "env.tokens.prompt_max=8", "algo.optimizer.lr=1e-3",
]
ARITHMETIC = ("rollout_logprob_gap", "rollout_value_gap", "policy_loss_gap", "value_loss_gap", "entropy_loss_gap",
              "grad_gap", "update_gap")
ROLLOUT, ROUTING = {"rollout_logprob_gap", "rollout_value_gap"}, {"route_mismatch_share", "route_flip_margin"}
UPDATE = {"policy_loss_gap", "value_loss_gap", "grad_gap", "update_gap"}


@pytest.fixture
def config(repo_root):
    with open(os.path.join(repo_root, "perfbench", "configs", "kimi_linear_48b_a3b_ep32.json")) as fh:
        return json.load(fh)


@pytest.mark.timeout(600)
def test_cell_runs_and_agrees_with_the_reference_at_tiny_widths():
    result = bench.run_cell(CELL, 2**31 + 77, 0.5, False, platform="cpu", extra_overrides=TINY_LM)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"} and result["metrics"]["env_steps_per_s"]["value"] > 0
    compared = result["compared"]
    # float32 on both sides here: decoding one token a step through the KDA states and the latent
    # cache agrees with the recurrent reference's full forward, and the chunked update with
    # `jax.grad` of it, to rounding; no choice of experts differs
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
    assert {"compile_s", "compiles_in_window", "host_other_share", "train_call_ms", "kl_max_expert_load"} <= set(metrics)
    assert metrics["kl_max_expert_load"]["value"] >= 1.0 and metrics["compiles_in_window"]["value"] == 0
    for absent in ("train_step_mfu", "kl_rollout_device_ms", "kl_kda_rule_roofline_share", "kl_experts_roofline_share",
                   "dsv3_max_expert_load", "q3n_max_expert_load", "moe_max_expert_load", "env_steps_per_s"):
        assert absent not in metrics


@pytest.mark.timeout(600)
@pytest.mark.parametrize("kind, caught_by, unseen", [
    ("scalar_decay", ROLLOUT | UPDATE, set()),
    ("mla_rope", ROLLOUT | UPDATE, set()),
    ("gate_silu", ROLLOUT | UPDATE, set()),
    ("no_beta", ROLLOUT | UPDATE, set()),
    ("top7", ROLLOUT | UPDATE, set()),
    ("no_expert_bias", ROUTING, set()),
    ("no_shared_expert", ROLLOUT | UPDATE, set()),
    ("rollout_state_zeroed", ROLLOUT, UPDATE),  # the update's chunked form is sound: only the decode's side sees it
])
def test_a_fault_comes_out_as_not_correct(kind, caught_by, unseen):
    # the file's limits are the chip's; at tiny widths in float32 rounding is far below these
    limits = {**{name: 1e-3 for name in ARITHMETIC}, "route_mismatch_share": 1e-3, "route_flip_margin": 1e-4}
    with kl_faults.planted(kind):
        result = bench.run_cell(CELL, 11, 0.2, False, platform="cpu", extra_overrides=TINY_LM)
    assert result["correct"] is False and result["failed"] >= 1  # under the file's own limits, the chip's, too
    over = {k for k, v in result["compared"].items() if k in limits and v["value"] > limits[k]}
    assert over & caught_by, result["compared"]
    assert not over & unseen, result["compared"]


def test_every_fault_is_planted_and_taken_out_again():
    from sheeprl_tpu.models import deepseek_v3, kimi_linear

    names = [(kimi_linear, name) for name in ("_kda_inputs", "_kda_output", "kda_step", "route", "expert_layer")]
    names.append((deepseek_v3, "_latent_inputs"))
    sound = {name: getattr(module, name) for module, name in names}
    for kind in kl_faults.KINDS:
        with kl_faults.planted(kind):
            changed = [name for module, name in names if getattr(module, name) is not sound[name]]
            assert len(changed) == (0 if kind in kl_faults.OF_THE_LOOP else 1), kind
        assert all(getattr(module, name) is sound[name] for module, name in names)
    assert {"scalar_decay", "mla_rope", "gate_silu", "no_beta", "top7", "no_expert_bias", "no_shared_expert",
            "rollout_state_zeroed"} <= set(kl_faults.KINDS)
    with pytest.raises(ValueError, match="unknown fault"):
        with kl_faults.planted("top9"):
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
    assert cfg.algo.lm.model_type == "kimi_linear" and cfg.algo.lm.mla_use_nope is True
    assert cfg.metric.log_level == 0 and cfg.checkpoint.every == 0 and cfg.algo.run_test is False
    assert data["cell"]["chips"] == 1 and data["traffic"]["warmup_cycles"] == 3 and data["traffic"]["trace_cycles"] == 2
    # a limit for each compared name and for no other
    assert set(data["config"]["limits"]) == set(adapter.compared)
    assert sum(limit is not None for limit in data["config"]["limits"].values()) >= 10


def test_the_cell_lists_the_metrics_of_its_own_trunk_and_the_shared_ones(repo_root):
    """`cycle_p90_ms` reads nothing under ten cycles and a traced window of this cell holds
    fewer, so that metric's list stays as it was; every other metric without a list is read here too."""
    manifest = bench.load_cell(CELL, repo_root)["manifest"]
    listed = [entry["name"] for entry in bench.metrics_for(manifest, CELL, "per_layer")]
    assert "cycle_p90_ms" not in listed
    assert [name for name in listed if not name.startswith("kl_")] == [
        "compile_s", "compiles_in_window", "host_other_share", "train_call_ms", "train_device_ms", "train_step_mfu",
        "mxu_op_share", "device_idle_share", "hbm_peak_gb"]
    assert len([name for name in listed if name.startswith("kl_")]) == 15
    for entry in manifest["per_layer"]:
        if entry["name"].startswith("kl_"):
            assert entry["workloads"] == [CELL] and entry["moves"] == "env_steps_per_s"
            assert entry["name"].endswith("roofline_share") == (entry["layer"] == "kernels")
            assert os.path.exists(os.path.join(repo_root, "perfbench", "metrics", entry["name"] + ".py"))


def test_the_configuration_keeps_the_published_widths(config):
    """Kimi-Linear-48B-A3B-Instruct's config.json: no width differs; depth, experts held,
    vocabulary and the layer lists cut to the layers held are the cut, each under `reduced` with
    the published value beside it."""
    published = {"first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
                 "intermediate_size": 9216, "kv_lora_rank": 512, "mla_use_nope": True, "model_max_length": 1048576,
                 "model_type": "kimi_linear", "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
                 "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
                 "num_experts_per_token": 8, "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
                 "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
                 "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128}
    for key, value in published.items():
        assert config[key] == value and key not in config["reduced"], key
    cut = {"num_hidden_layers": (5, 27), "num_experts": (8, 256), "vocab_size": (20480, 163840)}
    for key, (held, whole) in cut.items():
        assert config[key] == held and config["published"][key] == whole and key in config["reduced"], key
    linear, whole = config["linear_attn_config"], config["published"]["linear_attn_config"]
    assert {k: linear[k] for k in ("num_heads", "head_dim", "short_conv_kernel_size")} == {
        "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4} == {k: whole[k] for k in ("num_heads", "head_dim", "short_conv_kernel_size")}
    assert linear["kda_layers"] == [i for i in whole["kda_layers"] if i <= 5] == [1, 2, 3, 5]
    assert linear["full_attn_layers"] == [i for i in whole["full_attn_layers"] if i <= 5] == [4]
    assert len(whole["kda_layers"]) == 20 and len(whole["full_attn_layers"]) == 7 and "linear_attn_config" in config["reduced"]
    assert config["published"]["chips_sharing_a_layer"] == 32 and config["vocab_size"] * 8 == 163840
    model = config["model"]
    assert model["num_experts_routed"] == 256 and model["experts_held"] == [0, 8] and model["routed_scaling_factor"] == 2.446
    assert model["num_hidden_layers"] - model["first_k_dense_replace"] >= 4  # the guide's floor of expert layers
    assert {"decay initialisation", "output gate", "head_dim", "expert bias", "value head", "dispatch bound"} <= set(config["assumed"])
    assert config["deployment"].startswith("Thirty-two chips share each layer")


def _spec(m):
    from sheeprl_tpu.models import kimi_linear

    return kimi_linear.KimiLinearSpec(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"], intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"], num_attention_heads=m["num_attention_heads"],
        qk_nope_head_dim=m["qk_nope_head_dim"], qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        kv_lora_rank=m["kv_lora_rank"], num_hidden_layers=m["num_hidden_layers"], first_k_dense_replace=m["first_k_dense_replace"],
        kda_layers=tuple(m["kda_layers"]), full_attn_layers=tuple(m["full_attn_layers"]),
        linear_num_heads=m["linear_num_heads"], linear_head_dim=m["linear_head_dim"],
        num_experts=m["num_experts_routed"], num_experts_per_tok=m["num_experts_per_tok"], experts_held=tuple(m["experts_held"]),
        num_shared_experts=m["num_shared_experts"], routed_scaling_factor=m["routed_scaling_factor"], max_seq_len=m["rollout_steps"])


def test_flops_bytes_and_parameters_from_the_model_block(config):
    from sheeprl_tpu.models import kimi_linear, lm_layers

    m = config["model"]
    spec = _spec(m)
    assert spec.layers == [("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"), ("kda", "moe")]
    assert kimi_linear.parameter_count(spec) == config["parameters_held"] == 602_453_120  # 9.6 GB at 16 B
    # a sequence's carry: four [32, 128, 128] states and their convolutions' three columns, one [512, 576] cache
    assert spec.state_bytes_per_sequence == 4 * (4 * (32 * 128 * 128 + 3 * 3 * 4096) + 512 * 576)
    assert lm_layers.dispatch_rows(spec, m["minibatch_sequences"] * m["rollout_steps"]) == 4096
    # the rule's three products a token a head; the layer's other products: W_q, W_k, W_v and W_o,
    # the taps, the decay's and the gate's low-rank pairs, W_b
    assert kl_flops.kda_rule_macs(m) == 3 * 32 * 128 * 128
    assert kl_flops.kda_macs(m) == 4 * 2304 * 4096 + 3 * 4 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 3 * 32 * 128 * 128
    expected = kl_flops.iteration_flops(m)
    assert 8.0e13 < expected < 1.0e14  # the update (3 x 2 x 32,768 tokens x ~350M) is three quarters of it
    counted = kl_flops.iteration_flops(m, {"rollout_pairs_held": 300.0, "update_pairs_held": 40000.0})
    assert counted > expected  # more pairs on the held experts, more FLOPs: counted, not padded
    flops, nbytes = kl_flops.update_experts_flops_bytes(m)
    # 256 pairs an expert a step: the held weights' bytes bound it, at over twice the FLOPs' time
    assert (flops / 197e12) / (nbytes / 819e9) < 0.5
    flops, nbytes = kl_flops.update_kda_rule_flops_bytes(m)
    tokens = 512 * 64 * 4
    assert flops == 3 * 2.0 * tokens * 3 * 32 * 128 * 128
    assert nbytes == 3 * 4.0 * (5 * 4096 + 32) * tokens
    assert flops / 197e12 < nbytes / 819e9  # a token's operands bound the recurrent form's products


def test_the_scope_reader_on_a_made_up_capture():
    capture = ps.ProgramCapture()
    capture.modules["/device:TPU:0"] = [("jit_anakin_step", 0.0, 10.0), ("jit_anakin_step", 10.0, 20.0)]
    stacks = {
        "a": "jit(anakin_step)/jit(main)/rollout/while/body/kda/kda_rule/delta_rule_decode",
        "b": "jit(anakin_step)/jit(main)/update/while/body/transpose(jvp(kda))/transpose(jvp(kda_rule))/dot_general",
        "c": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/kda/dot_general",
        "d": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/mla/mla_attend/dot_general",
        "e": "jit(anakin_step)/jit(main)/update/optimizer/add",
        "f": "jit(anakin_step)/jit(main)/copy",
        "g": "jit(anakin_step)/jit(main)/update/while/body/add",
        "h": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/dense_ffn/dot_general",
    }
    capture.scopes = dict(stacks)
    ops = []
    for start in (0.0, 10.0):
        ops += [("a", start + 1, start + 3), ("b", start + 3, start + 6), ("c", start + 6, start + 7),
                ("d", start + 7, start + 7.5), ("e", start + 7.5, start + 8), ("f", start + 8, start + 8.5),
                ("g", start + 8.5, start + 9), ("h", start + 9, start + 9.5)]
    capture.ops["/device:TPU:0"] = ops
    assert kl_spans.place_of(stacks["b"]) == ("update", "kda_rule") and kl_spans.place_of(stacks["f"]) == (None, None)
    assert kl_spans.part_ms(capture, ("kda_rule",)) == pytest.approx(5000.0)
    assert kl_spans.part_ms(capture, ("kda_rule",), phase="rollout") == pytest.approx(2000.0)
    assert kl_spans.part_ms(capture, ("kda", "kda_rule")) == pytest.approx(6000.0)  # the whole mixer
    assert kl_spans.part_ms(capture, ("mla", "mla_attend")) == pytest.approx(500.0)
    other = kl_spans.part_ms(capture, ("embed", "gae", "ppo_loss", "optimizer", None))
    assert other == pytest.approx(1500.0)  # Adam, the op under no scope and the one under a phase and no part
    named = ("kda", "kda_rule", "mla", "mla_attend", "router", "experts", "shared_expert", "dense_ffn", "lm_head", "value_head")
    assert kl_spans.part_ms(capture, named) + other == pytest.approx(kl_spans.part_ms(capture))
    assert kl_spans.unscoped_share(capture) == pytest.approx(100 * 0.5 / 8.5)
    assert kl_spans.program_parts(ps.ProgramCapture()) is None  # a capture without the program: nothing, not 0
    # the reader is `lm_spans.py`'s own, by other parts: the two read one capture each by its parts
    assert lm_spans.part_ms(capture, ("kda_rule",)) == 0.0 and lm_spans.part_ms(capture) == pytest.approx(kl_spans.part_ms(capture))

    class Run:  # what a roofline reader is handed: the capture and the chip's peaks
        peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}

    run = Run()
    run._program_capture = capture  # where `program_spans.capture_of` keeps a run's capture
    # 3 s of the update's `kda_rule`; the bytes' 1.5 s is the roofline, the FLOPs' half a second is not
    assert kl_spans.roofline_share(run, "kda_rule", "update", 50.0, 15.0) == pytest.approx(50.0)
    assert kl_spans.roofline_share(run, "experts", "update", 50.0, 10.0) is None  # no such op on this capture
