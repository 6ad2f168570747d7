"""The sequence-policy cell `moonlight_16b_a3b_ep8.ppo_64x512`: its whole run at tiny widths
on the CPU, a traced run that reports its counter metrics, faults that `correct` catches, the
configuration's file against the composed configuration and the published widths, and the
functions that count its FLOPs and read its capture."""

import json
import os

import pytest

from perfbench.harness import bench, dsv3_faults, dsv3_flops, dsv3_spans, lm_spans
from perfbench.harness import program_spans as ps

CELL = "moonlight_16b_a3b_ep8.ppo_64x512"
TINY_LM = [
    "algo.lm.vocab_size=64", "algo.lm.hidden_size=32", "algo.lm.intermediate_size=48", "algo.lm.moe_intermediate_size=24",
    "algo.lm.num_attention_heads=4", "algo.lm.qk_nope_head_dim=8", "algo.lm.qk_rope_head_dim=4", "algo.lm.v_head_dim=8",
    "algo.lm.kv_lora_rank=16", "algo.lm.num_hidden_layers=3", "algo.lm.first_k_dense_replace=1", "algo.lm.num_experts=16",
    "algo.lm.num_experts_per_tok=3", "algo.lm.experts_held=[4,8]",
    "env.num_envs=8", "algo.rollout_steps=36", "algo.per_rank_batch_size=4",
    "env.tokens.prompt_min=4", "env.tokens.prompt_max=8", "algo.optimizer.lr=1e-3",
]
ARITHMETIC = ("rollout_logprob_gap", "rollout_value_gap", "policy_loss_gap", "value_loss_gap", "entropy_loss_gap",
              "grad_gap", "update_gap")


@pytest.fixture
def config(repo_root):
    with open(os.path.join(repo_root, "perfbench", "configs", "moonlight_16b_a3b_ep8.json")) as fh:
        return json.load(fh)


@pytest.mark.timeout(600)
def test_cell_runs_and_agrees_with_the_reference_at_tiny_widths():
    result = bench.run_cell(CELL, 2**31 + 77, 0.5, False, platform="cpu", extra_overrides=TINY_LM)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"} and result["metrics"]["env_steps_per_s"]["value"] > 0
    compared = result["compared"]
    # float32 on both sides here: decoding in the absorbed form through the latent caches agrees
    # with the expanded reference's full forward, and the update with `jax.grad` of it, to
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
    assert {"compile_s", "compiles_in_window", "host_other_share", "train_call_ms", "dsv3_max_expert_load",
            "dsv3_dispatch_fill"} <= set(metrics)
    assert metrics["dsv3_max_expert_load"]["value"] >= 1.0 and 0.0 < metrics["dsv3_dispatch_fill"]["value"] <= 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    for absent in ("train_step_mfu", "dsv3_rollout_device_ms", "dsv3_mla_decode_roofline_share", "dsv3_experts_roofline_share",
                   "lm_rollout_device_ms", "q3n_max_expert_load", "moe_max_expert_load", "env_steps_per_s"):
        assert absent not in metrics


@pytest.mark.timeout(600)
@pytest.mark.parametrize("kind", ["cache_k_unrotated", "rollout_rows_zeroed", "no_latent_norm", "scale_one"])
def test_a_fault_comes_out_as_not_correct(kind, config):
    limits = {name: 1e-3 for name in ARITHMETIC}  # the file's are the chip's: at tiny widths in float32, rounding is far below
    with dsv3_faults.planted(kind):
        result = bench.run_cell(CELL, 11, 0.2, False, platform="cpu", extra_overrides=TINY_LM)
    assert result["correct"] is False and result["failed"] >= 1  # under the file's own limits, the chip's, too
    over = {k for k, v in result["compared"].items() if k in limits and v["value"] > limits[k]}
    rollout, update = {"rollout_logprob_gap", "rollout_value_gap"}, {"policy_loss_gap", "value_loss_gap", "grad_gap"}
    caught_by = {"cache_k_unrotated": rollout, "rollout_rows_zeroed": rollout, "no_latent_norm": rollout | update,
                 "scale_one": rollout | update}[kind]
    assert over & caught_by, result["compared"]
    if kind in ("cache_k_unrotated", "rollout_rows_zeroed"):  # the update's expanded form is sound: only the decode's side sees it
        assert not over & update


def test_every_fault_is_planted_and_taken_out_again():
    from sheeprl_tpu.models import deepseek_v3

    names = ("route", "expert_layer", "_latent_inputs", "_score_scale", "mla_step")
    sound = {name: getattr(deepseek_v3, name) for name in names}
    for kind in dsv3_faults.KINDS:
        with dsv3_faults.planted(kind):
            changed = [name for name in names if getattr(deepseek_v3, name) is not sound[name]]
            assert len(changed) == (0 if kind in dsv3_faults.OF_THE_LOOP else 1), kind
        assert all(getattr(deepseek_v3, name) is sound[name] for name in names)
    with pytest.raises(ValueError, match="unknown fault"):
        with dsv3_faults.planted("top9"):
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
    assert sum(limit is not None for limit in data["config"]["limits"].values()) >= 10


def test_the_cell_lists_the_metrics_of_its_own_trunk_and_the_shared_ones(repo_root):
    """`cycle_p90_ms` reads nothing under ten cycles and a traced window of this cell holds
    fewer (the capture's stop stalls one cycle), so that metric's list stays the four cells
    it had; every other metric without a list is read here too."""
    manifest = bench.load_cell(CELL, repo_root)["manifest"]
    listed = [entry["name"] for entry in bench.metrics_for(manifest, CELL, "per_layer")]
    assert "cycle_p90_ms" not in listed
    assert [name for name in listed if not name.startswith("dsv3_")] == [
        "compile_s", "compiles_in_window", "host_other_share", "train_call_ms", "train_device_ms", "train_step_mfu",
        "mxu_op_share", "device_idle_share", "hbm_peak_gb"]
    assert len([name for name in listed if name.startswith("dsv3_")]) == 16
    for entry in manifest["per_layer"]:
        if entry["name"].startswith("dsv3_"):
            assert entry["workloads"] == [CELL] and entry["moves"] == "env_steps_per_s"
            assert entry["name"].endswith("roofline_share") == (entry["layer"] == "kernels")


def test_the_configuration_keeps_the_published_widths(config):
    """Moonlight-16B-A3B's config.json: no width differs; depth, experts held and vocabulary are
    the cut, each under `reduced` with the published count beside it."""
    published = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 11264, "kv_lora_rank": 512, "max_position_embeddings": 8192, "model_type": "deepseek_v3",
                 "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2, "norm_topk_prob": True,
                 "num_attention_heads": 16, "num_experts_per_tok": 6, "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
                 "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
                 "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
                 "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in published.items():
        assert config[key] == value and key not in config["reduced"], key
    cut = {"num_hidden_layers": (6, 27), "n_routed_experts": (8, 64), "vocab_size": (20480, 163840)}
    for key, (held, whole) in cut.items():
        assert config[key] == held and config["published"][key] == whole and key in config["reduced"], key
    assert config["published"]["chips_sharing_a_layer"] == 8 and config["vocab_size"] * 8 == 163840
    model = config["model"]
    assert model["num_experts_routed"] == 64 and model["experts_held"] == [0, 8] and model["routed_scaling_factor"] == 2.446
    assert model["num_hidden_layers"] - model["first_k_dense_replace"] >= 4  # the guide's floor of expert layers
    assert {"rotary layout", "expert bias", "value head", "seq_aux", "shared experts", "latent cache",
            "embedding and head", "dispatch bound"} <= set(config["assumed"])


def _spec(m):
    from sheeprl_tpu.models import deepseek_v3

    return deepseek_v3.DeepseekV3Spec(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"], intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"], num_attention_heads=m["num_attention_heads"],
        qk_nope_head_dim=m["qk_nope_head_dim"], qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        kv_lora_rank=m["kv_lora_rank"], num_hidden_layers=m["num_hidden_layers"], first_k_dense_replace=m["first_k_dense_replace"],
        num_experts=m["num_experts_routed"], num_experts_per_tok=m["num_experts_per_tok"], experts_held=tuple(m["experts_held"]),
        n_shared_experts=m["n_shared_experts"], routed_scaling_factor=m["routed_scaling_factor"], max_seq_len=m["rollout_steps"])


def test_flops_bytes_and_parameters_from_the_model_block(config):
    from sheeprl_tpu.models import deepseek_v3, lm_layers

    m = config["model"]
    spec = _spec(m)
    assert deepseek_v3.parameter_count(spec) == config["parameters_held"] == 668_892_480  # 10.7 GB at 16 B
    assert spec.latent_width == 576 and spec.cache_bytes_per_sequence == 6 * 512 * 576 * 4  # 7.1 MB; 0.45 GB at 64 sequences
    assert lm_layers.dispatch_rows(spec, m["minibatch_sequences"] * m["rollout_steps"]) == 12288
    tokens = m["rollout_steps"] * m["num_envs"]
    # the mixer's shared projections are 11.67M multiply-adds a token a layer; the expanded form adds
    # W_kvb's 2.10M and 5,120 a key, the absorbed form 2.10M of absorbed products and 17,408 a row
    assert dsv3_flops.projection_macs(m) == 2048 * 3072 + 2048 * 576 + 2048 * 2048
    assert dsv3_flops.expanded_macs(m, 1.0) - dsv3_flops.expanded_macs(m, 0.0) == 16 * 320
    assert dsv3_flops.absorbed_macs(m, 1.0) - dsv3_flops.absorbed_macs(m, 0.0) == 16 * 1088
    expected = dsv3_flops.iteration_flops(m)
    assert 7.0e13 < expected < 9.0e13  # the update (3 x 2 x 32,768 tokens x ~315M) is three quarters of it
    counted = dsv3_flops.iteration_flops(m, {"rollout_pairs_held": 300.0, "update_pairs_held": 40000.0})
    assert counted > expected  # more pairs on the held experts, more FLOPs: counted, not padded
    flops, nbytes = dsv3_flops.update_experts_flops_bytes(m)
    # 768 tokens an expert a step: the weights' bytes (35 ms an iteration) and the FLOPs (32 ms) bound it about alike
    assert 0.8 < (flops / 197e12) / (nbytes / 819e9) < 1.0
    flops, nbytes = dsv3_flops.rollout_mla_attend_flops_bytes(m)
    rows = 512 * 513 // 2
    assert nbytes == 4.0 * 6 * (512 * 512 * 16 * 256 + 64 * 576 * (rows + 512))
    assert flops == 2.0 * 64 * 6 * (512 * 16 * 2 * 128 * 512 + rows * 16 * 1088)
    assert flops / 197e12 < nbytes / 819e9  # the rows' bytes bound a decode step's attention
    assert tokens == 32768


def test_the_scope_reader_on_a_made_up_capture():
    capture = ps.ProgramCapture()
    capture.modules["/device:TPU:0"] = [("jit_anakin_step", 0.0, 10.0), ("jit_anakin_step", 10.0, 20.0)]
    stacks = {
        "a": "jit(anakin_step)/jit(main)/rollout/while/body/mla/mla_attend/dynamic_update_slice",
        "b": "jit(anakin_step)/jit(main)/update/while/body/transpose(jvp(mla))/transpose(jvp(mla_attend))/dot_general",
        "c": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/mla/dot_general",
        "d": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/shared_expert/dot_general",
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
    assert dsv3_spans.place_of(stacks["b"]) == ("update", "mla_attend") and dsv3_spans.place_of(stacks["f"]) == (None, None)
    assert dsv3_spans.part_ms(capture, ("mla_attend",)) == pytest.approx(5000.0)
    assert dsv3_spans.part_ms(capture, ("mla_attend",), phase="rollout") == pytest.approx(2000.0)
    assert dsv3_spans.part_ms(capture, ("mla", "mla_attend")) == pytest.approx(6000.0)  # the whole mixer
    assert dsv3_spans.part_ms(capture, ("shared_expert",)) == pytest.approx(500.0)
    other = dsv3_spans.part_ms(capture, ("embed", "gae", "ppo_loss", None))
    assert other == pytest.approx(1000.0)  # the op under no scope and the one under a phase and no part
    named = ("mla", "mla_attend", "router", "experts", "shared_expert", "dense_ffn", "lm_head", "value_head", "optimizer")
    assert dsv3_spans.part_ms(capture, named) + other == pytest.approx(dsv3_spans.part_ms(capture))
    assert dsv3_spans.unscoped_share(capture) == pytest.approx(100 * 0.5 / 8.5)
    assert dsv3_spans.program_parts(ps.ProgramCapture()) is None  # a capture without the program: nothing, not 0
    # the reader is `lm_spans.py`'s own, by other parts: the two read one capture each by its parts
    assert lm_spans.part_ms(capture, ("mla_attend",)) == 0.0 and lm_spans.part_ms(capture) == pytest.approx(dsv3_spans.part_ms(capture))

    class Run:  # what a roofline reader is handed: the capture and the chip's peaks
        peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}

    run = Run()
    run._program_capture = capture  # where `program_spans.capture_of` keeps a run's capture
    # 2 s of the rollout's `mla_attend`; the bytes' 1 s is the roofline, the FLOPs' half a second is not
    assert dsv3_spans.roofline_share(run, "mla_attend", "rollout", 50.0, 10.0) == pytest.approx(50.0)
    assert dsv3_spans.roofline_share(run, "experts", "update", 50.0, 10.0) is None  # no such op on this capture
