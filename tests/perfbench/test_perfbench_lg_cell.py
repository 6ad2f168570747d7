"""The sequence-policy cell `laguna_xs2_ep16.ppo_32x2048`: its whole run at tiny widths on the CPU, a
traced run that reports its counter metrics, faults that `correct` catches, the configuration's file
against the composed configuration and the published widths, the traffic file, and the functions that
count its FLOPs and read its capture."""

import json
import os

import pytest

from perfbench.harness import bench, lg_faults, lg_flops, lg_spans, lm_spans
from perfbench.harness import program_spans as ps

CELL = "laguna_xs2_ep16.ppo_32x2048"
# one full layer with the dense feed-forward and two sliding ones with experts, a window of 8 in
# episodes of 40: the ring wraps four times
TINY_LM = [
    "algo.lm.vocab_size=64", "algo.lm.hidden_size=32", "algo.lm.intermediate_size=48", "algo.lm.moe_intermediate_size=24",
    "algo.lm.shared_expert_intermediate_size=16", "algo.lm.num_key_value_heads=2", "algo.lm.head_dim=16",
    "algo.lm.num_hidden_layers=3", "algo.lm.layer_types=[full_attention,sliding_attention,sliding_attention]",
    "algo.lm.mlp_layer_types=[dense,sparse,sparse]", "algo.lm.num_attention_heads_per_layer=[6,8,8]",
    "algo.lm.sliding_window=8", "algo.lm.num_experts=16", "algo.lm.num_experts_per_tok=3", "algo.lm.experts_held=[4,8]",
    "env.num_envs=8", "algo.rollout_steps=40", "algo.per_rank_batch_size=4",
    "env.tokens.prompt_min=4", "env.tokens.prompt_max=8", "algo.optimizer.lr=1e-3",
]
ARITHMETIC = ("rollout_logprob_gap", "rollout_value_gap", "policy_loss_gap", "value_loss_gap", "entropy_loss_gap",
              "grad_gap", "update_gap")
ROLLOUT, ROUTING = {"rollout_logprob_gap", "rollout_value_gap"}, {"route_mismatch_share", "route_flip_margin"}
UPDATE = {"policy_loss_gap", "value_loss_gap", "grad_gap", "update_gap"}


@pytest.fixture
def config(repo_root):
    with open(os.path.join(repo_root, "perfbench", "configs", "laguna_xs2_ep16.json")) as fh:
        return json.load(fh)


@pytest.mark.timeout(600)
def test_cell_runs_and_agrees_with_the_reference_at_tiny_widths():
    result = bench.run_cell(CELL, 2**31 + 77, 0.5, False, platform="cpu", extra_overrides=TINY_LM)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"} and result["metrics"]["env_steps_per_s"]["value"] > 0
    compared = result["compared"]
    # float32 on both sides here: decoding one token a step through the rings and the caches agrees
    # with the reference's masked full softmax, and the banded update with `jax.grad` of it, to
    # rounding; no choice of experts differs
    for name in ARITHMETIC:
        assert compared[name]["value"] < 2e-5, name
    assert compared["route_mismatch_share"]["value"] == 0.0 and compared["route_flip_margin"]["value"] == 0.0
    assert compared["env_mismatch_count"] == {"value": 0.0, "limit": 0}


@pytest.mark.timeout(600)
def test_traced_run_reports_the_counter_metrics_it_can_read():
    result = bench.run_cell(CELL, 5, 0.5, True, platform="cpu", extra_overrides=TINY_LM)
    assert result["correct"] is True
    metrics = result["metrics"]
    # the host's spans and the program's counters are read on any platform; there is no TPU
    # capture here, and a reader that finds nothing to read returns nothing
    assert {"compile_s", "compiles_in_window", "host_other_share", "train_call_ms", "lg_max_expert_load",
            "lg_swa_ring_rows_read"} <= set(metrics)
    assert metrics["lg_max_expert_load"]["value"] >= 1.0 and metrics["compiles_in_window"]["value"] == 0
    # a window of 8 over episodes of 40: every decode step reads the ring's 8 rows
    assert metrics["lg_swa_ring_rows_read"]["value"] == pytest.approx(8.0)
    for absent in ("train_step_mfu", "lg_rollout_device_ms", "lg_swa_attend_roofline_share", "lg_experts_roofline_share",
                   "kl_max_expert_load", "q3n_max_expert_load", "moe_max_expert_load", "env_steps_per_s"):
        assert absent not in metrics


@pytest.mark.timeout(600)
@pytest.mark.parametrize("kind, caught_by, unseen", [
    ("no_window", ROLLOUT | UPDATE, set()),
    ("ring_slot_rotation", ROLLOUT, UPDATE),  # the update's band is sound: only the decode's side sees it
    ("yarn_default", ROLLOUT | UPDATE, set()),
    ("no_attention_factor", ROLLOUT | UPDATE, set()),
    ("full_rope_whole_head", ROLLOUT | UPDATE, set()),
    ("no_gate", ROLLOUT | UPDATE, set()),
    ("softmax_scores", ROUTING | UPDATE, set()),
    ("top7", ROLLOUT | UPDATE, set()),
    ("no_shared_expert", ROLLOUT | UPDATE, set()),
    ("half_sequences", UPDATE, ROLLOUT),
    ("state_unchanged", {"update_gap"}, ROLLOUT),
])
def test_a_fault_comes_out_as_not_correct(kind, caught_by, unseen):
    # the file's limits are the chip's; at tiny widths in float32 rounding is far below these
    limits = {**{name: 1e-3 for name in ARITHMETIC}, "route_mismatch_share": 1e-3, "route_flip_margin": 1e-4}
    with lg_faults.planted(kind):
        result = bench.run_cell(CELL, 11, 0.2, False, platform="cpu", extra_overrides=TINY_LM)
    assert result["correct"] is False and result["failed"] >= 1  # under the file's own limits, the chip's, too
    over = {k for k, v in result["compared"].items() if k in limits and v["value"] > limits[k]}
    assert over & caught_by, result["compared"]
    assert not over & unseen, result["compared"]


def test_the_readings_script_makes_one_reading_a_process(repo_root):
    script = bench.load_file(os.path.join(repo_root, "perfbench", "lg_readings.py"))
    one = ["--workload", CELL, "--seeds", "5", "--fault", "top7"]
    assert script.one_reading_each(one) == [one]
    lines = script.one_reading_each(["--workload", CELL, "--seeds", "5,6", "--fault", "no_window,top7", "--out", "x"])
    assert lines == [["--workload", CELL, "--out", "x", "--seeds", seed, "--fault", fault]
                     for fault in ("no_window", "top7") for seed in ("5", "6")]
    assert script.one_reading_each(["--workload", CELL, "--seeds=1,2", "--control", "matmul"]) == [
        ["--workload", CELL, "--control", "matmul", "--seeds", seed] for seed in ("1", "2")]


def test_every_fault_is_planted_and_taken_out_again():
    from sheeprl_tpu.models import laguna, lm_layers

    names = [(laguna, name) for name in ("swa_forward", "init_carry", "attention_step", "rope_frequencies", "output_gate",
                                         "route", "expert_layer")]
    names.append((lm_layers, "yarn_frequencies"))
    sound = {name: getattr(module, name) for module, name in names}
    for kind in lg_faults.KINDS:
        with lg_faults.planted(kind):
            changed = [name for module, name in names if getattr(module, name) is not sound[name]]
            expected = 0 if kind in lg_faults.OF_THE_LOOP else 2 if kind in ("no_window", "window_513") else 1
            assert len(changed) == expected, kind
        assert all(getattr(module, name) is sound[name] for module, name in names)
    assert {"no_window", "ring_slot_rotation", "yarn_default", "no_attention_factor", "full_rope_whole_head", "no_gate",
            "softmax_scores", "top7", "no_shared_expert"} <= set(lg_faults.REFUSED)
    assert "window_513" in lg_faults.KINDS and "window_513" not in lg_faults.REFUSED  # a reading, not a requirement
    with pytest.raises(ValueError, match="unknown fault"):
        with lg_faults.planted("top9"):
            pass


def test_the_configuration_file_says_what_is_run(repo_root, monkeypatch):
    from sheeprl_tpu.config import compose

    monkeypatch.setenv("SHEEPRL_SEARCH_PATH", os.path.join(repo_root, "perfbench", "sheeprl_configs"))
    data = bench.load_cell(CELL, repo_root)
    cfg = compose([f"exp={data['config']['exp']}", *data["config"]["overrides"], *data["traffic"]["overrides"]])
    adapter = bench.load_adapter(data["config"], repo_root)
    model = data["config"]["model"]
    assert adapter.spec(cfg) == model
    assert adapter.cycle(cfg) == (1, 32 // model["minibatch_sequences"], 65536)
    assert cfg.algo.lm.model_type == "laguna" and cfg.env.tokens.episode_steps == 2048
    assert cfg.metric.log_level == 0 and cfg.checkpoint.every == 0 and cfg.algo.run_test is False
    assert data["cell"]["chips"] == 1 and data["cell"]["traffic"] == "ppo_32x2048"
    # episodes four windows long: a sliding layer reads a quarter of the keys a full one reads at the end
    assert model["rollout_steps"] == 4 * model["sliding_window"] and model["minibatch_sequences"] * 2048 == 8192
    assert data["traffic"]["warmup_cycles"] == 3 and data["traffic"]["trace_cycles"] == 2
    # a limit for each compared name and for no other
    assert set(data["config"]["limits"]) == set(adapter.compared)
    assert sum(limit is not None for limit in data["config"]["limits"].values()) >= 10


@pytest.mark.parametrize("cycle_s", [18.49, 11.44, 25.0], ids=["measured", "one_pass", "slower"])
def test_the_traced_cycles_end_inside_the_window(repo_root, cycle_s):
    """A traced run's capture is written when its last traced cycle ends (`bench.Run._on_cycle`), so
    that cycle has to end by the window's last boundary. The window of `run_seconds` holds three of
    this cell's cycles of 18.49 s (measured on a v5e): a trace that started at the second boundary
    would end at the fourth, which never comes. It starts at the first and holds two whole cycles,
    at the measured cycle, at the one-pass control's 11.44 s, and up to a cycle of 25 s."""
    from perfbench.harness.window import CycleWindow

    traffic = bench.load_cell(CELL, repo_root)["traffic"]
    seconds = json.load(open(os.path.join(repo_root, "BENCHMARK.json")))["run_seconds"]
    first, cycles = traffic["trace_start_cycle"], traffic["trace_cycles"]
    now, traced = [0.0], []
    window = CycleWindow(cycle_iterations=1, gradient_steps_per_cycle=8, env_steps_per_iteration=65536, seconds=seconds,
                         warmup_cycles=traffic["warmup_cycles"], clock=lambda: now[0],
                         on_cycle=lambda index: traced.append(index) if first <= index <= first + cycles else None)
    window.arm()
    for _ in range(100):
        now[0] += cycle_s
        window.on_train(8)
        if window.on_iteration_end():
            break
    assert traced == list(range(first, first + cycles + 1)) and window.cycles >= first + cycles
    assert cycles == 2


def test_the_cell_lists_the_metrics_of_its_own_trunk_and_the_shared_ones(repo_root):
    """`cycle_p90_ms` reads nothing under ten cycles and a traced window of this cell holds
    fewer, so that metric's list stays as it was; every other metric without a list is read here too."""
    manifest = bench.load_cell(CELL, repo_root)["manifest"]
    listed = [entry["name"] for entry in bench.metrics_for(manifest, CELL, "per_layer")]
    assert "cycle_p90_ms" not in listed
    assert [name for name in listed if not name.startswith("lg_")] == [
        "compile_s", "compiles_in_window", "host_other_share", "train_call_ms", "train_device_ms", "train_step_mfu",
        "mxu_op_share", "device_idle_share", "hbm_peak_gb"]
    assert len([name for name in listed if name.startswith("lg_")]) == 16
    for entry in manifest["per_layer"]:
        if entry["name"].startswith("lg_"):
            assert entry["workloads"] == [CELL] and entry["moves"] == "env_steps_per_s"
            assert entry["name"].endswith("roofline_share") == (entry["layer"] == "kernels")
            assert os.path.exists(os.path.join(repo_root, "perfbench", "metrics", entry["name"] + ".py"))
    names = [entry["name"] for entry in manifest["per_layer"]]
    assert names[-16:] == [name for name in listed if name.startswith("lg_")]  # appended, nothing before them moved


def test_the_configuration_keeps_the_published_widths(config):
    """Laguna-XS.2's config.json: no width differs; depth, experts held, vocabulary and the three
    per-layer lists cut to the layers held are the cut, each under `reduced` with the published
    value beside it."""
    published = {"model_type": "laguna", "hidden_size": 2048, "intermediate_size": 8192, "num_attention_heads": 48,
                 "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144, "attention_bias": False,
                 "rms_norm_eps": 1e-06, "num_experts_per_tok": 8, "moe_intermediate_size": 512,
                 "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
                 "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5}
    for key, value in published.items():
        assert config[key] == value and key not in config["reduced"], key
    rope = config["rope_parameters"]
    assert rope["full_attention"] == {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                                      "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                                      "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5}
    assert rope["sliding_attention"] == {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    cut = {"num_hidden_layers": (5, 40), "num_experts": (16, 256), "vocab_size": (12544, 100352)}
    for key, (held, whole) in cut.items():
        assert config[key] == held and config["published"][key] == whole and key in config["reduced"], key
    kinds = ["full_attention"] + ["sliding_attention"] * 3
    assert config["layer_types"] == kinds + ["full_attention"] and config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert {"layer_types", "mlp_layer_types", "num_attention_heads_per_layer"} <= set(config["reduced"])
    assert config["published"]["chips_sharing_a_layer"] == 16 and config["vocab_size"] * 8 == 100352
    model = config["model"]
    assert model["num_experts_routed"] == 256 and model["experts_held"] == [0, 16] and model["routed_scaling_factor"] == 2.5
    assert model["num_hidden_layers"] - model["mlp_layer_types"].count("dense") >= 4  # the guide's floor of expert layers
    assert model["rope_parameters"]["full_attention"]["attention_factor"] == rope["full_attention"]["attention_factor"]
    assert {"output gate", "router scoring", "qk norm", "window", "yarn", "norms", "weights", "value head",
            "dispatch bound"} <= set(config["assumed"])
    assert "33.443B with a gate a head" in config["assumed"]["output gate"]
    assert config["deployment"].startswith("Sixteen chips share each layer")


def _spec(m):
    from sheeprl_tpu.models import laguna

    return laguna.LagunaSpec(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"], intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"], shared_expert_intermediate_size=m["shared_expert_intermediate_size"],
        num_key_value_heads=m["num_key_value_heads"], head_dim=m["head_dim"], layer_types=tuple(m["layer_types"]),
        mlp_layer_types=tuple(m["mlp_layer_types"]), num_attention_heads_per_layer=tuple(m["num_attention_heads_per_layer"]),
        sliding_window=m["sliding_window"],
        rope_parameters=tuple((kind, tuple(sorted(group.items()))) for kind, group in m["rope_parameters"].items()),
        num_experts=m["num_experts_routed"], num_experts_per_tok=m["num_experts_per_tok"], experts_held=tuple(m["experts_held"]),
        routed_scaling_factor=m["routed_scaling_factor"], max_seq_len=m["rollout_steps"])


def test_flops_bytes_and_parameters_from_the_model_block(config):
    from sheeprl_tpu.models import laguna, lm_layers

    m = config["model"]
    spec = _spec(m)
    assert [kind for kind, _, _ in spec.layers] == m["layer_types"]
    assert laguna.parameter_count(spec) == config["parameters_held"] == 490_300_416  # 7.84 GB at 16 B
    # a sequence's carry: two caches of 2,048 rows and three rings of 512, keys and values of 8 heads of 128
    assert spec.cache_bytes_per_sequence == 4 * 2 * (2 * 2048 + 3 * 512) * 8 * 128
    assert 32 * spec.cache_bytes_per_sequence == 1_476_395_008
    assert lm_layers.dispatch_rows(spec, m["minibatch_sequences"] * m["rollout_steps"]) == 8192
    # the keys a query reads: (T + 1) / 2 on a full layer; min(i + 1, 512) on a sliding one, 448.1 over 2,048
    assert lg_flops.keys_read(m, "full_attention") == 1024.5
    assert lg_flops.keys_read(m, "sliding_attention") == pytest.approx((512 * 513 / 2 + 1536 * 512) / 2048)
    assert lg_flops.attention_macs(m, "sliding_attention", 64) == pytest.approx(
        2048 * 8192 * 2 + 2 * 2048 * 1024 + 2048 * 64 + 2 * 8192 * lg_flops.keys_read(m, "sliding_attention"))
    expected = lg_flops.iteration_flops(m)
    assert 1.5e14 < expected < 1.8e14  # the update (3 x 2 x 65,536 tokens x ~317M) is three quarters of it
    counted = lg_flops.iteration_flops(m, {"rollout_pairs_held": 300.0, "update_pairs_held": 40000.0})
    assert counted > expected  # more pairs on the held experts, more FLOPs: counted, not padded
    flops, nbytes = lg_flops.update_experts_flops_bytes(m)
    # 256 pairs an expert a step: the held weights' bytes bound it
    assert (flops / 197e12) / (nbytes / 819e9) < 1.0
    flops, nbytes = lg_flops.swa_attend_flops_bytes(m)
    keys, tokens = lg_flops.keys_read(m, "sliding_attention"), 65536
    assert flops == pytest.approx(3 * tokens * 2 * 2 * keys * 64 * 128 * (1 + 3))
    ring = 3 * tokens * 4 * 2 * keys * 8 * 128  # the decode steps' reads of the rings
    assert ring < nbytes < 1.1 * ring and flops / 197e12 < nbytes / 819e9  # the rings' reads bound it


def test_the_scope_reader_on_a_made_up_capture():
    capture = ps.ProgramCapture()
    capture.modules["/device:TPU:0"] = [("jit_anakin_step", 0.0, 10.0), ("jit_anakin_step", 10.0, 20.0)]
    stacks = {
        "a": "jit(anakin_step)/jit(main)/rollout/while/body/swa/swa_attend/dot_general",
        "b": "jit(anakin_step)/jit(main)/update/while/body/transpose(jvp(swa))/transpose(jvp(swa_attend))/dot_general",
        "c": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/swa/dot_general",
        "d": "jit(anakin_step)/jit(main)/update/while/body/checkpoint/full_attn/attend/dot_general",
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
    assert lg_spans.place_of(stacks["b"]) == ("update", "swa_attend") and lg_spans.place_of(stacks["f"]) == (None, None)
    assert lg_spans.part_ms(capture, ("swa_attend",)) == pytest.approx(5000.0)
    assert lg_spans.part_ms(capture, ("swa_attend",), phase="rollout") == pytest.approx(2000.0)
    assert lg_spans.part_ms(capture, ("swa", "swa_attend")) == pytest.approx(6000.0)  # the whole mixer
    assert lg_spans.part_ms(capture, ("full_attn", "attend")) == pytest.approx(500.0)
    other = lg_spans.part_ms(capture, ("embed", "gae", "ppo_loss", "optimizer", None))
    assert other == pytest.approx(1500.0)  # Adam, the op under no scope and the one under a phase and no part
    named = ("swa", "swa_attend", "full_attn", "attend", "router", "experts", "shared_expert", "dense_ffn", "lm_head",
             "value_head")
    assert lg_spans.part_ms(capture, named) + other == pytest.approx(lg_spans.part_ms(capture))
    assert lg_spans.unscoped_share(capture) == pytest.approx(100 * 0.5 / 8.5)
    assert lg_spans.program_parts(ps.ProgramCapture()) is None  # a capture without the program: nothing, not 0
    # the reader is `lm_spans.py`'s own, by other parts: the two read one capture each by its parts
    assert lm_spans.part_ms(capture, ("swa_attend",)) == 0.0 and lm_spans.part_ms(capture) == pytest.approx(
        lg_spans.part_ms(capture))

    class Run:  # what a roofline reader is handed: the capture and the chip's peaks
        peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}

    run = Run()
    run._program_capture = capture  # where `program_spans.capture_of` keeps a run's capture
    # 5 s of `swa_attend` over both phases; the bytes' 2.5 s is the roofline, the FLOPs' half a second is not
    assert lg_spans.roofline_share(run, "swa_attend", None, 50.0, 25.0) == pytest.approx(50.0)
    assert lg_spans.roofline_share(run, "swa_attend", "update", 50.0, 15.0) == pytest.approx(50.0)
    assert lg_spans.roofline_share(run, "experts", "update", 50.0, 10.0) is None  # no such op on this capture
