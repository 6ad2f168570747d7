"""``chip_smoke.py`` on the CPU: the same phase functions the chip check runs,
at tiny widths with the expected platform passed as ``"cpu"``, and ``main()``'s
refusal to pass without an accelerator."""

from __future__ import annotations

import json

import pytest

import chip_smoke
from sheeprl_tpu.analysis.programs import DREAMER_TINY_OVERRIDES

# the S experiment at the tiny widths every dreamer-family AOT builder uses
_TINY = [
    chip_smoke.S_TRAIN_OVERRIDES[0],
    *DREAMER_TINY_OVERRIDES,
    "env.id=discrete_dummy",
    "env.num_envs=1",
    "env.sync_env=True",
    "buffer.size=64",
    "algo.learning_starts=8",
    "algo.total_steps=10",
    "algo.run_test=False",
]


@pytest.mark.timeout(300)
def test_phases_run_on_cpu_at_tiny_widths(tmp_path):
    kernel = chip_smoke.kernel_phase("cpu", rows=(16,), K=128, H=128)
    assert kernel["compiled"] is False and "16" in kernel["rows"]
    train = chip_smoke.train_phase(_TINY, platform="cpu", grad_steps=3, out_dir=str(tmp_path))
    assert train["grad_steps"] == 3 and train["gru_branch"] == "xla"  # H=8: never Mosaic
    served = chip_smoke.serve_phase(
        train["checkpoint"],
        ["serve.slots=2", "serve.sessions=2", "serve.max_session_steps=8"],
        platform="cpu",
        sessions=2,
        out_dir=str(tmp_path),
    )
    assert served["sessions_finished"] == 2 and served["sessions_failed"] == 0


@pytest.mark.timeout(300)
def test_the_experts_phase_runs_on_cpu_at_small_widths(tmp_path):
    """The kernels in Pallas' interpreter against `ragged_dot`, then the sequence-policy loop
    at widths they would tile: on the CPU the update sorts its pairs and takes `ragged_dot`."""
    small = [o for o in chip_smoke.LM_OVERRIDES if not o.startswith(("fabric.accelerator", "algo.lm.", "env.num_envs", "algo.total_steps"))]
    small += ["fabric.accelerator=cpu", "env.num_envs=4", "algo.total_steps=1536", "algo.per_rank_batch_size=2",
              "algo.lm.hidden_size=128", "algo.lm.moe_intermediate_size=128", "algo.lm.vocab_size=64", "algo.lm.experts_held=[0,4]"]
    experts = chip_smoke.experts_phase(small, platform="cpu", out_dir=str(tmp_path), product=(512, 256, 128, 4))
    assert max(experts["gaps_to_ragged_dot_at_highest"].values()) < chip_smoke.THREE_PASS_BOUND
    assert experts["counters"]["moe/update_pairs_dropped"] == 0 and 0 < experts["counters"]["moe/update_tile_fill"] <= 1
    assert experts["counters"]["moe/update_grouped_product_passes"] == 0 and experts["kernel_calls_under_update_experts"] == 0


@pytest.mark.timeout(300)
def test_the_qwen3_next_phase_runs_on_cpu_at_small_widths(tmp_path):
    """Decoding through the three kinds of state against the chunked forward, then the
    sequence-policy loop on the `qwen3_next` trunk: the update's bounded dispatch drops nothing."""
    small = [o for o in chip_smoke.Q3N_OVERRIDES if not o.startswith(("fabric.accelerator", "algo.lm.", "env.num_envs", "algo.total_steps"))]
    small += ["fabric.accelerator=cpu", "env.num_envs=4", "algo.total_steps=1536", "algo.per_rank_batch_size=2"]
    widths = dict(chip_smoke.Q3N_PUBLISHED, vocab_size=64, hidden_size=32, moe_intermediate_size=16, shared_expert_intermediate_size=16,
                  num_attention_heads=4, head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
                  linear_value_head_dim=8, num_experts=32, num_experts_per_tok=4, experts_held=(0, 8))
    trunk = chip_smoke.qwen3_next_phase(small, platform="cpu", out_dir=str(tmp_path), widths=widths, batch=2, steps=70)
    assert max(trunk["decode_gaps_to_the_full_forward"].values()) < chip_smoke.DECODE_GAP_BOUND
    counters = trunk["counters"]
    assert counters["moe/update_pairs_dropped"] == 0 and counters["moe/rollout_pairs_dropped"] == 0
    assert counters["lin_attn/rollout_decode_kernel_share"] == 0  # off the chip every step takes the XLA form
    assert 0 < counters["moe/update_dispatch_fill"] <= 1


def test_the_deepseek_v3_phase_runs_on_cpu_at_small_widths(tmp_path):
    """Decoding in the absorbed form through the latent caches against the expanded forward, then
    the sequence-policy loop on the `deepseek_v3` trunk: the update's bounded dispatch drops nothing,
    and no decode step takes the latent-cache kernel off the chip."""
    small = [o for o in chip_smoke.DSV3_OVERRIDES if not o.startswith(("fabric.accelerator", "algo.lm.", "env.num_envs", "algo.total_steps"))]
    small += ["fabric.accelerator=cpu", "env.num_envs=4", "algo.total_steps=1536", "algo.per_rank_batch_size=2"]
    widths = dict(chip_smoke.DSV3_PUBLISHED, vocab_size=64, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
                  num_attention_heads=4, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16,
                  num_experts=32, num_experts_per_tok=4, experts_held=(0, 8))
    trunk = chip_smoke.deepseek_v3_phase(small, platform="cpu", out_dir=str(tmp_path), widths=widths, batch=2, steps=70)
    assert max(trunk["decode_gaps_to_the_full_forward"].values()) < chip_smoke.DECODE_GAP_BOUND
    assert trunk["latent_cache_bytes_per_sequence"] == 3 * 70 * (16 + 4) * 4
    counters = trunk["counters"]
    assert counters["moe/update_pairs_dropped"] == 0 and counters["moe/rollout_pairs_dropped"] == 0
    assert counters["mla/rollout_decode_kernel_share"] == 0  # off the chip every step takes the XLA form
    assert 0 < counters["moe/update_dispatch_fill"] <= 1


def test_script_refuses_to_pass_without_the_chip(tmp_path, monkeypatch, capsys):
    # `python chip_smoke.py` is sys.exit(main()): what main() raises is a non-zero exit
    monkeypatch.setattr(chip_smoke, "WORK_DIR", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "REPORT_DIR", str(tmp_path / "report"))
    with pytest.raises(AssertionError, match="'platform': 'cpu'"):  # names what it found
        chip_smoke.main()
    assert '"ok"' not in capsys.readouterr().out  # and prints no result


def test_last_line_is_the_verdict_and_nothing_else(tmp_path, monkeypatch, capsys):
    # the chip check reads the last line of stdout: {"ok", "device"} with
    # {"platform", "kind", "count"} and no other key; the rest goes to the line before
    (tmp_path / "t.jsonl").write_text("")
    phase = {"telemetry": str(tmp_path / "t.jsonl"), "checkpoint": "c", "gru_branch": "pallas (tpu_custom_call)"}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "jax": "j", "jaxlib": "l", "libtpu": "t", "cache_dir": "d"}
    monkeypatch.setattr(chip_smoke, "WORK_DIR", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "REPORT_DIR", str(tmp_path / "report"))
    monkeypatch.setattr(chip_smoke, "device_report", lambda platform: device)
    for name in ("kernel_phase", "train_phase", "serve_phase", "experts_phase", "qwen3_next_phase", "deepseek_v3_phase"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: phase)
    assert chip_smoke.main() == 0
    *_, full, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {"ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert full.startswith("[chip-smoke] result: ") and json.loads(full.split(": ", 1)[1])["claim"] is None
