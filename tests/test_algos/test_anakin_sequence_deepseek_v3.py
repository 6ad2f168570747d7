"""The sequence flavour of the fused on-device PPO loop on its third trunk
(`algo.lm.model_type=deepseek_v3`): the CLI smoke of `exp=ppo_anakin_deepseek_v3` at toy widths
with telemetry on, what the fused program returns and names, the seam that picks the trunk, and
the other two trunks' programs, which a third trunk must leave as they were."""

import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.cli import run

TOY = [
    "exp=ppo_anakin_deepseek_v3",
    "dry_run=False",
    "fabric.accelerator=cpu",
    "fabric.devices=1",
    "metric.log_level=0",
    "checkpoint.save_last=False",
    "env.num_envs=4",
    "algo.rollout_steps=40",
    "algo.per_rank_batch_size=2",
    "env.tokens.prompt_min=3",
    "env.tokens.prompt_max=6",
    "algo.lm.hidden_size=16",
    "algo.lm.intermediate_size=24",
    "algo.lm.moe_intermediate_size=8",
    "algo.lm.qk_nope_head_dim=8",
    "algo.lm.qk_rope_head_dim=4",
    "algo.lm.v_head_dim=8",
    "algo.lm.kv_lora_rank=12",
    "algo.lm.vocab_size=32",
    "algo.lm.experts_held=[8,8]",
]

# sha256 of the lowered text of the other two trunks' toy fused programs (`_other_trunks_program`,
# matmul precision pinned to `highest`, jax 0.9.0), taken on the commit before this trunk (841ea4e)
OTHER_TRUNKS_SHA256 = {
    "ppo_anakin_lfm2": "f6c76c7bccb57de38bbc8e1816be491fde24303817b97fe8841962a412b36f70",
    # with the counter `lin_attn/decode_kernel_share` its step returns (0 off the chip); without it, the text of
    # before the counter byte for byte (2978b86e...)
    "ppo_anakin_qwen3_next": "9cb4d5b1aa728d37887b9694e0be15902d932d02898a9b77b8d921d67c5b35dc",
}


@pytest.mark.telemetry
@pytest.mark.timeout(300)
def test_cli_smoke_three_iterations_with_telemetry(tmp_path):
    jsonl = tmp_path / "telemetry.jsonl"
    run(TOY + [
        "algo.total_steps=480",  # three iterations: telemetry anchors after the first
        "algo.run_test=True",
        "metric.telemetry.enabled=true",
        "metric.telemetry.every=160",
        "metric.telemetry.compile_warmup_steps=0",
        f"metric.telemetry.jsonl_path={jsonl}",
        f"root_dir={tmp_path}/root",
        "run_name=smoke",
    ])
    events = [json.loads(line) for line in open(jsonl) if line.strip()]
    summary = next(e for e in events if e["event"] == "summary")
    assert summary["clean_exit"] is True and summary["total_steps"] == 320
    windows = [e for e in events if e["event"] == "window"]
    assert windows and all("anakin_step" in w["spans"] for w in windows)
    counters = windows[-1]["counters"]
    assert counters["moe/update_pairs_dropped"][1] == 0 and counters["moe/rollout_pairs_dropped"][1] == 0
    assert counters["moe/update_max_load"][1] / counters["moe/update_max_load"][0] >= 1.0
    # 80 tokens a gradient step: the dense form (no dispatch buffers)
    assert "moe/update_dispatch_fill" not in counters
    # the expert layers' counters, and the latent caches' own: off the chip no step takes the kernel
    assert all(name.startswith("moe/") for name in counters if name != "mla/rollout_decode_kernel_share")
    assert counters["mla/rollout_decode_kernel_share"][1] == 0
    assert not any(e["event"] == "health" and e.get("status") == "nonfinite" for e in events)


def _toy_program(overrides, extra=()):
    from types import SimpleNamespace

    from sheeprl_tpu.algos.ppo import anakin
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.jax import make_jax_env

    cfg = compose(list(overrides) + list(extra))
    envs = int(cfg.env.num_envs)
    env = make_jax_env(cfg, envs)
    policy, params = anakin.build_sequence_policy(cfg, env.spec.action.num_actions, jax.random.PRNGKey(0))
    tx = anakin._build_optimizer(cfg, 10, 2)
    fused, rollout_only, updates = anakin.make_anakin_program(
        policy, env, cfg, SimpleNamespace(world_size=1), tx, (32,), False, "tokens", envs)
    env_state, obs = jax.jit(env.reset)(jax.random.PRNGKey(1))
    stats = {"ep_return_sum": jnp.float32(0), "ep_length_sum": jnp.float32(0), "ep_count": jnp.float32(0),
             "losses": jnp.zeros((3,), jnp.float32)}
    args = (params, tx.init(params), env_state, obs, jax.random.PRNGKey(2), stats, np.float32(0.2), np.float32(0.0))
    return fused, args, updates, policy


@pytest.mark.timeout(300)
def test_the_fused_program_returns_its_record_and_counters_and_names_its_parts():
    # 8 sequences of 40 tokens, 4 a minibatch: 160 tokens a gradient step, the bounded dispatch
    fused, args, updates, policy = _toy_program(TOY, ["env.num_envs=8", "algo.per_rank_batch_size=4"])
    from sheeprl_tpu.models import deepseek_v3

    assert policy.trunk is deepseek_v3 and isinstance(policy.spec, deepseek_v3.DeepseekV3Spec)
    carry = policy.initial_carry(8)  # a latent cache a layer, nothing per head
    assert {k: v.shape for k, v in carry.items()} == {"t": (), **{f"layer_{i}": (8, 40, 12 + 4) for i in range(3)}}
    text = fused.lower(*args).as_text(debug_info=True)
    for scope in ("rollout", "update", "embed", "mla", "mla_attend", "router", "experts", "shared_expert", "dense_ffn",
                  "lm_head", "value_head", "gae", "ppo_loss", "optimizer"):
        assert re.search(rf'[/("]{scope}[/)]', text), scope  # on an op's name stack, plain or under jvp/transpose
    assert "callback" not in text and "outfeed" not in text  # nothing goes to the host inside the program
    first_params = jax.device_get(args[0])
    out = fused(*args)
    params, stats, extras = out[0], out[5], out[7]
    assert updates == 2 and float(stats["ep_count"]) == 8.0
    record, counters = extras["record"], extras["counters"]
    # two expert layers (the leading layer is dense), three experts a token
    assert record["traj"]["route_ids"].shape == (40, 8, 2, 3) and record["update_route_ids"].shape == (2, 4, 40, 2, 3)
    assert set(counters) == {
        f"rollout_{name}" for name in ("pairs_held", "max_load", "pairs_dropped", "mla/decode_kernel_share")} | {
        f"update_{name}" for name in ("pairs_held", "max_load", "pairs_dropped", "tile_fill", "grouped_product_passes", "dispatch_fill")}
    assert all(np.ndim(v) == 0 for v in counters.values()) and float(counters["update_pairs_dropped"]) == 0.0
    assert 0.0 < float(counters["update_dispatch_fill"]) <= 1.0
    moved = jax.tree_util.tree_map(lambda a, b: float(np.abs(np.asarray(a) - b).max()), params, first_params)
    op, ffn = moved["layer_1"]["op"], moved["layer_1"]["ffn"]
    assert min(op["wq"], op["w_kva"], op["kv_norm"], op["w_kvb"], op["wo"], ffn["router"], ffn["w1"], ffn["shared"]["w2"],
               moved["layer_0"]["ffn"]["w2"], moved["lm_head"]) > 0
    assert ffn["bias"] == 0.0  # a buffer: it chooses experts and is never trained


def test_the_policy_takes_the_trunk_its_configuration_names():
    from sheeprl_tpu.algos.ppo import anakin, sequence_policy
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.models import deepseek_v3

    assert sorted(sequence_policy.TRUNKS) == ["deepseek_v3", "kimi_linear", "laguna", "lfm2_moe", "qwen3_next"]
    policy, params = anakin.build_sequence_policy(compose(TOY), 32, jax.random.PRNGKey(0))
    assert policy.trunk is deepseek_v3 and policy.spec.routed_scaling_factor == 2.446 and policy.spec.max_seq_len == 40
    assert policy.spec.shared_expert and not policy.spec.shared_expert_gate and "shared_gate" not in params["layer_1"]["ffn"]
    with pytest.raises(ValueError, match="names no trunk"):
        anakin.build_sequence_policy(compose(TOY + ["algo.lm.model_type=deepseek_v2"]), 32, jax.random.PRNGKey(0))


def _other_trunks_program(exp):
    widths = {"ppo_anakin_lfm2": ["algo.lm.intermediate_size=24"],
              "ppo_anakin_qwen3_next": ["algo.lm.shared_expert_intermediate_size=8", "algo.lm.head_dim=8",
                                        "algo.lm.linear_key_head_dim=8", "algo.lm.linear_value_head_dim=8", "algo.lm.chunk_size=16",
                                        "algo.lm.layer_types=[linear_attention,full_attention]"]}[exp]
    return _toy_program([f"exp={exp}", "fabric.accelerator=cpu", "fabric.devices=1", "env.num_envs=8", "algo.rollout_steps=40",
                         "algo.per_rank_batch_size=4", "env.tokens.prompt_min=3", "env.tokens.prompt_max=6",
                         "algo.lm.hidden_size=16", "algo.lm.moe_intermediate_size=8", "algo.lm.vocab_size=32", *widths])


@pytest.mark.timeout(300)
@pytest.mark.parametrize("exp", sorted(OTHER_TRUNKS_SHA256))
def test_the_other_trunks_lowered_programs_are_unchanged(exp):
    """The routed scale and the ungated shared expert are properties a spec states: with the
    LFM2 and `qwen3_next` specs, which state neither, the fused program (rollout, bounded
    dispatch, update) lowers to the text it had before this trunk, byte for byte."""
    with jax.default_matmul_precision("highest"):
        fused, args, _, _ = _other_trunks_program(exp)
        text = fused.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == OTHER_TRUNKS_SHA256[exp]
