"""The sequence flavour of the fused on-device PPO loop on its second trunk
(`algo.lm.model_type=qwen3_next`): the CLI smoke of `exp=ppo_anakin_qwen3_next` at toy
widths with telemetry on, what the fused program returns and names, and the seam that
picks the trunk. The LFM2 program's own tests are `test_anakin_sequence.py`'s, unchanged."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.cli import run

TOY = [
    "exp=ppo_anakin_qwen3_next",
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
    "algo.lm.moe_intermediate_size=8",
    "algo.lm.shared_expert_intermediate_size=8",
    "algo.lm.head_dim=8",
    "algo.lm.linear_key_head_dim=8",
    "algo.lm.linear_value_head_dim=8",
    "algo.lm.chunk_size=16",
    "algo.lm.layer_types=[linear_attention,full_attention]",
    "algo.lm.vocab_size=32",
    "algo.lm.experts_held=[8,8]",
]


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
    # 80 tokens a gradient step: the dense form (no dispatch buffers)
    assert "moe/update_dispatch_fill" not in counters
    # the expert layers' counters, and the matrix state's own: off the chip no step takes the kernel
    assert all(name.startswith("moe/") for name in counters if name != "lin_attn/rollout_decode_kernel_share")
    assert counters["lin_attn/rollout_decode_kernel_share"][1] == 0
    assert not any(e["event"] == "health" and e.get("status") == "nonfinite" for e in events)


def _toy_program(extra=()):
    from types import SimpleNamespace

    from sheeprl_tpu.algos.ppo import anakin
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.jax import make_jax_env

    cfg = compose(TOY + list(extra))
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
    fused, args, updates, policy = _toy_program(["env.num_envs=8", "algo.per_rank_batch_size=4"])
    from sheeprl_tpu.models import qwen3_next

    assert policy.trunk is qwen3_next and isinstance(policy.spec, qwen3_next.Qwen3NextSpec)
    text = fused.lower(*args).as_text(debug_info=True)
    for scope in ("rollout", "update", "embed", "linear_attention", "delta_rule", "attention", "router", "experts",
                  "shared_expert", "lm_head", "value_head", "gae", "ppo_loss", "optimizer"):
        assert re.search(rf'[/("]{scope}[/)]', text), scope  # on an op's name stack, plain or under jvp/transpose
    assert "callback" not in text and "outfeed" not in text  # nothing goes to the host inside the program
    first_params = jax.device_get(args[0])
    out = fused(*args)
    params, stats, extras = out[0], out[5], out[7]
    assert updates == 2 and float(stats["ep_count"]) == 8.0
    record, counters = extras["record"], extras["counters"]
    assert record["traj"]["route_ids"].shape == (40, 8, 2, 4) and record["update_route_ids"].shape == (2, 4, 40, 2, 4)
    assert set(counters) == {
        f"rollout_{name}" for name in ("pairs_held", "max_load", "pairs_dropped", "lin_attn/decode_kernel_share")} | {
        f"update_{name}" for name in ("pairs_held", "max_load", "pairs_dropped", "tile_fill", "grouped_product_passes", "dispatch_fill")}
    assert all(np.ndim(v) == 0 for v in counters.values()) and float(counters["update_pairs_dropped"]) == 0.0
    assert 0.0 < float(counters["update_dispatch_fill"]) <= 1.0
    moved = jax.tree_util.tree_map(lambda a, b: float(np.abs(np.asarray(a) - b).max()), params, first_params)
    op, ffn = moved["layer_0"]["op"], moved["layer_0"]["ffn"]
    assert min(op["A_log"], op["dt_bias"], op["w_conv"], op["norm"], op["w_qkvz"], ffn["shared_gate"], ffn["w1"],
               ffn["shared"]["w2"], moved["layer_1"]["op"]["q_norm"], moved["layer_1"]["op"]["wq"], moved["lm_head"]) > 0


def test_the_policy_takes_the_trunk_its_configuration_names():
    from sheeprl_tpu.algos.ppo import anakin
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.models import lfm2, qwen3_next

    policy, _ = anakin.build_sequence_policy(compose(TOY), 32, jax.random.PRNGKey(0))
    assert policy.trunk is qwen3_next
    default = compose(["exp=ppo_anakin_lfm2", "fabric.accelerator=cpu", "algo.lm.hidden_size=16", "algo.lm.vocab_size=32"])
    assert "model_type" not in default.algo.lm  # LFM2's configuration does not say: the default
    policy, _ = anakin.build_sequence_policy(default, 32, jax.random.PRNGKey(0))
    assert policy.trunk is lfm2 and isinstance(policy.spec, lfm2.LFM2Spec)
    with pytest.raises(ValueError, match="names no trunk"):
        anakin.build_sequence_policy(compose(TOY + ["algo.lm.model_type=mamba"]), 32, jax.random.PRNGKey(0))
