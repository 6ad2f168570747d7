"""The sequence flavour of the fused on-device PPO loop (`algos/ppo/anakin.py`): the CLI
smoke of `exp=ppo_anakin_lfm2` at toy widths with telemetry on, what the fused program
returns, and the MLP flavour's program, which the seam must leave as it was."""

import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.cli import run

TOY = [
    "exp=ppo_anakin_lfm2",
    "dry_run=False",
    "fabric.accelerator=cpu",
    "fabric.devices=1",
    "metric.log_level=0",
    "checkpoint.save_last=False",
    "env.num_envs=4",
    "algo.rollout_steps=16",
    "algo.per_rank_batch_size=2",
    "env.tokens.prompt_min=3",
    "env.tokens.prompt_max=6",
    "algo.lm.hidden_size=16",
    "algo.lm.intermediate_size=24",
    "algo.lm.moe_intermediate_size=8",
    "algo.lm.vocab_size=32",
    "algo.lm.experts_held=[2,4]",
]

# sha256 of `_aot_anakin_program()`'s lowered text (8 devices, telemetry-on program, matmul
# precision pinned to `highest`: an earlier test's `cli.run` leaves the process's default at
# `high`) on the commit before the sequence seam (c0e8ca3, jax 0.9.0): the MLP flavour's
# program, byte for byte
MLP_PROGRAM_SHA256 = "2ac808c826be748ad02f070f2606559241ebb6e6e0ce3eca3a09db90db3143b3"


@pytest.mark.telemetry
@pytest.mark.timeout(300)
def test_cli_smoke_two_iterations_with_telemetry(tmp_path):
    jsonl = tmp_path / "telemetry.jsonl"
    run(TOY + [
        "algo.total_steps=192",  # three iterations: telemetry anchors after the first
        "algo.run_test=True",
        "metric.telemetry.enabled=true",
        "metric.telemetry.every=64",
        "metric.telemetry.compile_warmup_steps=0",
        f"metric.telemetry.jsonl_path={jsonl}",
        f"root_dir={tmp_path}/root",
        "run_name=smoke",
    ])
    events = [json.loads(line) for line in open(jsonl) if line.strip()]
    start = next(e for e in events if e["event"] == "start")
    assert start["fingerprint"]["algo"] == "ppo_anakin" and start["fingerprint"]["env_backend"] == "jax"
    summary = next(e for e in events if e["event"] == "summary")
    assert summary["clean_exit"] is True and summary["total_steps"] == 128
    assert summary["train_units"] >= 4  # 1 epoch x 2 minibatches x 2 counted iterations
    windows = [e for e in events if e["event"] == "window"]
    assert windows and all(w["phases"]["rollout"] > 0 and w["phases"]["env"] == 0 for w in windows)
    assert all("anakin_step" in w["spans"] for w in windows)  # the host's span around the fused call
    counters = windows[-1]["counters"]
    pairs = counters["moe/rollout_pairs_held"][1] / counters["moe/rollout_pairs_held"][0]
    assert 0 < pairs <= 4 * 2 * 2  # pairs a decode step: 4 tokens x top-2 x 2 expert layers at most
    assert counters["moe/update_pairs_dropped"][1] == 0 and counters["moe/rollout_pairs_dropped"][1] == 0
    assert counters["moe/update_max_load"][1] / counters["moe/update_max_load"][0] >= 1.0
    assert not any(e["event"] == "health" and e.get("status") == "nonfinite" for e in events)


def _toy_program():
    from types import SimpleNamespace

    from sheeprl_tpu.algos.ppo import anakin
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.jax import make_jax_env

    cfg = compose(TOY)
    env = make_jax_env(cfg, 4)
    policy, params = anakin.build_sequence_policy(cfg, env.spec.action.num_actions, jax.random.PRNGKey(0))
    tx = anakin._build_optimizer(cfg, 10, 2)
    fused, rollout_only, updates = anakin.make_anakin_program(
        policy, env, cfg, SimpleNamespace(world_size=1), tx, (32,), False, "tokens", 4)
    env_state, obs = jax.jit(env.reset)(jax.random.PRNGKey(1))
    stats = {"ep_return_sum": jnp.float32(0), "ep_length_sum": jnp.float32(0), "ep_count": jnp.float32(0),
             "losses": jnp.zeros((3,), jnp.float32)}
    args = (params, tx.init(params), env_state, obs, jax.random.PRNGKey(2), stats, np.float32(0.2), np.float32(0.0))
    return fused, args, updates, policy


@pytest.mark.timeout(300)
def test_the_fused_program_returns_its_record_and_counters_and_names_its_parts():
    fused, args, updates, policy = _toy_program()
    text = fused.lower(*args).as_text(debug_info=True)
    for scope in ("rollout", "update", "embed", "short_conv", "attention", "router", "experts", "dense_ffn",
                  "lm_head", "value_head", "gae", "ppo_loss", "optimizer"):
        assert re.search(rf'[/("]{scope}[/)]', text), scope  # on an op's name stack, plain or under jvp/transpose
    assert "callback" not in text and "outfeed" not in text  # nothing goes to the host inside the program
    first_params = jax.device_get(args[0])
    out = fused(*args)
    params, stats, extras = out[0], out[5], out[7]
    assert updates == 2 and float(stats["ep_count"]) == 4.0  # one rollout is one episode of every env
    record, counters = extras["record"], extras["counters"]
    assert record["traj"]["tokens"].shape == (16, 4) and record["traj"]["route_ids"].shape == (16, 4, 2, 2)
    assert record["update_route_ids"].shape == (2, 2, 16, 2, 2) and record["losses"].shape == (2, 3)
    assert sorted(np.asarray(record["sequences"]).ravel()) == [0, 1, 2, 3]  # minibatches of whole sequences
    mask = np.asarray(record["traj"]["mask"])
    assert mask[:3].sum() == 0 and mask[6:].all()  # the prompt's steps are masked, the response's count
    assert set(counters) == {f"{phase}_{name}" for phase in ("rollout", "update")
                             for name in ("pairs_held", "max_load", "pairs_dropped")}
    assert all(np.ndim(v) == 0 for v in counters.values()) and float(counters["update_pairs_dropped"]) == 0.0
    moved = jax.tree_util.tree_map(lambda a, b: float(np.abs(np.asarray(a) - b).max()), params, first_params)
    assert moved["lm_head"] > 0 and moved["layer_1"]["ffn"]["w1"] > 0 and moved["layer_1"]["ffn"]["bias"] == 0.0


def test_a_counter_is_kept_under_the_space_its_trunk_names_or_the_expert_layers():
    from sheeprl_tpu.algos.ppo.anakin import _counter_name

    assert _counter_name("rollout_pairs_held") == "moe/rollout_pairs_held"
    assert _counter_name("update_grouped_product_passes") == "moe/update_grouped_product_passes"
    assert _counter_name("rollout_mla/decode_kernel_share") == "mla/rollout_decode_kernel_share"
    assert _counter_name("rollout_lin_attn/decode_kernel_share") == "lin_attn/rollout_decode_kernel_share"


def test_a_sequence_policy_needs_episodes_of_one_rollout():
    from types import SimpleNamespace

    from sheeprl_tpu.algos.ppo import anakin
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.envs.jax import make_jax_env

    cfg = compose(TOY + ["env.tokens.episode_steps=12"])
    env = make_jax_env(cfg, 4)
    policy, _ = anakin.build_sequence_policy(cfg, 32, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="every rollout begins at a reset"):
        anakin.make_anakin_program(policy, env, cfg, SimpleNamespace(world_size=1), None, (32,), False, "tokens", 4)


@pytest.mark.timeout(300)
def test_the_mlp_flavours_lowered_program_is_unchanged():
    from sheeprl_tpu.algos.ppo.anakin import _aot_anakin_program

    with jax.default_matmul_precision("highest"):
        fused, args = _aot_anakin_program()
        text = fused.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == MLP_PROGRAM_SHA256
