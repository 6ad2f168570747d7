"""The sheeprl-compile cache-priming verb (cli.compile_warm).

XLA-specific operational surface with no reference analogue: remote TPU compiles
take minutes cold, so priming the persistent cache with the exact (program, shape)
keys of a real run is the difference between a hot and a cold pod launch."""

import pytest

from sheeprl_tpu.cli import compile_warm, one_train_phase_steps
from sheeprl_tpu.config import compose


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(monkeypatch):
    import os

    import jax

    from sheeprl_tpu.fleet.runner import _build_member_env
    from sheeprl_tpu.utils import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: JAX owns the directory, the program sets none
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", "/what/jax/read")
        assert compile_cache.enable_compile_cache() == "/what/jax/read"
        # unset: the one fixed in-checkout path
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == compile_cache.DEFAULT_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # fleet members inherit that cache: no per-fleet (time-stamped) directory
    env = _build_member_env({"compile_cache": True})
    assert "JAX_COMPILATION_CACHE_DIR" not in env and "SHEEPRL_JAX_CACHE" not in env
    assert compile_cache.cache_dir(env) == compile_cache.DEFAULT_CACHE_DIR


def test_one_train_phase_steps_on_policy():
    cfg = compose(["exp=ppo", "env.num_envs=4"])
    # one full rollout across the vectorized envs = one PPO update
    assert one_train_phase_steps(cfg) == cfg.algo.rollout_steps * 4


def test_one_train_phase_steps_off_policy():
    cfg = compose(["exp=sac", "env.num_envs=2", "algo.replay_ratio=0.5"])
    # learning_starts, then 1/ratio iterations for the first granted G-step
    assert one_train_phase_steps(cfg) == cfg.algo.learning_starts + (2 + 1) * 2 + 2


def test_one_train_phase_steps_dreamer():
    cfg = compose(["exp=dreamer_v3", "env.num_envs=1"])
    assert one_train_phase_steps(cfg) == cfg.algo.learning_starts + 2 + 1


def test_compile_warm_runs_one_update(tmp_path, monkeypatch, capsys):
    """End-to-end: a tiny PPO priming run completes, reports the cache, and
    leaves no run directory behind (logging fully off)."""
    monkeypatch.chdir(tmp_path)
    compile_warm(
        [
            "exp=ppo",
            "fabric.accelerator=cpu",
            "env.sync_env=True",
            "env.num_envs=2",
            "algo.rollout_steps=16",
            "algo.per_rank_batch_size=16",
            "buffer.memmap=False",
        ]
    )
    out = capsys.readouterr().out
    assert "[sheeprl-compile] priming ppo for 32 env steps" in out
    assert "[sheeprl-compile] done in" in out
    assert not (tmp_path / "logs").exists()


def test_compile_warm_dreamer_runs_one_train_phase(tmp_path, monkeypatch, capsys):
    """The off-policy branch end-to-end: a tiny DV3 priming run must reach its
    first gradient phase (learning_starts + replay-ratio credit) and leave no
    artifacts behind."""
    monkeypatch.chdir(tmp_path)
    compile_warm(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            "fabric.accelerator=cpu",
            "env.sync_env=True",
            "env.num_envs=1",
            "algo.learning_starts=8",
            "algo.replay_ratio=1",
            "algo.per_rank_batch_size=1",
            "algo.per_rank_sequence_length=1",
            "algo.horizon=4",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.discrete_size=4",
            "algo.world_model.stochastic_size=4",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=8",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.cnn_keys.decoder=[rgb]",
            "algo.mlp_keys.encoder=[state]",
            "algo.mlp_keys.decoder=[state]",
        ]
    )
    out = capsys.readouterr().out
    assert "[sheeprl-compile] priming dreamer_v3 for 11 env steps" in out
    assert "[sheeprl-compile] done in" in out
    assert not (tmp_path / "logs").exists()


def test_compile_warm_rejects_underivable_budget():
    cfg = compose(["exp=ppo"])
    del cfg.algo["rollout_steps"]
    with pytest.raises(ValueError, match="one-train-phase step budget"):
        one_train_phase_steps(cfg)
