"""The token env of the on-device plane (`sheeprl_tpu/envs/jax/tokens.py`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.envs.jax import AutoReset, TokenCopy, VmapEnv, resolve_jax_env

STEPS = 20


def play(env, key, policy):
    """One episode: (observations, rewards, masks, dones, prompt, prompt length)."""
    state, obs = env.reset(key)
    prompt, length = np.asarray(state.prompt), int(state.prompt_len)
    rows = []
    for t in range(STEPS):
        action = policy(t, prompt, length)
        state, next_obs, reward, done, info = env.step(state, jnp.int32(action))
        rows.append((int(obs), action, float(reward), float(info["action_mask"]), bool(done)))
        obs = next_obs
    return rows, prompt, length


def test_the_episode_is_a_pure_function_of_the_key():
    env = TokenCopy(vocab_size=30, episode_steps=STEPS, prompt_min=3, prompt_max=6)
    policy = lambda t, prompt, length: (7 * t) % 30  # noqa: E731
    a, b = play(env, jax.random.PRNGKey(1), policy), play(env, jax.random.PRNGKey(1), policy)
    c = play(env, jax.random.PRNGKey(2), policy)
    assert a[0] == b[0] and a[2] == b[2] and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])
    lengths = {int(env.reset(jax.random.PRNGKey(k))[0].prompt_len) for k in range(40)}
    assert lengths == {3, 4, 5, 6}  # uniform on [prompt_min, prompt_max]


def test_prompt_then_response_with_reward_for_repeating_the_prompt():
    env = TokenCopy(vocab_size=30, episode_steps=STEPS, prompt_min=3, prompt_max=6)
    perfect = lambda t, prompt, length: int(prompt[(t - length) % length]) if t >= length else 29  # noqa: E731
    rows, prompt, length = play(env, jax.random.PRNGKey(3), perfect)
    for t, (obs, action, reward, mask, done) in enumerate(rows):
        if t < length:  # the prompt is fed one id a step; the action is ignored
            assert obs == prompt[t] and mask == 0.0 and reward == 0.0
        else:  # then the observation is the agent's last action
            assert obs == rows[t - 1][1] and mask == 1.0 and reward == 1.0
        assert done == (t == STEPS - 1)  # an episode is exactly `episode_steps` steps
    wrong = lambda t, prompt, length: (int(prompt[(t - length) % length]) + 1) % 30 if t >= length else 0  # noqa: E731
    assert sum(r[2] for r in play(env, jax.random.PRNGKey(3), wrong)[0]) == 0.0


def test_the_wrappers_carry_the_mask_and_start_a_fresh_episode_after_the_last_step():
    env = VmapEnv(AutoReset(TokenCopy(vocab_size=30, episode_steps=STEPS, prompt_min=3, prompt_max=6)), 4)
    assert env.spec.episode_steps == STEPS and env.spec.max_episode_steps is None and env.spec.obs_shape == ()
    state, obs = jax.jit(env.reset)(jax.random.PRNGKey(5))
    step = jax.jit(env.step)
    first_prompts = np.asarray(state.inner.prompt)
    for t in range(STEPS):
        state, obs, reward, done, info = step(state, jnp.zeros((4,), jnp.int32))
        assert info["action_mask"].shape == (4,) and bool(done.all()) == (t == STEPS - 1)
    assert np.all(np.asarray(state.inner.t) == 0) and not np.array_equal(np.asarray(state.inner.prompt), first_prompts)
    assert np.array_equal(np.asarray(obs), np.asarray(state.inner.prompt)[:, 0])  # the reset's observation
    assert float(info["episode_length"][0]) == STEPS


@pytest.mark.parametrize("policy", ["random", "copies", "copies_half"])
def test_the_benchmarks_plain_recomputation_is_the_envs_rule(policy):
    """`perfbench/reference/lfm2_moe.py::copy_env` (numpy, from the prompts and the actions)
    against a batch of episodes of the env itself: what decides `env_mismatch_count`."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "perfbench", "reference", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_reference_env", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    envs = 6
    env = VmapEnv(AutoReset(TokenCopy(vocab_size=9, episode_steps=STEPS, prompt_min=3, prompt_max=6)), envs)
    state, obs = jax.jit(env.reset)(jax.random.PRNGKey(8))
    prompt, length = np.asarray(state.inner.prompt), np.asarray(state.inner.prompt_len)
    step, rng, seen = jax.jit(env.step), np.random.default_rng(0), []
    for t in range(STEPS):
        actions = rng.integers(0, 9, envs)
        if policy != "random":
            right = prompt[np.arange(envs), np.mod(t - length, length)]
            actions = np.where((rng.random(envs) < 0.5) | (policy == "copies"), right, actions)
        state, next_obs, reward, done, info = step(state, jnp.asarray(actions, jnp.int32))
        seen.append({"tokens": obs, "actions": actions, "rewards": reward, "mask": info["action_mask"], "dones": done})
        obs = next_obs
    seen = {k: np.stack([np.asarray(row[k]) for row in seen], axis=1) for k in seen[0]}  # [E, T]
    again = ref.copy_env(prompt, length, seen["actions"])
    for name, value in again.items():
        assert np.array_equal(value, seen[name].astype(value.dtype)), name
    if policy == "copies":
        assert again["rewards"].sum() == again["mask"].sum() > 0


def test_the_factory_builds_it_from_the_env_group():
    from types import SimpleNamespace

    group = SimpleNamespace(vocab_size=16, episode_steps=12, prompt_min=2, prompt_max=4)
    env, limit = resolve_jax_env("token_copy", group)
    assert isinstance(env, TokenCopy) and limit is None and env.spec.action.num_actions == 16
    with pytest.raises(ValueError, match="env.tokens"):
        resolve_jax_env("token_copy")
    with pytest.raises(ValueError, match="prompt_max"):
        TokenCopy(vocab_size=16, episode_steps=4, prompt_min=2, prompt_max=4)
