"""The benchmark's seeded env."""

import numpy as np

from perfbench.harness.env import SeededPixelEnv


def rollout(seed, steps=40, **kw):
    env = SeededPixelEnv(seed=seed, **kw)
    obs, _ = env.reset()
    frames, rewards = [obs], []
    for _ in range(steps):
        obs, reward, terminated, truncated, _ = env.step(0)
        frames.append(obs)
        rewards.append(reward)
        if terminated:
            obs, _ = env.reset()
    return frames, rewards


def test_same_seed_same_frames_other_seed_other_frames():
    a, ra = rollout(2**31 + 5)
    b, rb = rollout(2**31 + 5)
    c, _ = rollout(2**31 + 6)
    assert all(np.array_equal(x["rgb"], y["rgb"]) for x, y in zip(a, b)) and ra == rb
    assert not np.array_equal(a[0]["rgb"], c[0]["rgb"])


def test_frames_vector_and_rewards_are_not_constant():
    frames, rewards = rollout(3, steps=400, vector_key="state", vector_dim=46, channels=1,
                              screen_size=128, image_key="frame", actions=16)
    first = frames[0]
    assert first["frame"].shape == (1, 128, 128) and first["frame"].dtype == np.uint8
    assert first["state"].shape == (46,) and first["state"].dtype == np.float32
    assert first["frame"].std() > 50  # noise, not a flat frame
    assert not np.array_equal(frames[1]["frame"], frames[2]["frame"])
    assert 0 < sum(rewards) < len(rewards)


def test_episodes_last_some_hundreds_of_steps():
    env = SeededPixelEnv(seed=1, episode_min=200, episode_max=600)
    lengths = []
    for _ in range(6):
        env.reset()
        n = 0
        while True:
            n += 1
            if env.step(0)[2]:
                break
        lengths.append(n)
    # the first one is short, so that the player's reset program compiles in prefill
    assert 16 <= lengths[0] <= 64
    assert all(200 <= n <= 600 for n in lengths[1:]) and len(set(lengths)) > 2
