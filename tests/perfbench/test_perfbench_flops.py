"""The shapes-FLOP function against a hand count at a tiny size."""

import pytest

from perfbench.harness import flops

M = dict(
    dense_units=8, mlp_layers=1, cnn_channels_multiplier=2, recurrent_state_size=16, hidden_size=8,
    stochastic_size=4, discrete_size=4, screen_size=8, cnn_keys={"rgb": 3}, mlp_keys={"reward": 1},
    mlp_decoder_keys=[], actions=5, bins=255, batch_size=2, sequence_length=3, horizon=2,
)


def test_hand_count_with_both_scans_counted_per_step():
    T, B, H = 3, 2, 2
    frames = N = T * B
    U, R, hid, S, A, latent, embed = 8, 16, 8, 16, 5, 32, 4 * 4 * 2 + 8
    p = flops.parts(M)
    # encoder: one conv stage 8x8x3 -> 4x4x2 (k4), data in: forward + weight gradient; the
    # reward's one dense layer likewise
    assert p["encoder"] == 2 * (2 * frames * 16 * 16 * 3 * 2) + 2 * (2 * frames * 1 * U)
    # one RSSM step on B rows: input projection, GRU gates, prior head, posterior head
    step = 2 * B * ((S + A) * U + (U + R) * 3 * R + R * hid + hid * S + (R + embed) * hid + hid * S)
    assert p["rssm"] == 3 * T * step  # every one of the T steps, forward and backward
    # decoder: dense to 4x4x2, one transposed conv to 8x8x3
    assert p["decoder"] == 3 * 2 * frames * latent * 32 + 3 * 2 * frames * 64 * 4 * 2 * 3
    head = lambda rows, out: 2 * rows * (latent * U + U * out)  # noqa: E731
    assert p["heads"] == 3 * head(frames, 255) + 3 * head(frames, 1)
    # imagination: every one of the H steps on N rows, forward only, plus H+1 actor passes
    imagine_step = 2 * N * ((S + A) * U + (U + R) * 3 * R + R * hid + hid * S)
    assert p["imagine"] == H * imagine_step + (H + 1) * head(N, A)
    rows = (H + 1) * N
    trained = lambda n, out: 2 * (2 * n * latent * U) + 3 * (2 * n * U * out)  # noqa: E731
    assert p["actor_critic"] == (
        2 * head(rows, 255) + head(rows, 1) + trained(rows, A) + trained(H * N, 255) + head(H * N, 255)
    )
    assert flops.train_step_flops(M) == sum(p.values())


def test_scans_scale_with_their_lengths():
    longer = dict(M, sequence_length=6)
    assert flops.parts(longer)["rssm"] == 2 * flops.parts(M)["rssm"]
    further = dict(M, horizon=4)
    base, more = flops.parts(M)["imagine"], flops.parts(further)["imagine"]
    assert more > 1.5 * base


def test_the_cells_counts(repo_root):
    import json
    import os

    counts = {}
    for name in ("dv3_XL_crafter", "dv3_L_doapp128"):
        with open(os.path.join(repo_root, "perfbench", "configs", name + ".json")) as fh:
            counts[name] = flops.parts(json.load(fh)["model"])
    xl, l = counts["dv3_XL_crafter"], counts["dv3_L_doapp128"]
    assert sum(xl.values()) == pytest.approx(9.07e12, rel=0.01)
    conv_share = (l["encoder"] + l["decoder"]) / sum(l.values())
    assert conv_share > (xl["encoder"] + xl["decoder"]) / sum(xl.values())
