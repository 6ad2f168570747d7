"""Operations one Dreamer-V3 gradient step needs, counted from the configuration's
shapes: matrix multiplications and convolutions only (2 per multiply-add), forward
plus backward, nothing recomputed. Layer norms, activations, the two-hot heads'
softmax and the optimizer's elementwise work are left out (under 1% at these widths).

XLA's own `cost_analysis()` counts a scan's body once, and the step is two scans (T
posterior steps, `horizon` imagination steps), so the benchmark counts per step here.

What has a backward pass: everything the world-model loss touches (encoder, RSSM
over T steps, decoder, reward and continue heads), the actor's pass over the imagined
latents and the critic's pass over them. What has none: the imagination roll-out
itself (the actor's gradient reaches its parameters only through that last pass, since
latents, actions and advantages are stop-gradient'ed), the reward/continue/critic
reads of the imagined latents inside the actor loss, and the target critic.
A layer that reads data (no gradient asked of its input) costs 2x its forward for
forward plus weight gradient; any other trained layer costs 3x."""

from __future__ import annotations

from perfbench.reference.dreamer_v3 import derived


def _stack(rows, d_in, units, layers, first_input_grad=True):
    """(forward, forward+backward) FLOPs of `layers` dense layers on `rows` rows."""
    fwd = total = 0.0
    for i in range(layers):
        f = 2.0 * rows * (d_in if i == 0 else units) * units
        fwd += f
        total += f * (3 if (i > 0 or first_input_grad) else 2)
    return fwd, total


def _head(rows, d_in, units, layers, d_out, first_input_grad=True):
    fwd, total = _stack(rows, d_in, units, layers, first_input_grad)
    f = 2.0 * rows * units * d_out
    return fwd + f, total + 3 * f


def parts(m) -> dict:
    """FLOPs of one gradient step by part of the train program."""
    d = derived(m)
    T, B, H = m["sequence_length"], m["batch_size"], m["horizon"]
    units, layers, mult = m["dense_units"], m["mlp_layers"], m["cnn_channels_multiplier"]
    R, hidden, A = m["recurrent_state_size"], m["hidden_size"], m["actions"]
    frames, N = T * B, T * B
    out = {}

    encoder = 0.0
    if m["cnn_keys"]:
        side, c_in = m["screen_size"], d["image_channels"]
        for i in range(d["stages"]):
            side //= 2
            c_out = (2**i) * mult
            f = 2.0 * frames * side * side * 16 * c_in * c_out
            encoder += f * (2 if i == 0 else 3)
            c_in = c_out
    if d["mlp_in"]:
        encoder += _stack(frames, d["mlp_in"], units, layers, first_input_grad=False)[1]
    out["encoder"] = encoder

    def rssm_step(rows, posterior):
        fwd = 2.0 * rows * (d["stoch"] + A) * units  # input projection
        fwd += 2.0 * rows * (units + R) * 3 * R  # GRU gates
        fwd += _head(rows, R, hidden, 1, d["stoch"])[0]  # prior
        if posterior:
            fwd += _head(rows, R + d["embed"], hidden, 1, d["stoch"])[0]
        return fwd

    out["rssm"] = 3 * T * rssm_step(B, posterior=True)

    decoder = 0.0
    if m["cnn_keys"]:
        decoder += 3 * 2.0 * frames * d["latent"] * d["top"] * d["spatial"] ** 2
        side, c_in = d["spatial"], d["top"]
        for i in range(d["stages"]):
            last = i == d["stages"] - 1
            c_out = d["image_channels"] if last else (2 ** (d["stages"] - 2 - i)) * mult
            side *= 2
            # every output pixel of a k4 s2 transposed conv reads 4 taps of every input channel
            decoder += 3 * 2.0 * frames * side * side * 4 * c_in * c_out
            c_in = c_out
    if m["mlp_decoder_keys"]:
        dims = sum(m["mlp_keys"][k] for k in m["mlp_decoder_keys"])
        decoder += _head(frames, d["latent"], units, layers, dims)[1]
    out["decoder"] = decoder
    out["heads"] = (
        _head(frames, d["latent"], units, layers, m["bins"])[1]
        + _head(frames, d["latent"], units, layers, 1)[1]
    )

    actor_fwd = _head(N, d["latent"], units, layers, A)[0]
    out["imagine"] = H * rssm_step(N, posterior=False) + (H + 1) * actor_fwd
    rows = (H + 1) * N
    out["actor_critic"] = (
        _head(rows, d["latent"], units, layers, m["bins"])[0]  # critic values of the latents
        + _head(rows, d["latent"], units, layers, m["bins"])[0]  # imagined rewards
        + _head(rows, d["latent"], units, layers, 1)[0]  # imagined continues
        + _head(rows, d["latent"], units, layers, A, first_input_grad=False)[1]  # actor, trained
        + _head(H * N, d["latent"], units, layers, m["bins"], first_input_grad=False)[1]  # critic, trained
        + _head(H * N, d["latent"], units, layers, m["bins"])[0]  # target critic
    )
    return out


def train_step_flops(m) -> float:
    return sum(parts(m).values())
