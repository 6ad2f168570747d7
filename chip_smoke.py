#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, six phases, through the entry points a user calls:

1. ``kernel``: the fused Pallas LayerNorm-GRU step, compiled, at the row counts
   the S run sends it (1 from the player's step, which runs on the chip since
   PR 28; 1024 in the train program's imagination; 16 as well, the posterior
   scan's rows, though since PR 32 that scan steps the XLA reference, whose
   bias carries the tap), value and gradient against ``ln_gru_step_reference``.
2. ``train``: ``sheeprl_tpu.cli.run`` on Dreamer-V3 at the S preset the repo
   ships (``exp=dreamer_v3_100k_ms_pacman``: dense 512, recurrent 512, 32x32
   latents, CNN multiplier 32, batch 16 x sequence 64, horizon 15, 64x64x3
   uint8 frames, float32) with the seeded pixel dummy env in place of ALE,
   which is not installed. Nothing about the model or the batch is cut; only
   the prefill, the run length, the replay capacity and logging are shortened,
   so the run prefills, compiles and takes ``GRAD_STEPS`` gradient steps.
3. ``serve``: ``sheeprl_tpu.cli.serve`` answers ``SESSIONS`` sessions from the
   checkpoint that run wrote, through the donated slot-step program.
4. ``experts``: the grouped expert products of the sequence-model policy
   (``ops/grouped_matmul.py``), compiled, at the LFM2 cell's shapes against
   ``lax.ragged_dot`` at ``highest``, value and both gradients; then
   ``sheeprl_tpu.cli.run`` on ``exp=ppo_anakin_lfm2`` at widths the kernels tile,
   whose update must hold the kernels under its ``experts`` scope, count three
   bf16 passes at the CLI's ``high`` and drop no pair.
5. ``qwen3_next``: the sequence policy's second trunk (``models/qwen3_next.py``)
   at Qwen3-Next-80B-A3B's published widths, one period of its layers: tokens
   decoded one a step through the three kinds of state against the chunked
   whole-sequence forward (the matrix state through the delta-rule kernel,
   ``ops/delta_rule_decode.py``); then ``sheeprl_tpu.cli.run`` on
   ``exp=ppo_anakin_qwen3_next`` at widths the kernels tile, whose update's
   bounded dispatch must drop no pair, fill its buffers by a share in (0, 1]
   and count three bf16 passes, whose every decode step must take the
   delta-rule kernel, and whose compiled program must write or copy no whole
   matrix state.
6. ``deepseek_v3``: the third trunk (``models/deepseek_v3.py``) at
   Moonlight-16B-A3B's published widths, the dense layer and two expert layers:
   tokens decoded one a step in the absorbed form through the latent caches
   (the latent-cache kernel, ``ops/latent_decode.py``) against the expanded
   whole-sequence forward; then ``sheeprl_tpu.cli.run`` on
   ``exp=ppo_anakin_deepseek_v3`` with experts of the published width 1408, whose
   grouped products must take the kernels at three passes (and not
   ``lax.ragged_dot``) and drop no pair, whose every decode step must take the
   latent-cache kernel, and whose compiled program must write or copy no whole
   latent cache.

Every check reads what the run itself recorded (its telemetry stream, its
checkpoint) or what JAX reports; a failed check raises, so any failed phase is a
non-zero exit. ``python chip_smoke.py`` takes no argument and demands the chip.
The phases are functions of their overrides and the expected platform so that
tier-1 (tests/test_chip_smoke.py) runs the same code at tiny widths with
``platform="cpu"``. Nothing here is a benchmark: compile seconds and the like
are printed as information about the start-up, not as metrics.

The last line of stdout is one JSON object with exactly two keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``; the
full result is the ``[chip-smoke] result:`` line before it and ``result.json``.
"""

from __future__ import annotations

import glob
import importlib.metadata
import json
import os
import re
import shutil
import sys
import time
from typing import Any, Dict, List, Sequence

REPO = os.path.dirname(os.path.abspath(__file__))
# run directories, the checkpoint and the lowered programs: large, stay on the machine
WORK_DIR = os.path.join(REPO, "chip_smoke_out")
# what is worth bringing back from the chip: the result and the two telemetry streams
REPORT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

GRAD_STEPS = 16
LEARNING_STARTS = 128  # >= the 64-step sequences the S batch samples
SESSIONS = 4

S_TRAIN_OVERRIDES = [
    "exp=dreamer_v3_100k_ms_pacman",
    # ALE is not installed: the seeded pixel dummy env, rgb keys only
    "env=dummy",
    "env.id=discrete_dummy",
    "env.capture_video=False",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.cnn_keys.decoder=[rgb]",
    "algo.mlp_keys.encoder=[]",
    "algo.mlp_keys.decoder=[]",
    "fabric.accelerator=tpu",
    "fabric.devices=1",
    # the only things shortened: prefill, run length, replay capacity, logging
    f"algo.learning_starts={LEARNING_STARTS}",
    f"algo.total_steps={LEARNING_STARTS + GRAD_STEPS - 1}",
    "buffer.size=4096",
    "metric.log_level=0",
]
# the fused PPO loop's sequence flavour at widths the grouped kernels tile (multiples of 128)
# with more than `lfm2.DENSE_TOKENS` tokens a gradient step, so that the update sorts its pairs
LM_OVERRIDES = [
    "exp=ppo_anakin_lfm2",
    "fabric.accelerator=tpu",
    "fabric.devices=1",
    "env.num_envs=16",
    "algo.rollout_steps=128",
    "algo.per_rank_batch_size=8",
    "algo.total_steps=6144",  # three iterations: telemetry anchors after the first
    "algo.lm.vocab_size=1024",
    "algo.lm.hidden_size=512",
    "algo.lm.intermediate_size=1024",
    "algo.lm.moe_intermediate_size=256",
    "algo.lm.num_attention_heads=8",
    "algo.lm.num_key_value_heads=2",
    "algo.lm.experts_held=[0,4]",
    "checkpoint.every=0",
    "checkpoint.save_last=False",
    "metric.log_level=0",
]
# the same loop on the `qwen3_next` trunk, at widths the grouped kernels tile, with a share
# (8 of 64 experts, 4 a token) whose dispatch bound is under tokens x k
Q3N_OVERRIDES = [
    "exp=ppo_anakin_qwen3_next",
    "fabric.accelerator=tpu",
    "fabric.devices=1",
    "env.num_envs=16",
    "algo.rollout_steps=128",
    "algo.per_rank_batch_size=8",
    "algo.total_steps=6144",
    "algo.lm.vocab_size=1024",
    "algo.lm.hidden_size=512",
    "algo.lm.moe_intermediate_size=256",
    "algo.lm.shared_expert_intermediate_size=256",
    "algo.lm.num_attention_heads=4",
    "algo.lm.num_key_value_heads=2",
    "algo.lm.head_dim=128",
    "algo.lm.linear_num_key_heads=2",
    "algo.lm.linear_num_value_heads=4",
    "algo.lm.linear_key_head_dim=128",
    "algo.lm.linear_value_head_dim=128",
    "algo.lm.num_experts=64",
    "algo.lm.experts_held=[0,8]",
    "checkpoint.every=0",
    "checkpoint.save_last=False",
    "metric.log_level=0",
]
# Qwen3-Next-80B-A3B's published widths (perfbench/configs/qwen3_next_80b_a3b_ep16.json), one
# period of its layers with 8 of the 512 experts held: what the decode-against-forward check runs
Q3N_PUBLISHED = dict(
    vocab_size=4096, hidden_size=2048, moe_intermediate_size=512, shared_expert_intermediate_size=512,
    num_attention_heads=16, num_key_value_heads=2, head_dim=256, linear_num_key_heads=16, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128, num_experts=512, num_experts_per_tok=10, experts_held=(0, 8),
    layer_types=("linear_attention", "linear_attention", "linear_attention", "full_attention"),
)
# the same loop on the `deepseek_v3` trunk: latent attention at the published head sizes and experts
# of the published width 1408 = 11 x 128 (8 of 64 held, 6 a token), which the grouped kernels take whole
DSV3_OVERRIDES = [
    "exp=ppo_anakin_deepseek_v3",
    "fabric.accelerator=tpu",
    "fabric.devices=1",
    "env.num_envs=16",
    "algo.rollout_steps=128",
    "algo.per_rank_batch_size=8",
    "algo.total_steps=6144",
    "algo.lm.vocab_size=1024",
    "algo.lm.hidden_size=512",
    "algo.lm.intermediate_size=1024",
    "algo.lm.moe_intermediate_size=1408",
    "algo.lm.num_attention_heads=4",
    "algo.lm.qk_nope_head_dim=128",
    "algo.lm.qk_rope_head_dim=64",
    "algo.lm.v_head_dim=128",
    "algo.lm.kv_lora_rank=512",
    "algo.lm.num_experts=64",
    "algo.lm.num_experts_per_tok=6",
    "algo.lm.experts_held=[0,8]",
    "checkpoint.every=0",
    "checkpoint.save_last=False",
    "metric.log_level=0",
]
# Moonlight-16B-A3B's published widths (perfbench/configs/moonlight_16b_a3b_ep8.json), the leading
# dense layer and two expert layers with 8 of the 64 experts held: what the decode-against-forward check runs
DSV3_PUBLISHED = dict(
    vocab_size=4096, hidden_size=2048, intermediate_size=11264, moe_intermediate_size=1408, num_attention_heads=16,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512, num_hidden_layers=3, first_k_dense_replace=1,
    num_experts=64, num_experts_per_tok=6, experts_held=(0, 8), n_shared_experts=2, routed_scaling_factor=2.446,
)
DECODE_GAP_BOUND = 2e-3  # of the logits' spread, at `high`: PERF.md section 2 has the cell's readings
# [M, K, N] of the LFM2 cell's w1/w3 product and its 8 held experts; the bound of
# tests/test_models/test_lfm2_grouped.py on three bf16 passes against float64
CELL_PRODUCT = (32768, 2048, 1792, 8)
THREE_PASS_BOUND = 2e-5
S_SERVE_OVERRIDES = [
    "serve.slots=4",
    f"serve.sessions={SESSIONS}",
    "serve.max_session_steps=64",
]


def _check(cond: bool, message: str) -> None:
    if not cond:
        raise AssertionError(message)


def _one(events: Sequence[Dict[str, Any]], kind: str) -> Dict[str, Any]:
    found = [e for e in events if e["event"] == kind]
    _check(len(found) == 1, f"expected one {kind!r} event, found {len(found)}")
    return found[0]


def device_report(platform: str) -> Dict[str, Any]:
    """What JAX found, checked against what was asked for. Raises before any
    phase runs when the default device is not ``platform``."""
    import jax
    import jaxlib

    from sheeprl_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    _check(
        found["platform"] == platform,
        f"chip_smoke needs platform {platform!r}; JAX found {found} "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
    )
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:  # a CPU-only install has none to name
        libtpu = None
    report = {
        **found,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "cache_dir": enable_compile_cache(),
    }
    print(
        f"[chip-smoke] platform={report['platform']} device_kind={report['kind']} "
        f"count={report['count']} jax={report['jax']} jaxlib={report['jaxlib']} "
        f"libtpu={report['libtpu']} cache_dir={report['cache_dir']}",
        flush=True,
    )
    return report


def kernel_phase(
    platform: str, rows: Sequence[int] = (1, 16, 1024), K: int = 1024, H: int = 512, act_rows: Sequence[int] = (1,)
) -> Dict[str, Any]:
    """The fused GRU step against the XLA reference, value and gradient, at the
    S cell (``[rows, K] x [K, 3H]``). Compiled by Mosaic on a TPU; the Pallas
    interpreter elsewhere. The kernel's dot is one bf16 pass (``DEFAULT``): the
    value is held to the reference on matmul operands rounded to bfloat16, which
    is what one pass computes at any row count (at one row XLA's own ``DEFAULT``
    product is no MXU pass, and read 3.6e-3 away on the chip, PR 28); the
    gradient, which the kernel takes through the reference's own math, to the
    reference's at the same precision, at the rows that are ever differentiated
    (``act_rows`` are the player's: forward only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.ops.gru import fused_ln_gru_step, ln_gru_step_reference, pallas_gru_applicable

    _check(pallas_gru_applicable(K, H), f"the S cell K={K} H={H} must take the fused kernel")
    interpret = platform != "tpu"
    out: Dict[str, Any] = {"compiled": not interpret, "rows": {}}
    for B in rows:
        ks = jax.random.split(jax.random.PRNGKey(B), 6)
        args = (
            jax.random.normal(ks[0], (B, K)),
            jax.random.normal(ks[1], (B, H)),
            jax.random.normal(ks[2], (K, 3 * H)) / np.sqrt(K),
            0.1 * jax.random.normal(ks[3], (3 * H,)),
            1.0 + 0.1 * jax.random.normal(ks[4], (3 * H,)),
            0.1 * jax.random.normal(ks[5], (3 * H,)),
        )

        def fused_loss(*a):
            return jnp.sum(jnp.square(fused_ln_gru_step(*a, interpret=interpret)))

        def reference_loss(*a):
            return jnp.sum(jnp.square(ln_gru_step_reference(*a)))

        with jax.default_matmul_precision("default"):
            value = jax.jit(lambda *a: fused_ln_gru_step(*a, interpret=interpret))(*args)
            xla_default = jax.jit(ln_gru_step_reference)(*args)
            grads = jax.jit(jax.grad(fused_loss, argnums=tuple(range(6))))(*args)
            ref_grads = jax.jit(jax.grad(reference_loss, argnums=tuple(range(6))))(*args)
        # the interpreter's dot is exact float32: nothing to round there
        one_pass = [
            a.astype(jnp.bfloat16).astype(jnp.float32) if i in (0, 2) and not interpret else a
            for i, a in enumerate(args)
        ]
        with jax.default_matmul_precision("highest"):
            reference = jax.jit(ln_gru_step_reference)(*one_pass)
        _check(value.shape == (B, H) and value.devices() == {jax.devices()[0]}, "kernel output misplaced")
        value_err = float(jnp.max(jnp.abs(value - reference)))
        grad_err = max(
            float(jnp.max(jnp.abs(g - r)) / (jnp.max(jnp.abs(r)) + 1e-9)) for g, r in zip(grads, ref_grads)
        )
        # h' is a convex mix of tanh and h: O(1) values, so 1e-4 absolute is
        # float32 reduction-order noise, far below one bf16 ulp of a wrong branch
        _check(value_err < 1e-4, f"fused GRU value off the reference at rows={B}: {value_err:.3e}")
        _check(
            grad_err < 1e-3 or B in act_rows, f"fused GRU gradient off the reference at rows={B}: {grad_err:.3e}"
        )
        out["rows"][str(B)] = {
            "value_max_abs_err": value_err,
            "grad_max_rel_err": grad_err,
            "xla_default_max_abs_err": float(jnp.max(jnp.abs(value - xla_default))),  # information
        }
    print(f"[chip-smoke] kernel: {json.dumps(out)}", flush=True)
    return out


def train_phase(overrides: Sequence[str], *, platform: str, grad_steps: int, out_dir: str) -> Dict[str, Any]:
    """``cli.run`` into ``out_dir`` with the telemetry stream on, then read the
    run back: where it ran, how many gradient steps it took, whether every loss
    of the last step is finite, what it compiled."""
    import jax
    import numpy as np

    import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.obs.jsonl import read_events

    players = []

    class SeenPlayer(dv3.PlayerDV3):  # the loop's own player, kept to ask where its carry lives
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            players.append(self)

    run_dir = os.path.join(out_dir, "train")
    ir_dir = os.path.join(out_dir, "ir")
    # the StableHLO of every program the run lowers, to name the GRU branch the
    # train program was compiled with (platform_dependent picks it at lowering)
    jax.config.update("jax_dump_ir_to", ir_dir)
    t0 = time.perf_counter()
    original, dv3.PlayerDV3 = dv3.PlayerDV3, SeenPlayer
    try:
        run(
            list(overrides)
            + [
                f"hydra.run.dir={run_dir}",
                "metric.telemetry.enabled=true",
                # one window per policy step: the last window is the last gradient step
                "metric.telemetry.every=1",
            ]
        )
    finally:
        dv3.PlayerDV3 = original
        jax.config.update("jax_dump_ir_to", None)
    wall = time.perf_counter() - t0

    (stream,) = glob.glob(os.path.join(run_dir, "version_*", "telemetry.jsonl"))
    events = read_events(stream)
    start, summary = _one(events, "start"), _one(events, "summary")
    _check(
        start["platform"] == platform,
        f"the train state was built on {start['platform']!r} ({start['device_kind']}), not {platform!r}",
    )
    _check(summary["clean_exit"] is True, "the training run did not exit cleanly")
    _check(
        summary["train_units"] == grad_steps,
        f"asked for {grad_steps} gradient steps, the run took {summary['train_units']}",
    )
    trained = [e for e in events if e["event"] == "window" and e["train_units"] > 0]
    losses = {k: v for k, v in trained[-1]["learning"]["stats"].items() if k.startswith("loss/")}
    _check(len(losses) >= 3, f"expected world-model, actor and critic losses, got {sorted(losses)}")
    _check(all(np.isfinite(v) for v in losses.values()), f"non-finite loss in the last step: {losses}")
    # the run's own non-finite-loss guard, as of its last window
    _check(summary["health"] == "ok", f"the run's loss guard ended on {summary['health']!r}")

    program = _one(events, "program")
    _check("error" not in program, f"program analysis failed: {program.get('error')}")
    hbm = trained[-1].get("hbm")
    if platform != "cpu":
        # the allocator's word for it: between steps EVERY mesh device holds at
        # least the donated train state (params, optimizer state, moments)
        state_bytes = int(program["memory"]["alias_bytes"])
        per_device = (hbm or {}).get("per_device") or [hbm]
        _check(
            state_bytes > 0 and all(d and d["bytes_in_use"] >= state_bytes for d in per_device),
            f"device memory in use {hbm} does not cover the {state_bytes} B train state on every device",
        )

        # the coupled loop on one accelerator device acts there, on the trainer's own
        # buffers: the player's carry is on the device and no act view was ever copied
        # (a decoupled smoke, if one is added, asserts the opposite on both counts)
        (player,) = players
        carry = {
            d.platform
            for x in (player.actions, player.recurrent_state, player.stochastic_state)
            for d in x.devices()
        }
        _check(carry == {platform}, f"the player's carry lives on {sorted(carry)}, not on {platform!r}")
        views = [e["counters"]["act_view_bytes"] for e in events if "act_view_bytes" in (e.get("counters") or {})]
        _check(
            len(views) > 0 and sum(total for _, total in views) == 0,
            f"act views copied {sum(total for _, total in views)} bytes in {len(views)} windows: "
            "the coupled loop should alias the trainer's buffers",
        )

    # the train program's posterior scan forms every RSSM kernel's gradient outside its
    # backward loop (counted when the program is traced, so in one window)
    counted = [e["counters"] for e in events if e.get("counters")]
    grads = {
        name: sum(c[f"rssm/weight_grad_bytes_{name}"][1] for c in counted if f"rssm/weight_grad_bytes_{name}" in c)
        for name in ("hoisted", "in_scan")
    }
    _check(
        grads["hoisted"] > 0 and grads["in_scan"] == 0,
        f"the posterior scan sums {grads['in_scan']} bytes of kernel gradients inside its loop and forms "
        f"{grads['hoisted']} outside: expected all of them outside",
    )

    ir_files = glob.glob(os.path.join(ir_dir, "*jit_train_step*compile*"))
    _check(len(ir_files) >= 1, f"no lowered train_step program under {ir_dir}")
    with open(max(ir_files, key=os.path.getsize)) as fh:
        gru_branch = "pallas (tpu_custom_call)" if "tpu_custom_call" in fh.read() else "xla"

    (checkpoint,) = glob.glob(os.path.join(run_dir, "version_*", "checkpoint", "*.ckpt"))
    result = {
        "telemetry": stream,
        "checkpoint": checkpoint,
        "device_kind": start["device_kind"],
        "grad_steps": summary["train_units"],
        "first_step_world_model_loss": trained[0]["learning"]["stats"]["loss/world_model"],
        "last_step_losses": losses,
        "gru_branch": gru_branch,
        "hbm_bytes_in_use": (hbm or {}).get("bytes_in_use"),
        "train_step_flops": program.get("flops"),
        # information about the start-up, not metrics
        "compile": summary["compile"],
        "train_step_analysis_compile_seconds": program.get("compile_seconds"),
        "wall_seconds": round(wall, 1),
    }
    print(f"[chip-smoke] train: {json.dumps(result)}", flush=True)
    return result


def serve_phase(checkpoint: str, overrides: Sequence[str], *, platform: str, sessions: int, out_dir: str) -> Dict[str, Any]:
    """``cli.serve`` from ``checkpoint`` (whose config.yaml names the model and
    the accelerator), then read the serving stream back."""
    from sheeprl_tpu.cli import serve
    from sheeprl_tpu.obs.jsonl import read_events

    serve_dir = os.path.join(out_dir, "serve")
    t0 = time.perf_counter()
    rc = serve([f"checkpoint_path={checkpoint}", f"serve.log_dir={serve_dir}", *overrides])
    wall = time.perf_counter() - t0
    _check(rc == 0, f"serve exited {rc}: a session failed or the server crashed")

    stream = os.path.join(serve_dir, "telemetry.jsonl")
    events = read_events(stream)
    start, summary = _one(events, "start"), _one(events, "summary")
    _check(
        start["platform"] == platform,
        f"the slot table was built on {start['platform']!r} ({start['device_kind']}), not {platform!r}",
    )
    _check(summary["clean_exit"] is True, "the server did not close cleanly")
    served = summary["serve"]
    _check(
        served["sessions_started"] == served["sessions_finished"] == sessions,
        f"asked for {sessions} sessions: {served['sessions_started']} started, "
        f"{served['sessions_finished']} finished",
    )
    _check(
        served["sessions_shed"] == served["sessions_drained"] == served["deadline_missed"] == 0,
        f"sessions were lost: {served}",
    )
    _check(summary["total_steps"] > 0 and served["state_bytes"] > 0, "the slot table served nothing")
    if platform != "cpu":
        _check(
            (summary.get("hbm_peak_bytes") or 0) >= served["state_bytes"],
            f"device memory peak {summary.get('hbm_peak_bytes')} does not cover the slot table",
        )
    result = {
        "telemetry": stream,
        "device_kind": start["device_kind"],
        "sessions_finished": served["sessions_finished"],
        "sessions_failed": served["sessions_started"] - served["sessions_finished"],
        "steps": summary["total_steps"],
        "slot_table_bytes": served["state_bytes"],
        "compile": summary["compile"],
        "wall_seconds": round(wall, 1),
    }
    print(f"[chip-smoke] serve: {json.dumps(result)}", flush=True)
    return result


def _sequence_run_counters(run_dir: str, platform: str):
    """(the stream, its summary, the mean of each counter) of a sequence-policy run that has
    ended: it ran on ``platform``, exited cleanly, held pairs in the update and dropped none."""
    from sheeprl_tpu.obs.jsonl import read_events

    (stream,) = glob.glob(os.path.join(run_dir, "version_*", "telemetry.jsonl"))
    events = read_events(stream)
    start, summary = _one(events, "start"), _one(events, "summary")
    _check(start["platform"] == platform, f"the policy was built on {start['platform']!r}, not {platform!r}")
    _check(summary["clean_exit"] is True, f"the sequence-policy run in {run_dir} did not exit cleanly")
    counted: Dict[str, list] = {}
    for event in events:
        for name, (count, total) in (event.get("counters") or {}).items():
            seen = counted.setdefault(name, [0, 0.0])
            seen[0], seen[1] = seen[0] + count, seen[1] + total
    mean = {name: total / count for name, (count, total) in counted.items() if count}
    _check(
        mean.get("moe/update_pairs_held", 0) > 0 and mean.get("moe/update_pairs_dropped") == 0
        and mean.get("moe/rollout_pairs_dropped") == 0,
        f"the run's expert counters: {mean}",
    )
    return stream, summary, mean


def _run_seeing_program(overrides: Sequence[str], run_dir: str, see: bool):
    """``cli.run`` of the sequence-policy loop with telemetry on -> (wall seconds, the compiled
    text of the loop's own fused program where ``see``, else None): the program is compiled
    once more to read it."""
    import sheeprl_tpu.algos.ppo.anakin as anakin
    from sheeprl_tpu.cli import run

    programs = []

    class SeenProgram:
        def __init__(self, fused):
            self.fused, self.hlo = fused, None

        def __call__(self, *args):
            if self.hlo is None and see:
                self.hlo = self.fused.lower(*args).compile().as_text()
            return self.fused(*args)

        def __getattr__(self, name):  # `lower`, for the telemetry's program analysis
            return getattr(self.fused, name)

    def seen_program(*args, **kwargs):
        fused, *rest = original(*args, **kwargs)
        programs.append(SeenProgram(fused))
        return (programs[-1], *rest)

    t0 = time.perf_counter()
    original, anakin.make_anakin_program = anakin.make_anakin_program, seen_program
    try:
        run(list(overrides) + [f"hydra.run.dir={run_dir}", "metric.telemetry.enabled=true", "metric.telemetry.every=1"])
    finally:
        anakin.make_anakin_program = original
    (program,) = programs
    return time.perf_counter() - t0, program.hlo


def whole_buffer_writes(hlo: str, shape: str) -> List[str]:
    """The instructions of a compiled program, outside fusions' bodies, whose result is a whole
    buffer of ``shape`` (``f32[64,512,576]``) and that write or copy it: a dynamic-update-slice,
    a copy, or a fusion named for either. A kernel's aliased output is none of these."""
    found = []
    for block in hlo.split("\n\n"):
        if block.lstrip().startswith(("%fused_", "%wrapped_")):
            continue
        for line in block.splitlines():
            op = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\(", line)
            if op and op.group(2).startswith(shape + "{") and (
                    op.group(3) in ("dynamic-update-slice", "copy", "copy-start")
                    or (op.group(3) == "fusion" and ("dynamic-update-slice" in op.group(1) or "copy" in op.group(1)))):
                found.append(line.strip()[:200])
    return found


def experts_phase(
    overrides: Sequence[str], *, platform: str, out_dir: str, product: Sequence[int] = CELL_PRODUCT
) -> Dict[str, Any]:
    """The grouped expert products, alone and inside the program that trains with them.
    Alone: ``lfm2._gmm_tpu`` (Mosaic on a TPU, Pallas' interpreter elsewhere) at three
    passes over ``product``'s ``[M, K, N]`` and groups, uneven and one of them empty,
    value, input gradient and weight gradient against ``lax.ragged_dot`` at ``highest``.
    Inside: ``cli.run`` of the sequence-policy PPO loop with telemetry on, then the run's
    own counters and, on a TPU, the compiled ``anakin_step``'s custom calls by name stack."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.models import lfm2

    m, k, n, groups = product
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    shares = jax.random.dirichlet(keys[0], jnp.full((groups - 1,), 4.0))
    sizes = jnp.concatenate([jnp.floor(shares * (m // 4)), jnp.zeros((1,))]).astype(jnp.int32)  # the last group empty
    valid = (jnp.arange(m) < sizes.sum())[:, None]
    rows = jnp.where(valid, jax.random.normal(keys[1], (m, k)), 0.0)
    weights = jax.random.normal(keys[2], (groups, k, n)) / jnp.sqrt(k)
    cotangent = jnp.where(valid, jax.random.normal(keys[3], (m, n)), 0.0)

    def through(product_fn):
        def masked(rows, weights):  # as `lfm2.grouped_matmul` masks: rows of no group read 0 both ways
            return jnp.where(valid, product_fn(jnp.where(valid, rows, 0.0), weights), 0.0)

        def loss(rows, weights):
            return jnp.sum(masked(rows, weights) * cotangent)

        return jax.jit(lambda r, w: (masked(r, w), *jax.grad(loss, argnums=(0, 1))(r, w)))

    kernels = through(lambda r, w: lfm2._gmm_tpu(r, w, sizes, 3))(rows, weights)
    with jax.default_matmul_precision("highest"):
        expected = through(lambda r, w: jax.lax.ragged_dot(r, w, sizes))(rows, weights)
    gaps = {
        name: float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        for name, got, want in zip(("value", "input_gradient", "weight_gradient"), kernels, expected)
    }
    _check(
        all(gap < THREE_PASS_BOUND for gap in gaps.values()),
        f"the grouped products at {list(product)} are off `ragged_dot` at `highest` by {gaps}, over {THREE_PASS_BOUND}",
    )
    _check(not bool(jnp.any(kernels[2][-1])), "an expert that no pair landed on has a weight gradient")

    run_dir = os.path.join(out_dir, "experts")
    wall, hlo = _run_seeing_program(overrides, run_dir, see=platform == "tpu")
    stream, summary, mean = _sequence_run_counters(run_dir, platform)
    _check(
        0 < mean.get("moe/update_tile_fill", 0) <= 1,
        f"`moe/update_tile_fill` reads {mean.get('moe/update_tile_fill')}: the update did not sort its pairs",
    )
    calls = []
    if platform == "tpu":
        # the CLI runs at `float32_matmul_precision=high`: three bf16 passes in the kernels
        _check(
            mean.get("moe/update_grouped_product_passes") == 3,
            f"`moe/update_grouped_product_passes` reads {mean.get('moe/update_grouped_product_passes')} under `high`, not 3",
        )
        calls = [line for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
        scoped = [line for line in calls if "/update/" in line and "experts" in line and "grouped_matmul" in line]
        _check(
            len(scoped) > 0 and len(scoped) == len(calls),
            f"{len(scoped)} of the program's {len(calls)} kernel calls sit under `update/.../experts`",
        )
    result = {
        "telemetry": stream,
        "product": list(product),
        "gaps_to_ragged_dot_at_highest": gaps,
        "counters": {name: mean[name] for name in sorted(mean) if name.startswith("moe/")},
        "kernel_calls_under_update_experts": len(calls),
        "compile": summary["compile"],
        "wall_seconds": round(wall, 1),
    }
    print(f"[chip-smoke] experts: {json.dumps(result)}", flush=True)
    return result


def _trunk_phase(name: str, trunk, spec, overrides: Sequence[str], *, platform: str, out_dir: str, batch: int,
                 whole_cache: str = "") -> Dict[str, Any]:
    """A trunk of the sequence policy on this platform. Alone, at ``spec``'s widths:
    ``spec.max_seq_len`` tokens decoded one a step through the trunk's state against its
    whole-sequence forward over the same tokens, logits and values. Inside: ``cli.run`` of
    the sequence-policy PPO loop with telemetry on, then the run's own counters: no pair
    dropped, the bounded dispatch's fill in (0, 1] and, on a TPU, three bf16 passes in the
    grouped kernels under `high` (0 would be `lax.ragged_dot`) and, where ``whole_cache``
    names a cache's shape, no instruction of the compiled program that writes or copies
    such a whole buffer (`whole_buffer_writes`)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("high"):  # what the CLI sets for a run
        params = jax.jit(lambda key: trunk.init_params(spec, key))(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, spec.max_seq_len), 0, spec.vocab_size)

        @jax.jit
        def decode(params, tokens):
            def one(carry, column):
                logits, value, carry, _, counters = trunk.step(params, spec, carry, column)
                return carry, (logits, value, counters["pairs_dropped"])

            _, (logits, values, dropped) = jax.lax.scan(one, trunk.init_carry(spec, batch), tokens.T)
            return jnp.swapaxes(logits, 0, 1), values.T, dropped.sum()

        logits, values, dropped = decode(params, tokens)
        full_logits, full_values, _, counters = jax.jit(lambda p, t: trunk.forward(p, spec, t))(params, tokens)
    spread = float(jnp.std(full_logits))
    gaps = {"logits": float(jnp.max(jnp.abs(logits - full_logits))) / spread,
            "values": float(jnp.max(jnp.abs(values - full_values))) / max(float(jnp.std(full_values)), 1e-30)}
    _check(
        all(gap < DECODE_GAP_BOUND for gap in gaps.values()),
        f"decoding through the state is off the full forward by {gaps} of the spread, over {DECODE_GAP_BOUND}",
    )
    _check(float(dropped) == 0 and float(counters["pairs_dropped"]) == 0, "a pair on a held expert was dropped")
    del params

    run_dir = os.path.join(out_dir, name)
    wall, hlo = _run_seeing_program(overrides, run_dir, see=platform == "tpu" and bool(whole_cache))
    stream, summary, mean = _sequence_run_counters(run_dir, platform)
    _check(
        0 < mean.get("moe/update_dispatch_fill", 0) <= 1,
        f"`moe/update_dispatch_fill` reads {mean.get('moe/update_dispatch_fill')}: not in (0, 1]",
    )
    if platform == "tpu":
        _check(
            mean.get("moe/update_grouped_product_passes") == 3,
            f"`moe/update_grouped_product_passes` reads {mean.get('moe/update_grouped_product_passes')} under `high`, not 3",
        )
        if whole_cache:
            writes = whole_buffer_writes(hlo, whole_cache)
            _check(not writes, f"the compiled program writes or copies a whole {whole_cache} cache: {writes}")
    result = {
        "telemetry": stream,
        "decode_gaps_to_the_full_forward": gaps,
        "counters": {name: mean[name] for name in sorted(mean) if name.startswith(("moe/", "mla/", "lin_attn/"))},
        "compile": summary["compile"],
        "wall_seconds": round(wall, 1),
    }
    print(f"[chip-smoke] {name}: {json.dumps(result)}", flush=True)
    return result


def qwen3_next_phase(
    overrides: Sequence[str], *, platform: str, out_dir: str, widths: Dict[str, Any] = Q3N_PUBLISHED,
    batch: int = 4, steps: int = 96
) -> Dict[str, Any]:
    """The `qwen3_next` trunk (`_trunk_phase`): decoding through its three kinds of state (KV
    cache, convolution columns, matrix state) against the chunked whole-sequence forward. In the
    run every linear-attention layer's decode step takes the delta-rule kernel on a TPU and none
    elsewhere (`lin_attn/rollout_decode_kernel_share` 1 or 0), and on a TPU no instruction of the
    program writes or copies a whole matrix state (the kernel writes it in place)."""
    from sheeprl_tpu.models import qwen3_next

    spec = qwen3_next.Qwen3NextSpec(**widths, max_seq_len=steps)
    given = dict(o.split("=", 1) for o in overrides if "=" in o)
    whole_state = "f32[{}]".format(",".join(given.get(key, "") for key in (
        "env.num_envs", "algo.lm.linear_num_value_heads", "algo.lm.linear_key_head_dim", "algo.lm.linear_value_head_dim")))
    result = _trunk_phase("qwen3_next", qwen3_next, spec, overrides, platform=platform, out_dir=out_dir, batch=batch,
                          whole_cache=whole_state)
    share = result["counters"].get("lin_attn/rollout_decode_kernel_share")
    _check(share == (1.0 if platform == "tpu" else 0.0),
           f"`lin_attn/rollout_decode_kernel_share` reads {share} on {platform}: the delta-rule kernel {'not ' if platform == 'tpu' else ''}taken")
    return result


def deepseek_v3_phase(
    overrides: Sequence[str], *, platform: str, out_dir: str, widths: Dict[str, Any] = DSV3_PUBLISHED,
    batch: int = 4, steps: int = 128
) -> Dict[str, Any]:
    """The `deepseek_v3` trunk (`_trunk_phase`): decoding in the absorbed form through the latent
    caches (``spec.cache_bytes_per_sequence`` of state a sequence; on a TPU the latent-cache
    kernel at ``steps`` a multiple of its chunk) against the expanded whole-sequence forward;
    the run's grouped products are at the published width 1408. In the run every layer's
    decode step takes the kernel on a TPU and none elsewhere (`mla/rollout_decode_kernel_share`
    1 or 0), and on a TPU no instruction of the program writes or copies a whole latent cache
    (the kernel writes its row in place)."""
    from sheeprl_tpu.models import deepseek_v3

    spec = deepseek_v3.DeepseekV3Spec(**widths, max_seq_len=steps)
    given = dict(o.split("=", 1) for o in overrides if "=" in o)
    width = int(given.get("algo.lm.kv_lora_rank", 0)) + int(given.get("algo.lm.qk_rope_head_dim", 0))
    whole_cache = f"f32[{given.get('env.num_envs')},{given.get('algo.rollout_steps')},{width}]"
    result = _trunk_phase("deepseek_v3", deepseek_v3, spec, overrides, platform=platform, out_dir=out_dir, batch=batch,
                          whole_cache=whole_cache)
    share = result["counters"].get("mla/rollout_decode_kernel_share")
    _check(share == (1.0 if platform == "tpu" else 0.0),
           f"`mla/rollout_decode_kernel_share` reads {share} on {platform}: the latent-cache kernel {'not ' if platform == 'tpu' else ''}taken")
    return {**result, "latent_cache_bytes_per_sequence": spec.cache_bytes_per_sequence}


def main() -> int:
    for fresh in (WORK_DIR, REPORT_DIR):  # no earlier run is read
        shutil.rmtree(fresh, ignore_errors=True)
        os.makedirs(fresh)
    device = device_report("tpu")
    kernel = kernel_phase("tpu")
    train = train_phase(S_TRAIN_OVERRIDES, platform="tpu", grad_steps=GRAD_STEPS, out_dir=WORK_DIR)
    _check(
        train["gru_branch"].startswith("pallas"),
        f"the S train program on one chip should carry the fused GRU, found {train['gru_branch']}",
    )
    served = serve_phase(
        train["checkpoint"], S_SERVE_OVERRIDES, platform="tpu", sessions=SESSIONS, out_dir=WORK_DIR
    )
    experts = experts_phase(LM_OVERRIDES, platform="tpu", out_dir=WORK_DIR)
    trunk = qwen3_next_phase(Q3N_OVERRIDES, platform="tpu", out_dir=WORK_DIR)
    latent = deepseek_v3_phase(DSV3_OVERRIDES, platform="tpu", out_dir=WORK_DIR)
    verdict = {"ok": True, "device": {k: device[k] for k in ("platform", "kind", "count")}}
    result = {
        **verdict,
        "versions": {k: device[k] for k in ("jax", "jaxlib", "libtpu")},
        "cache_dir": device["cache_dir"],
        "kernel": kernel,
        "train": train,
        "serve": served,
        "experts": experts,
        "qwen3_next": trunk,
        "deepseek_v3": latent,
        "claim": None,
    }
    streams = (("train", train), ("serve", served), ("experts", experts), ("qwen3_next", trunk), ("deepseek_v3", latent))
    for name, stream in ((name, phase["telemetry"]) for name, phase in streams):
        shutil.copy(stream, os.path.join(REPORT_DIR, f"{name}.telemetry.jsonl"))
    with open(os.path.join(REPORT_DIR, "result.json"), "w") as fh:
        json.dump(result, fh, indent=1)
    print(f"[chip-smoke] result: {json.dumps(result)}", flush=True)
    # the contract's last line: these two keys and no other
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
