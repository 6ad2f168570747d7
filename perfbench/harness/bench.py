"""One run of one cell: compose the cell's overrides, drive the program's normal
entry (`sheeprl_tpu.cli.run` -> `dreamer_v3.main` -> `run_dreamer`) with four
injected factories, time a window of whole train cycles, then compare what the
run's own first gradient steps produced with the plain reference.

What is injected, and why nothing else is: `player_cls` subclasses `PlayerDV3` to copy
one `get_actions` call (the first after the checked steps); `build_agent_fn` swaps the initial
weights for the benchmark's own (made from `--seed`, so the reference can make the
same ones without taking anything from the program); `trainer_factory` subclasses
`_InlineTrainer` to copy the inputs and results of the first three gradient steps
(set-up only; in the window it adds one attribute test to a train call);
`telemetry_factory` wraps the loop's telemetry object, whose `step()` is called at
the end of every iteration, which makes it the cycle clock, and whose `StopRun`
ends the loop once the window has closed (no checkpoint, no new switch).
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import sys
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHECK_STEPS = 3  # gradient steps the reference follows
MAX_PROGRAM_SEED = 2**31 - 8  # numpy and the env seeds (`seed + i`) stay inside 32 bits


class StopRun(Exception):
    """Raised from the telemetry tap at the cycle boundary that closes the window."""


def log(*parts: Any) -> None:
    print("[perfbench]", *parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------------
# data files
# ---------------------------------------------------------------------------------
def load_cell(workload: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, config_entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(root, manifest["paths"][0], "traffic", cell["traffic"] + ".json")) as fh:
        traffic = json.load(fh)
    return {"manifest": manifest, "cell": cell, "config": config, "traffic": traffic}


def metrics_for(manifest: dict, workload: str, kind: str) -> List[dict]:
    return [m for m in manifest[kind] if workload in m.get("workloads", [workload])]


def read_metric(name: str, run: "Run", root: str = ROOT) -> Optional[float]:
    """A per-layer metric is a reader of its own: `perfbench/metrics/<name>.py` with
    `read(run)`, which returns None where it finds nothing to read."""
    path = os.path.join(root, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("perfbench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(run)
    return None if value is None or not math.isfinite(value) else float(value)


def spec_from_cfg(cfg) -> dict:
    """The reference's `model` block, read back from the composed configuration: what
    the program will really run. At the cell's own size it has to equal the block in
    the configuration's file."""
    algo, wm, env = cfg.algo, cfg.algo.world_model, cfg.env
    wrapper = env.wrapper
    assumptions = {
        "one discrete action head": str(wrapper.get("_target_", "")).endswith("SeededPixelEnv"),
        "plain actor": str(algo.actor.cls).endswith("agent.Actor"),
        "coupled rssm": not wm.get("decoupled_rssm", False),
        "hafner init": bool(algo.hafner_initialization),
        "silu": algo.dense_act == "silu" and algo.cnn_act == "silu",
        "one width": len({algo.dense_units, wm.encoder.dense_units, wm.recurrent_model.dense_units,
                          wm.observation_model.dense_units, wm.reward_model.dense_units,
                          wm.discount_model.dense_units, algo.actor.dense_units,
                          algo.critic.dense_units}) == 1,
        "one depth": len({algo.mlp_layers, wm.encoder.mlp_layers, wm.observation_model.mlp_layers,
                          wm.reward_model.mlp_layers, wm.discount_model.mlp_layers,
                          algo.actor.mlp_layers, algo.critic.mlp_layers}) == 1,
        "one hidden size": wm.transition_model.hidden_size == wm.representation_model.hidden_size,
        "no weight decay": not any(
            o.optimizer.get("weight_decay") for o in (wm, algo.actor, algo.critic)
        ),
    }
    broken = [k for k, ok in assumptions.items() if not ok]
    if broken:
        raise ValueError(f"the plain reference does not cover this configuration: {broken}")
    channels = int(wrapper.channels)
    vector = {wrapper.vector_key: int(wrapper.vector_dim)} if wrapper.get("vector_key") else {}
    dims = {**vector, "reward": 1}
    optim = {
        group: {"lr": float(c.optimizer.lr), "eps": float(c.optimizer.eps), "clip": float(c.clip_gradients)}
        for group, c in (("world_model", wm), ("actor", algo.actor), ("critic", algo.critic))
    }
    return {
        "dense_units": int(algo.dense_units),
        "mlp_layers": int(algo.mlp_layers),
        "cnn_channels_multiplier": int(wm.encoder.cnn_channels_multiplier),
        "recurrent_state_size": int(wm.recurrent_model.recurrent_state_size),
        "hidden_size": int(wm.transition_model.hidden_size),
        "stochastic_size": int(wm.stochastic_size),
        "discrete_size": int(wm.discrete_size),
        "screen_size": int(env.screen_size),
        "cnn_keys": {k: channels for k in algo.cnn_keys.encoder},
        "mlp_keys": {k: dims[k] for k in algo.mlp_keys.encoder},
        "mlp_decoder_keys": list(algo.mlp_keys.decoder),
        "actions": int(wrapper.actions),
        "bins": int(wm.reward_model.bins),
        "batch_size": int(algo.per_rank_batch_size),
        "sequence_length": int(algo.per_rank_sequence_length),
        "horizon": int(algo.horizon),
        "gamma": float(algo.gamma),
        "lmbda": float(algo.lmbda),
        "unimix": float(algo.unimix),
        "layer_norm_eps": float(algo.layer_norm_eps),
        "kl_dynamic": float(wm.kl_dynamic),
        "kl_representation": float(wm.kl_representation),
        "kl_free_nats": float(wm.kl_free_nats),
        "kl_regularizer": float(wm.kl_regularizer),
        "continue_scale_factor": float(wm.continue_scale_factor),
        "ent_coef": float(algo.actor.ent_coef),
        "tau": float(algo.critic.tau),
        "target_freq": int(algo.critic.per_rank_target_network_update_freq),
        "moments": {
            "decay": float(algo.actor.moments.decay),
            "max": float(algo.actor.moments.max),
            "low": float(algo.actor.moments.percentile.low),
            "high": float(algo.actor.moments.percentile.high),
        },
        "optim": optim,
        "precision": str(cfg.fabric.precision),
        "matmul_precision": str(cfg.get("float32_matmul_precision", "high")),
    }


# ---------------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------------
class Run:
    """State of one run, and what the per-layer readers read."""

    def __init__(self, *, model: dict, seed: int, traffic: dict, seconds: float, trace: bool,
                 trace_dir: str):
        from sheeprl_tpu.obs.compile_monitor import compile_snapshot, install_compile_monitor

        from perfbench.harness.window import CycleWindow, cycle_of

        install_compile_monitor()
        self._compile_snapshot = compile_snapshot
        self.model, self.seed, self.trace, self.trace_dir = model, seed, trace, trace_dir
        self.traffic = traffic
        self.stamps: Dict[str, float] = {}
        self.trainer = self.sampler = self.device = None
        self.recorded: Dict[str, Any] = {"calls": [], "steps": 0}
        self.tracing = False
        self._trace_span = None
        iterations, gradient_steps = cycle_of(model["num_envs"], model["replay_ratio"])
        self.window = CycleWindow(
            cycle_iterations=iterations,
            gradient_steps_per_cycle=gradient_steps,
            env_steps_per_iteration=model["num_envs"],
            seconds=seconds,
            warmup_cycles=int(traffic.get("warmup_cycles", 10)),
            clock=time.perf_counter,
            sync=self._sync,
            compiles=lambda: int(self._compile_snapshot()["count"]),
            on_cycle=self._on_cycle if trace else None,
        )
        # filled after the window, for the per-layer readers
        self.phases: Optional[dict] = None
        self.capture: Optional[dict] = None
        self.compile_s = self.compiles_in_window = self.memory_peak_bytes = None
        self.flops = self.peaks = None

    def stamp(self, name: str) -> None:
        self.stamps.setdefault(name, time.perf_counter())

    def _sync(self) -> None:
        import jax

        jax.block_until_ready(self.trainer.sync_tree())

    # -- injected factories ------------------------------------------------------
    def build_agent(self, fabric, actions_dim, is_continuous, cfg, obs_space, key, agent_state=None):
        """The program's `build_agent`, handed the benchmark's weights (made on the
        device from `--seed` in one jitted call) as the state to start from, as a
        checkpoint would be. The tree is the program's checkpoint layout; a leaf of
        another shape stops the first train step, and tests/perfbench compares the two
        trees at the cells' own layouts."""
        import jax
        import numpy as np

        from sheeprl_tpu.algos.dreamer_v3.agent import build_agent

        from perfbench.reference import dreamer_v3 as ref

        self.stamp("composed")
        if tuple(actions_dim) != (self.model["actions"],) or is_continuous:
            raise ValueError(f"the env's actions {actions_dim} are not the configuration's")
        # the seed is an argument, not a constant of the program: one cache entry for all seeds
        weights = jax.jit(partial(ref.init_params, self.model))(np.int32(self.seed))
        agent, params = build_agent(fabric, actions_dim, is_continuous, cfg, obs_space, key, weights)
        self.device = jax.tree_util.tree_leaves(params)[0].devices().pop()
        self.stamp("agent")
        return agent, params

    def make_trainer(self, **kwargs):
        import jax

        from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import _InlineTrainer

        run = self

        class CheckedTrainer(_InlineTrainer):
            def train(self, data, cum_steps, train_key, want_full_state, want_metrics):
                run.stamp("prefilled")
                if run.recorded["steps"] < CHECK_STEPS:
                    return run._recorded_train(super().train, self, data, cum_steps, train_key,
                                               want_full_state, want_metrics)
                span = (
                    jax.profiler.TraceAnnotation("perfbench.train_call")
                    if run.tracing
                    else contextlib.nullcontext()
                )
                with span:
                    out = super().train(data, cum_steps, train_key, want_full_state, want_metrics)
                run.last_act_view = out[0]
                return out

        self.trainer = CheckedTrainer(**kwargs)
        return self.trainer

    def _recorded_train(self, train, trainer, data, cum_steps, train_key, *rest):
        """One of the train calls that make up the first CHECK_STEPS gradient steps:
        the loop's own call and feed, with host copies of what went in and came out."""
        import jax
        import numpy as np

        from perfbench.harness.check import GROUPS, LOSSES

        rec = self.recorded
        steps = int(jax.tree_util.tree_leaves(data)[0].shape[0])
        call = {
            "batch": {k: np.asarray(v) for k, v in jax.device_get(data).items()},
            "key": np.asarray(train_key),
            "cum": int(cum_steps),
            "steps": steps,
        }
        out = train(data, cum_steps, train_key, *rest)
        metrics = jax.device_get(trainer.last_metrics)
        call["losses"] = {g: float(metrics[LOSSES[g]]) for g in GROUPS}
        rec["calls"].append(call)
        rec["steps"] += steps
        if len(rec["calls"]) == 1:
            if steps != 1:
                raise RuntimeError(
                    f"the first train call ran {steps} gradient steps: the first gradient is read "
                    "from Adam's state after ONE step, so the traffic file has to make the first "
                    "call a single step (algo.per_rank_pretrain_steps)"
                )
            rec["first_mu"] = {g: jax.device_get(_adam_mu(trainer.opt_state[g])) for g in GROUPS}
        if rec["steps"] > CHECK_STEPS:
            raise RuntimeError(f"train calls do not add up to {CHECK_STEPS} steps: {rec['steps']}")
        if rec["steps"] == CHECK_STEPS:
            rec["params_after"] = jax.device_get({g: trainer.params[g] for g in GROUPS})
            self.stamp("checked")
            self.window.arm()
        self.last_act_view = out[0]
        return out

    def make_player(self, agent, num_envs, cnn_keys, mlp_keys):
        """The program's player; the first `get_actions` after the checked steps is
        copied (what went in, what came out) for the reference to follow."""
        import numpy as np

        from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3

        run = self

        class CheckedPlayer(PlayerDV3):
            def get_actions(self, params, obs, key, greedy=False):
                rec = run.recorded
                if rec["steps"] < CHECK_STEPS or "act" in rec:
                    return super().get_actions(params, obs, key, greedy)
                act = {"obs": {k: np.asarray(v) for k, v in obs.items()}, "key": np.asarray(key),
                       "a": np.asarray(self.actions), "h": np.asarray(self.recurrent_state),
                       "z": np.asarray(self.stochastic_state)}
                out = super().get_actions(params, obs, key, greedy)
                act.update(h_after=np.asarray(self.recurrent_state), z_after=np.asarray(self.stochastic_state),
                           actions=np.asarray(out[0]))
                rec["act"] = act
                return out

        return CheckedPlayer(agent, num_envs, cnn_keys, mlp_keys)

    def make_telemetry(self, fabric, cfg, log_dir, logger):
        from sheeprl_tpu.obs import build_telemetry

        self.log_dir = log_dir
        return TelemetryTap(build_telemetry(fabric, cfg, log_dir, logger=logger), self)

    # -- the traced cycles ---------------------------------------------------------
    def _on_cycle(self, index: int) -> None:
        import jax

        first = int(self.traffic.get("trace_start_cycle", 2))
        if index == first:
            self.trace_steps = [self.policy_step, None]
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
            self.tracing = True
            self._trace_span = jax.profiler.TraceAnnotation("perfbench.traced_window")
            self._trace_span.__enter__()
            self.traced_cycles = int(self.traffic.get("trace_cycles", 4))
        elif self.tracing and index == first + self.traced_cycles:
            self._sync()
            self._trace_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.tracing = False
            self.trace_steps[1] = self.policy_step


def _adam_mu(opt_state):
    """Adam's first moments out of an optax state (the chain of clip and adam)."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
             if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state in the optimizer's, found {len(found)}")
    return found[0].mu


class TelemetryTap:
    """The loop's telemetry object with three calls observed; the rest passes through."""

    def __init__(self, inner, run: Run):
        self._inner, self._run = inner, run

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def attach_sampler(self, sampler) -> None:
        self._run.sampler = sampler
        self._inner.attach_sampler(sampler)

    def observe_train(self, units, losses=None) -> None:
        self._run.window.on_train(units)
        self._inner.observe_train(units, losses)

    def step(self, policy_step: int) -> None:
        self._inner.step(policy_step)
        run = self._run
        run.policy_step = policy_step
        was_open = run.window.state == "open"
        closed = run.window.on_iteration_end()
        if run.window.state == "open" and not was_open:
            run.policy_step_open = policy_step
        if closed:
            run.policy_step_close = policy_step
            raise StopRun


def telemetry_phases(log_dir: str, lo: int, hi: int, skip=(0, 0)) -> Optional[dict]:
    """Sum the program's telemetry windows (one per cycle in a traced run) whose end
    lies inside the timed window, leaving out those that hold the profiler's start,
    its traced cycles or its stop (`skip`, in policy steps): they time the profiler."""
    path = os.path.join(log_dir, "telemetry.jsonl")
    if not os.path.exists(path):
        return None
    total = {"env": 0.0, "replay_wait": 0.0, "train": 0.0, "other": 0.0, "wall": 0.0,
             "train_calls": 0, "windows": 0}
    with open(path) as fh:
        for line in fh:
            event = json.loads(line)
            step = event.get("step")
            if event.get("event") != "window" or step is None:
                continue
            if not lo < step <= hi or skip[0] < step <= skip[1]:
                continue
            for key in ("env", "replay_wait", "train", "other"):
                total[key] += float(event["phases"].get(key, 0.0))
            total["wall"] += float(event["wall_seconds"])
            total["train_calls"] += 1 if event.get("train_units") else 0
            total["windows"] += 1
    return total if total["windows"] else None


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    platform: str = "tpu",
    extra_overrides: Sequence[str] = (),
    root: str = ROOT,
    t_start: Optional[float] = None,
) -> dict:
    """Drive one run and return the result object (the last line of stdout).
    `platform` and `extra_overrides` are for the tests, which accept the CPU and
    shrink the widths from inside; the command line offers neither."""
    t_start = time.perf_counter() if t_start is None else t_start
    data = load_cell(workload, root)
    cell, config, traffic, manifest = data["cell"], data["config"], data["traffic"], data["manifest"]
    os.environ["SHEEPRL_SEARCH_PATH"] = os.path.join(root, "perfbench", "sheeprl_configs")
    os.environ["SHEEPRL_JAX_CACHE_MIN_COMPILE_SECS"] = "0"  # every program persists

    import jax

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < int(cell["chips"]):
        raise SystemExit(
            f"[perfbench] {workload} needs {cell['chips']} {platform} chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})"
        )
    import numpy as np

    import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
    from sheeprl_tpu import cli
    from sheeprl_tpu.config import compose

    from perfbench.harness import capture as capture_mod
    from perfbench.harness import check, devices as device_table, flops
    from perfbench.harness.window import cycle_of

    program_seed = int(seed) % MAX_PROGRAM_SEED
    run_name = f"{workload}-{seed}-t{int(trace)}"
    overrides = [
        f"exp={config['exp']}",
        *config["overrides"],
        *traffic["overrides"],
        f"seed={program_seed}",
        f"fabric.accelerator={platform}",
        "root_dir=perfbench",
        f"run_name={run_name}",
    ]
    cfg = compose([*overrides, *extra_overrides])
    model = spec_from_cfg(cfg)
    if not extra_overrides:
        expected = config["model"]
        differing = {k: (model.get(k), expected.get(k)) for k in set(model) | set(expected)
                     if model.get(k) != expected.get(k)}
        if differing:
            raise SystemExit(f"[perfbench] the composed configuration differs from {cell['config']}'s file: {differing}")
    model = {**model, "num_envs": int(cfg.env.num_envs), "replay_ratio": float(cfg.algo.replay_ratio)}
    cycle_steps = cycle_of(model["num_envs"], model["replay_ratio"])[0] * model["num_envs"]
    if trace:
        overrides += [
            "metric.telemetry.enabled=true",
            "metric.telemetry.learning=false",
            "metric.telemetry.program_analysis=false",
            "metric.telemetry.diagnosis=false",
            "metric.telemetry.slo.enabled=false",
            "metric.telemetry.health_every=1000000000",
            f"metric.telemetry.every={cycle_steps}",
        ]
    overrides += list(extra_overrides)

    run = Run(
        model=model, seed=program_seed, traffic=traffic, seconds=seconds, trace=trace,
        trace_dir=os.path.join(root, "logs", "perfbench_trace", run_name),
    )
    run.stamp("imported")

    def bench_main(fabric, cfg_):
        return dv3.run_dreamer(
            fabric, cfg_, build_agent_fn=run.build_agent, player_cls=run.make_player,
            trainer_factory=run.make_trainer, telemetry_factory=run.make_telemetry,
        )

    original, dv3.main = dv3.main, bench_main
    try:
        cli.run(overrides)
        raise SystemExit("[perfbench] the run ended before its window closed: algo.total_steps is too small")
    except StopRun:
        pass
    finally:
        dv3.main = original
    window = run.window
    stats = run.device.memory_stats() or {}
    # XLA's program temporaries are not in `peak_bytes_in_use` on this runtime: they are a
    # standing reservation (`peak_bytes_reserved`) beside the buffers (PERF.md, PR 25)
    run.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))
    log("memory_stats:", json.dumps({k: int(v) for k, v in stats.items()}))
    run.compile_s = float(run._compile_snapshot()["seconds"])  # nothing compiled since the window opened...
    run.compiles_in_window = int(window.compiles_at_close - window.compiles_at_open)
    if run.compiles_in_window:
        raise SystemExit(f"[perfbench] {run.compiles_in_window} compilation(s) inside the window")  # ...or the run fails

    # the act view the player last got against the trainer's own parameters
    view = jax.device_get(run.last_act_view)
    own = jax.device_get({k: run.trainer.params[k] for k in view})
    run.recorded["act_view_gap"] = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) if np.size(a) else 0.0
        for a, b in zip(jax.tree_util.tree_leaves(view), jax.tree_util.tree_leaves(own))
    )
    del view, own

    # free the program's state before the reference runs
    run.sampler.close()
    trainer, run.trainer, run.sampler, run.last_act_view = run.trainer, None, None, None
    trainer.params = trainer.opt_state = trainer.moments_state = trainer.last_metrics = None
    del trainer
    gc.collect()

    metrics: Dict[str, dict] = {}
    setup_s = window.t_open - t_start
    if trace:
        start, stop = getattr(run, "trace_steps", None) or (0, 0)
        run.phases = telemetry_phases(
            run.log_dir, run.policy_step_open, run.policy_step_close,
            skip=(start, (stop or run.policy_step_close) + cycle_steps),
        )
        xplane = capture_mod.find_xplane(run.trace_dir)
        if xplane:
            loaded = capture_mod.load_xplane(xplane)
            spans = [s for s in loaded.host_spans if s[0] == "perfbench.traced_window"]
            run.capture = capture_mod.reduce(loaded, window=(spans[0][1], spans[0][2]) if spans else None)
        run.flops = flops.train_step_flops(model)
        run.peaks = device_table.peaks(run.device.device_kind) if platform == "tpu" else None
        for entry in metrics_for(manifest, workload, "per_layer"):
            value = read_metric(entry["name"], run, root)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        values = {"env_steps_per_s": window.env_steps_per_s, "setup_s": setup_s}
        for entry in metrics_for(manifest, workload, "end_to_end"):
            metrics[entry["name"]] = {"value": values[entry["name"]], "unit": entry["unit"]}

    t_check = time.perf_counter()
    reference = check.run_reference(model, program_seed, run.recorded["calls"], run.recorded.get("act"))
    compared = check.judge(check.numbers(model, run.recorded, reference), config["limits"])
    check_s = time.perf_counter() - t_check

    s = run.stamps
    cycle = sorted(window.cycle_seconds())
    log("set-up items (s):", json.dumps({
        "imports_and_compose": round(s["composed"] - t_start, 3),
        "agent_and_weights": round(s["agent"] - s["composed"], 3),
        "optimizer_env_prefill": round(s["prefilled"] - s["agent"], 3),
        "first_steps_compile_and_copies": round(s["checked"] - s["prefilled"], 3),
        "warm_up_cycles": round(window.t_open - s["checked"], 3),
        "setup_s": round(setup_s, 3),
        "compile_s": round(run.compile_s, 3),
    }))
    log("window:", json.dumps({
        "seconds": window.window_seconds, "cycles": window.cycles, "env_steps": window.env_steps,
        "train_calls": window.train_calls, "gradient_steps": window.gradient_steps,
        "env_steps_per_s": window.env_steps_per_s,
        "cycle_s_min_median_max": [cycle[0], cycle[len(cycle) // 2], cycle[-1]],
        "compiles_in_window": run.compiles_in_window, "reference_check_s": round(check_s, 3),
    }))
    judged = [c for c in compared.values() if c["limit"] is not None]
    result: Dict[str, Any] = {
        "correct": all(c["ok"] for c in judged),
        "attempted": len(judged),
        "failed": sum(not c["ok"] for c in judged),
        "metrics": metrics,
        "device": {
            "platform": run.device.platform,
            "kind": run.device.device_kind,
            "count": int(cell["chips"]),
            "memory_peak_bytes": run.memory_peak_bytes,
        },
    }
    if trace and run.capture:
        result["device"]["busy_s"] = run.capture["busy_s"]
        result["device"]["window_s"] = run.capture["window_s"]
        result["breakdown"] = {
            "device_ops": run.capture["device_ops"],
            "idle_gaps": run.capture["idle_gaps"],
        }
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in compared.items()}
    for name, c in compared.items():
        limit = "none (read, not compared)" if c["limit"] is None else f"{c['limit']:.6g}"
        log(f"compared {name}: {c['value']:.6g} limit {limit} {'ok' if c['ok'] else 'OVER'} ({c['where']})")
    return result


def main(argv: Optional[Sequence[str]] = None, t_start: Optional[float] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(prog="perfbench/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start=t_start)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
