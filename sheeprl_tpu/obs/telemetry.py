"""``RunTelemetry``: the per-run observability facade every training loop threads.

One instance is built per run (``build_telemetry``, from the ``metric.telemetry``
config group) and driven by four hooks, each a no-op when the feature is off:

- ``attach_sampler(sampler)`` — once, after the replay sampler exists; wires the
  prefetch pipeline gauges (``Time/prefetch_wait``, ``Buffer/pipeline_occupancy``,
  ``Buffer/pipeline_staleness``).
- ``observe_train(units, losses)`` — after each train round; accumulates the
  gradient-step count that scales the in-loop MFU and keeps the latest host/device
  losses for the periodic loss-finiteness health guard.
- ``observe_learn(stats)`` — after each train round, with the fused program's
  device-side ``Learn/*`` scalar block (``utils/learn_stats.py``): grad norms
  pre/post clip, clip fraction, update-to-param ratios, param/moment norms,
  policy entropy, value stats, TD-error quantiles, dreamer KL balance. Only
  REFERENCES are kept (a bounded stride-doubling reservoir per window); the
  host fetches them in ONE ``jax.device_get`` at window cadence, so the
  zero-steady-state-host-transfer contract survives.
- ``observe_episodes(returns, lengths)`` — whenever episodes finish; feeds the
  per-window episode-return distribution (count/mean/p10/p50/p90) the
  reward-plateau detector and ``compare``'s learning-curve extraction read.
- ``register_program(name, fn, args, units=...)`` — once (guard with
  ``wants_program``) with the live fused train program; lowers it from avals
  (no execution, donation-safe) to read XLA's own FLOPs/memory numbers.
- ``step(policy_step)`` — once per loop iteration; drives the windowed profiler
  capture and, every ``telemetry.every`` policy steps, emits one telemetry window:
  TensorBoard gauges (``Mem/*``, ``Compile/*``, ``Perf/mfu``, ``Time/prefetch_*``,
  ``Buffer/pipeline_*``, ``Perf/sps``) plus one JSONL ``window`` event.
- ``close(policy_step, clean_exit=...)`` — from the loop's ``finally`` path;
  flushes the final window, writes the ``summary`` event ``bench.py`` attaches
  to BENCH JSONs (``clean_exit=False`` on an exception unwind, so crashed and
  preempted attempts leave end-of-attempt state too), and stops an open
  profiler window.

Every ``window`` event carries a ``phases`` wall-time breakdown (env
interaction, fused on-device rollout, replay/prefetch wait, device train,
checkpoint write, logging, eval/test, unattributed remainder — see
``_PHASE_TIMERS``) and every event the
stream identity triple ``rank``/``attempt``/``seq`` (``obs/jsonl.py``). At
window cadence the in-loop diagnosis (``metric.telemetry.diagnosis``, default
on) runs the ``obs/diagnose.py`` detector catalog over the run's own history
and emits live ``health`` events with ``status=diagnosis``.

Telemetry is rank-0-only and fully decoupled from ``metric.log_level``: a bench
run with logging off still produces ``telemetry.jsonl``. With
``metric.telemetry.enabled=false`` (the default) and ``metric.profiler.mode`` not
``window``, :func:`build_telemetry` returns the :class:`NullTelemetry` no-op and
the loops behave byte-for-byte as before.
"""

from __future__ import annotations

import json
import os
import time
import warnings
from typing import Any, Dict, Mapping, Optional, Sequence

import numpy as np

from sheeprl_tpu.obs.compile_monitor import compile_snapshot, install_compile_monitor
from sheeprl_tpu.obs.jsonl import JsonlEventSink, spans_path
from sheeprl_tpu.obs.profiler import ProfilerWindow, resolve_profiler_config
from sheeprl_tpu.utils.mfu import peak_flops, program_analysis
from sheeprl_tpu.utils.timer import aggregate_spans, timer

# cumulative counter keys of a sampler telemetry snapshot (diffed per window)
_PREFETCH_COUNTERS = (
    "wait_seconds",
    "sample_calls",
    "units",
    "occupancy_sum",
    "staleness_sum",
    "empty_waits",
)

# phase attribution: named loop phases and the Time/* span each one harvests.
# Every window event carries a ``phases`` breakdown built from these (plus
# ``replay_wait``, carved out of the train span from the sampler's wait counter,
# and the ``other`` remainder) with the invariant
# sum(phases.values()) ≈ window wall_seconds.
_PHASE_TIMERS = {
    "env": "Time/env_interaction_time",
    # fused on-device env+act (the Anakin loops: the rollout half of ONE jitted
    # rollout+train program, split from `train` by a one-shot measured
    # rollout-only wall time — algos/ppo/anakin.py). Host-env loops simply
    # contribute zero here.
    "rollout": "Time/rollout_time",
    "train": "Time/train_time",
    "checkpoint": "Time/checkpoint_time",
    "logging": "Time/logging_time",
    "eval": "Time/test_time",
}

# window/health events the in-loop diagnosis keeps (bounded history)
_HISTORY_CAP = 512

# learn-stats reservoir: at most this many per-round device-stat dicts are held
# per window; past it the reservoir drops every other entry and doubles its
# sampling stride, so coverage stays spread over the whole window at O(1) memory
_LEARN_RESERVOIR = 64

# episode returns kept per window for the return distribution (count stays exact)
_EPISODE_RESERVOIR = 4096

# the Learn/* key grammar lives in utils/learn_stats.py (the producers' module);
# importing it keeps the filter and the gauges on the one shared definition
from sheeprl_tpu.utils.learn_stats import LEARN_PREFIX, learn_keys

# live (built, not yet closed) RunTelemetry instances of this process. The loops
# close their own instance on the normal path; an exception that unwinds past a
# loop leaves its instance here, and cli.run_algorithm's finally flushes it with
# clean_exit=False — so a crashed/preempted attempt still writes its summary
# event (the supervisor's cross-attempt history needs end-of-attempt state).
# WeakSet: instances abandoned by unit tests drop out on GC instead of being
# closed by an unrelated later run.
import weakref

_LIVE_TELEMETRY: "weakref.WeakSet[RunTelemetry]" = weakref.WeakSet()


def close_all_live_telemetry(clean_exit: bool = False) -> None:
    """Close every still-open RunTelemetry of this process (crash path; the
    normal path leaves nothing live). Each instance flushes at the last policy
    step its loop reported."""
    for t in list(_LIVE_TELEMETRY):
        try:
            t.close(t._last_step, clean_exit=clean_exit)
        except Exception:
            continue


class NullTelemetry:
    """The disabled facade: every hook is an attribute-cheap no-op so call sites
    never branch on whether telemetry is configured."""

    enabled = False

    def attach_sampler(self, sampler: Any) -> None:
        pass

    def attach_dataflow(self, provider: Any) -> None:
        pass

    def wants_program(self, name: str) -> bool:
        return False

    def register_program(self, name: str, fn: Any, args: Sequence[Any], **_: Any) -> None:
        pass

    def observe_train(self, units: int, losses: Any = None) -> None:
        pass

    def observe_learn(self, stats: Any = None) -> None:
        pass

    def observe_episodes(
        self, returns: Any = None, lengths: Any = None, count: Any = None
    ) -> None:
        pass

    def observe_env_restart(self, count: int = 1) -> None:
        pass

    def emit_event(self, event: str, step: Optional[int] = None, **fields: Any) -> bool:
        return False

    def step(self, policy_step: int) -> None:
        pass

    def close(self, policy_step: Optional[int] = None, clean_exit: bool = True) -> None:
        pass


def _rss_bytes() -> Optional[int]:
    """Current resident set size of this process (Linux /proc, cheap)."""
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        return None


def rss_peak_bytes() -> Optional[int]:
    """Peak RSS (ru_maxrss is KiB on Linux) — the CPU stand-in for peak HBM."""
    try:
        import resource

        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
    except Exception:
        return None


def device_memory(device: Any) -> Optional[Dict[str, int]]:
    """``{bytes_in_use, peak_bytes}`` from ``device.memory_stats()`` (TPU/GPU),
    or None on backends without allocator stats (host CPU)."""
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    out: Dict[str, int] = {}
    if "bytes_in_use" in stats:
        out["bytes_in_use"] = int(stats["bytes_in_use"])
    if "peak_bytes_in_use" in stats:
        out["peak_bytes"] = int(stats["peak_bytes_in_use"])
    for extra in ("largest_alloc_size", "bytes_limit", "num_allocs"):
        if extra in stats:
            out[extra] = int(stats[extra])
    return out or None


def mesh_device_memory(devices: Sequence[Any]) -> Optional[Dict[str, Any]]:
    """Allocator stats across EVERY local mesh device: the top-level keys
    report the worst device (max — one hot model-axis shard is what OOMs a
    run, not the mean) and ``per_device`` carries the full breakdown when more
    than one device reports, so ``watch``/``diagnose`` can see a model-axis
    imbalance instead of a single-device guess. None on backends without
    allocator stats (host CPU)."""
    per = []
    for d in devices:
        mem = device_memory(d)
        if mem:
            per.append({"id": getattr(d, "id", None), **mem})
    if not per:
        return None
    out: Dict[str, Any] = {}
    for key in ("bytes_in_use", "peak_bytes", "largest_alloc_size", "bytes_limit", "num_allocs"):
        vals = [p[key] for p in per if key in p]
        if vals:
            out[key] = max(vals)
    if len(per) > 1:
        out["per_device"] = per
    return out or None


def _nonfinite_losses(losses: Any) -> list:
    """Names of non-finite entries in the latest observed losses. Accepts the
    loops' two shapes: a metrics mapping (dreamer host metrics) or an array of
    stacked losses (sac-family ``mean_losses``). Device arrays sync here — the
    guard runs once per telemetry window, not on the hot path."""
    bad = []
    if isinstance(losses, Mapping):
        for k, v in losses.items():
            try:
                if not np.all(np.isfinite(np.asarray(v))):
                    bad.append(str(k))
            except TypeError:
                continue
        return bad
    arr = np.asarray(losses)
    if arr.ndim == 0:
        return [] if np.isfinite(arr) else ["loss"]
    flat = arr.reshape(-1)
    return [f"loss[{i}]" for i in range(flat.shape[0]) if not np.isfinite(flat[i])]


class RunTelemetry:
    """See the module docstring for the hook contract. Construct via
    :func:`build_telemetry` (which handles rank gating and the disabled path)."""

    def __init__(
        self,
        fabric: Any,
        cfg: Any,
        log_dir: Optional[str],
        logger: Any = None,
        *,
        enabled: bool = True,
        profiler_cfg: Optional[Mapping[str, Any]] = None,
        jsonl_path: Optional[str] = None,
        rank: Optional[int] = None,
        http: bool = False,
    ) -> None:
        metric_cfg = cfg.metric
        tcfg = dict(metric_cfg.get("telemetry") or {})
        self.enabled = bool(enabled)
        self._logger = logger
        self._log_dir = log_dir

        # stream identity: rank = the writing process's launch-topology position
        # (role streams override it), attempt = supervisor restart counter
        self._rank = int(rank if rank is not None else getattr(fabric, "global_rank", 0) or 0)
        self._attempt = int(tcfg.get("attempt") or 0)

        pcfg = dict(profiler_cfg or resolve_profiler_config(metric_cfg))
        base_dump = pcfg.get("dir") or (os.path.join(log_dir, "profiler") if log_dir else "profiler")
        # attempt-scoped capture dir: a supervised restart must never collide
        # with (or overwrite) a prior attempt's capture. The resolved path is
        # written back into pcfg so the start event records where the captures
        # actually land, and the profiler stop event repeats it — `profile`
        # enumerates captures from the stream alone.
        dump_dir = os.path.join(base_dump, f"attempt_{self._attempt}")
        pcfg["dir"] = dump_dir
        self.profiler = ProfilerWindow(
            pcfg.get("mode", "off"), pcfg.get("start_step", 0), pcfg.get("num_steps", 0), dump_dir
        )
        self._last_profile: Optional[Dict[str, Any]] = None

        self.every = int(tcfg.get("every") or metric_cfg.get("log_every") or 5000)
        self.health_every = max(1, int(tcfg.get("health_every") or 1))
        self.abort_on_nonfinite = bool(tcfg.get("abort_on_nonfinite", False))
        self.compile_warmup_steps = int(tcfg.get("compile_warmup_steps") or 0)
        self._program_analysis = bool(tcfg.get("program_analysis", True))
        self.diagnosis = bool(tcfg.get("diagnosis", True))
        self.learning = bool(tcfg.get("learning", True))

        # SLO plane (obs/slo.py + obs/alerts.py): objectives resolved from
        # metric.telemetry.slo + a per-run slo.yaml. On a pure training stream
        # the serving objectives never see their signal (structural no-ops);
        # the training floors (step_rate/mfu/episode_return) default to null
        # targets and only judge when declared per experiment.
        self._slo_evaluator: Any = None
        self._alert_engine: Any = None
        if self.enabled:
            try:
                from sheeprl_tpu.obs.alerts import AlertEngine
                from sheeprl_tpu.obs.slo import SloEvaluator, load_objectives

                objectives = load_objectives(tcfg.get("slo"), run_dir=log_dir)
            except Exception:
                objectives = []
            if objectives:
                self._slo_evaluator = SloEvaluator(objectives)
                self._alert_engine = AlertEngine(objectives)

        self._sink: Optional[JsonlEventSink] = None
        if self.enabled and bool(tcfg.get("jsonl", True)):
            path = jsonl_path or tcfg.get("jsonl_path") or (
                os.path.join(log_dir, "telemetry.jsonl") if log_dir else "telemetry.jsonl"
            )
            self._sink = JsonlEventSink(path, rank=self._rank, attempt=self._attempt)

        self._device = getattr(fabric, "device", None)
        # every LOCAL mesh device: Mem/hbm_* gauges report the max across them
        # and window events carry a per-device breakdown (a 2-D model-axis
        # mesh can be imbalanced; one device's stats would hide that)
        try:
            local_pid = getattr(self._device, "process_index", 0)
            self._devices = [
                d
                for d in (getattr(fabric, "devices", None) or [])
                if getattr(d, "process_index", 0) == local_pid
            ] or ([self._device] if self._device is not None else [])
        except Exception:
            self._devices = [self._device] if self._device is not None else []
        self._peak_flops = peak_flops(self._device) if self._device is not None else None
        self._world_size = int(getattr(fabric, "world_size", 1) or 1)

        # window state
        self._anchor_step: Optional[int] = None
        self._anchor_time = 0.0
        self._start_step: Optional[int] = None
        self._start_time = 0.0
        self._timer_last: Dict[str, tuple] = {}  # name -> (total, reset generation)
        # the timer's span ring and counters, read per window: where the last
        # window's reading stopped (a perf_counter time; the counters' totals)
        self._span_cursor = self._born = time.perf_counter()
        self._counter_last: Dict[str, tuple] = {}
        # "analysis" has no backing timer: register_program accounts its one-shot
        # program-introspection wall time there (it already shifts the open train
        # span past itself, so the window would otherwise leak it into `other`)
        self._window_phases: Dict[str, float] = {**{k: 0.0 for k in _PHASE_TIMERS}, "analysis": 0.0}
        self._total_phases: Dict[str, float] = {}
        self._total_wall_seconds = 0.0
        self._window_idx = 0
        self._window_train_units = 0
        self._total_train_units = 0
        self._total_train_seconds = 0.0
        self._last_losses: Any = None
        self._history: list = []  # window/health payloads for the in-loop diagnosis
        self._last_diagnosis_key: Any = None
        self._env_restarts = 0
        self._health_status = "unknown"
        self._sampler: Any = None
        self._prefetch_last: Optional[Dict[str, float]] = None
        self._prefetch_total: Dict[str, float] = {}
        self._programs: Dict[str, Dict[str, Any]] = {}
        self._mfu_flops_per_unit: Optional[float] = None
        self._compile_base = {"count": 0, "seconds": 0.0, "cache_hits": 0}
        self._compile_last = {"count": 0, "seconds": 0.0, "cache_hits": 0}
        self._last_mfu: Optional[float] = None
        self._peak_hbm = 0
        self._last_step: Optional[int] = None
        # learning-health state: per-window device-stat reservoir (references
        # only — fetched in one device_get at window cadence), per-window
        # episode-return sample, and run-level accumulators for the summary
        self._learn_window: list = []
        self._learn_stride = 1
        self._learn_seen = 0
        self._learn_rounds_total = 0
        self._learn_run_sums: Dict[str, float] = {}
        self._learn_run_counts: Dict[str, int] = {}
        self._learn_run_max: Dict[str, float] = {}
        self._last_learning: Optional[Dict[str, Any]] = None
        self._ep_returns: list = []
        self._ep_lengths: list = []
        self._ep_count_window = 0
        self._ep_count_total = 0
        self._ep_return_total = 0.0
        self._dataflow: Any = None  # attach_dataflow provider (experience plane)
        self._last_dataflow: Optional[Dict[str, Any]] = None
        # opt-in Prometheus endpoint (metric.telemetry.http_port): serves the
        # SAME gauges the window emit aggregates — no second bookkeeping path.
        # Only the primary facade binds it (`http=`): per-role streams of a gang
        # are separate processes that would race one configured port.
        self.metrics_endpoint = None
        if self.enabled and http:
            from sheeprl_tpu.obs.metrics_http import build_endpoint

            labels = {}
            run_name = getattr(cfg, "run_name", None)
            if run_name:
                labels["run"] = str(run_name)
            self.metrics_endpoint = build_endpoint(tcfg, labels=labels or None)
        _LIVE_TELEMETRY.add(self)

        if self.enabled:
            install_compile_monitor()
            self._compile_base = compile_snapshot()
            self._compile_last = dict(self._compile_base)
            dev = self._device
            # the run fingerprint makes this stream comparable-by-construction:
            # `compare`/`bench-diff` refuse-or-warn on mismatched fingerprints
            # instead of silently diffing different experiments (obs/fingerprint.py)
            from sheeprl_tpu.obs.fingerprint import run_fingerprint

            try:
                fingerprint: Optional[Dict[str, Any]] = run_fingerprint(cfg, fabric)
            except Exception:
                fingerprint = None
            from sheeprl_tpu.obs.schema import SCHEMA_VERSION

            start_event: Dict[str, Any] = dict(
                schema=SCHEMA_VERSION,
                platform=getattr(dev, "platform", None),
                device_kind=getattr(dev, "device_kind", None),
                world_size=self._world_size,
                peak_flops=self._peak_flops,
                every=self.every,
                compile_warmup_steps=self.compile_warmup_steps,
                profiler=dict(pcfg),
                fingerprint=fingerprint,
            )
            # the in-loop diagnosis needs the start event too (the recompile
            # detector reads compile_warmup_steps from it), sink or no sink
            self._append_history("start", start_event)
            if self._sink is not None:
                self._sink.emit("start", step=None, **start_event)

    # -- wiring ------------------------------------------------------------------

    def attach_sampler(self, sampler: Any) -> None:
        """Wire the replay sampler's pipeline gauges (any object exposing
        ``telemetry_snapshot()``; others are ignored)."""
        if self.enabled and hasattr(sampler, "telemetry_snapshot"):
            self._sampler = sampler
            self._prefetch_last = None

    def attach_dataflow(self, provider: Any) -> None:
        """Wire the experience-plane dataflow view (any object exposing
        ``dataflow_snapshot()`` — ``data/service.py``'s :class:`ActorDataflow` /
        :class:`LearnerDataflow`). Every window/summary event then carries a
        ``dataflow`` block (weight version/lag, sampled-row ages, ingest
        latency, queue depth) and the ``Service/*`` gauges light up."""
        if self.enabled and hasattr(provider, "dataflow_snapshot"):
            self._dataflow = provider

    def wants_program(self, name: str) -> bool:
        """Cheap per-iteration guard: True until ``name`` has been registered."""
        return self.enabled and self._program_analysis and name not in self._programs

    def register_program(
        self,
        name: str,
        fn: Any,
        args: Sequence[Any],
        kwargs: Optional[Mapping[str, Any]] = None,
        *,
        units: int = 1,
    ) -> None:
        """Introspect a live jitted program once: lower from avals (no execution,
        donation-safe), read XLA's FLOPs / bytes-accessed / memory_analysis, and
        emit a ``program`` event. ``units`` is how many logical gradient steps one
        call performs (a ``[G, ...]``-scanned program registers units=G) so MFU
        accounting is per gradient step regardless of fusion shape. The first
        registered program with FLOPs drives ``Perf/mfu``."""
        if not self.wants_program(name):
            return
        # record before analyzing: a failing analysis must not retry every round
        info: Dict[str, Any] = {"units": int(max(units, 1))}
        self._programs[name] = info
        # The memory_analysis() half needs a backend compile. The loop's first
        # real call just compiled the same HLO, so with the persistent compile
        # cache on (cli._setup_xla_env turns it on) the AOT compile is a cache
        # hit; a caller that never enabled it would pay a second cold compile
        # of the train program on an accelerator, so only the CPU backend
        # compiles then — FLOPs still come from the pre-compile lowering.
        import jax

        do_compile = bool(jax.config.jax_compilation_cache_dir) or (
            getattr(self._device, "platform", "cpu") == "cpu"
        )
        t0 = time.perf_counter()
        compiles_before = compile_snapshot()
        try:
            analysis = program_analysis(fn, args, kwargs, compile=do_compile)
        except Exception as exc:
            info["error"] = repr(exc)[:300]
            warnings.warn(f"telemetry: program analysis of {name!r} failed: {exc!r}")
            if self._sink is not None:
                self._sink.emit("program", name=name, error=info["error"])
            return
        finally:
            # the analysis must not pollute the run's own gauges: shift the open
            # Time/train_time span (the loops register inside it) past the
            # analysis, and credit its compile events out of the Compile/* base
            spent = time.perf_counter() - t0
            self._window_phases["analysis"] += spent
            span = timer.timers.get("Time/train_time")
            if span is not None and span._start is not None:
                span._start += spent
            compiles_after = compile_snapshot()
            for key in ("count", "seconds", "cache_hits"):
                own = compiles_after[key] - compiles_before[key]
                self._compile_base[key] += own
                self._compile_last[key] += own
        info.update(analysis)
        flops = analysis.get("flops")
        if flops:
            info["flops_per_unit"] = float(flops) / info["units"]
            if self._mfu_flops_per_unit is None:
                self._mfu_flops_per_unit = info["flops_per_unit"]
        if self._sink is not None:
            self._sink.emit("program", name=name, **info)

    # -- per-iteration hooks -----------------------------------------------------

    def observe_train(self, units: int, losses: Any = None) -> None:
        """Account ``units`` gradient steps for this window's MFU and keep the
        latest losses for the health guard (device arrays are fine — they are
        only synced at window boundaries)."""
        if not self.enabled:
            return
        self._window_train_units += int(units)
        self._total_train_units += int(units)
        if losses is not None:
            self._last_losses = losses

    def observe_learn(self, stats: Any = None) -> None:
        """Keep this train round's ``Learn/*`` device-stat block (references
        only — no sync here; see the module docstring). Accepts either a pure
        learn dict or a mixed metrics mapping (the dreamer family's) and keeps
        the ``Learn/``-prefixed subset, prefix stripped."""
        if not self.enabled or not self.learning or not isinstance(stats, Mapping):
            return
        learn = {k[len(LEARN_PREFIX) :]: v for k, v in learn_keys(stats).items()}
        if not learn:
            return
        self._learn_seen += 1
        self._learn_rounds_total += 1
        if (self._learn_seen - 1) % self._learn_stride:
            return
        self._learn_window.append(learn)
        if len(self._learn_window) >= _LEARN_RESERVOIR:
            # stride-doubling decimation: coverage stays spread across the
            # whole window instead of biasing to its head or tail
            self._learn_window = self._learn_window[::2]
            self._learn_stride *= 2

    def observe_episodes(
        self, returns: Any = None, lengths: Any = None, count: Optional[int] = None
    ) -> None:
        """Account finished episodes: exact counts + return sums, plus a bounded
        per-window return sample for the p10/p50/p90 distribution. ``count``
        overrides the episode count when the caller aggregates on device and
        only ships a batch mean (the Anakin loops: one sample, exact count)."""
        if not self.enabled or not self.learning or returns is None:
            return
        r = np.asarray(returns, dtype=np.float64).reshape(-1)
        if r.size == 0:
            return
        n = int(count) if count is not None else int(r.size)
        self._ep_count_window += n
        self._ep_count_total += n
        self._ep_return_total += float(r.mean()) * n
        room = _EPISODE_RESERVOIR - len(self._ep_returns)
        if room > 0:
            self._ep_returns.extend(float(x) for x in r[:room])
        if lengths is not None:
            ln = np.asarray(lengths, dtype=np.float64).reshape(-1)
            room = _EPISODE_RESERVOIR - len(self._ep_lengths)
            if room > 0:
                self._ep_lengths.extend(float(x) for x in ln[:room])

    def observe_env_restart(self, count: int = 1) -> None:
        """Account ``RestartOnException`` env restarts (previously invisible):
        a ``Health/env_restarts`` gauge plus an immediate ``health`` event — a
        flapping env is an operational signal, not noise to average away."""
        if not self.enabled or count <= 0:
            return
        self._env_restarts += int(count)
        event = {"status": "env_restart", "restarts": int(count), "total": self._env_restarts}
        self._append_history("health", event)
        if self._sink is not None:
            self._sink.emit("health", **event)

    def emit_event(self, event: str, step: Optional[int] = None, **fields: Any) -> bool:
        """Write an arbitrary event to the run's JSONL stream (used by the
        resilience subsystem for preempt/checkpoint/stall events). Returns False
        when no sink is open so the caller can fall back to its own."""
        if self._sink is None:
            return False
        self._sink.emit(event, step=step, **fields)
        return True

    def step(self, policy_step: int) -> None:
        """Once per loop iteration: advance the profiler window and emit a
        telemetry window every ``every`` policy steps. Idle cost is two int
        compares plus a method call."""
        self._last_step = policy_step
        was_started, was_stopped = self.profiler.started_at, self.profiler.stopped_at
        self.profiler.on_step(policy_step)
        if self._sink is not None:
            if self.profiler.started_at is not None and was_started is None:
                self._sink.emit("profiler", step=policy_step, action="start", dir=self.profiler.dump_dir)
            if (
                self.profiler.stopped_at is not None
                and was_stopped is None
                and self.profiler.started_at is not None  # a failed start never opened a trace
            ):
                self._sink.emit(
                    "profiler",
                    step=policy_step,
                    action="stop",
                    dir=self.profiler.dump_dir,
                    covered_steps=self.profiler.stopped_at - self.profiler.started_at,
                )
                self._emit_profile_analysis(policy_step)
        if not self.enabled:
            return
        if self._anchor_step is None:
            now = time.perf_counter()
            self._anchor_step = self._start_step = policy_step
            self._anchor_time = self._start_time = now
            # baseline the non-monotonic sources so window 0 diffs cleanly
            # (the one-shot analysis accumulator is kept — register_program can
            # legitimately run before the anchor in warmup-heavy loops)
            self._harvest_timers()
            analysis = self._window_phases["analysis"]
            self._window_phases = {**{k: 0.0 for k in _PHASE_TIMERS}, "analysis": analysis}
            self._prefetch_delta()
            self._span_cursor = now
            self._window_counters()
            return
        # harvest EVERY iteration, not just at window boundaries: the metric log
        # sites reset the timer registry on their own (log_every) cadence, and a
        # reset between two windows would otherwise drop everything accrued
        # before it. The loops call step() right before the log block, so the
        # read always lands ahead of the reset.
        self._harvest_timers()
        if policy_step - self._anchor_step >= self.every:
            self._emit_window(policy_step)

    def close(self, policy_step: Optional[int] = None, clean_exit: bool = True) -> None:
        """Flush the last partial window, write the run ``summary`` event and
        finalize the profiler/JSONL artifacts. The loops call this from a
        ``finally`` path, so a crashed or preempted run still leaves its summary
        — ``clean_exit=False`` marks an exception unwind (the supervisor's
        cross-attempt history reads end-of-attempt state from it). Idempotent:
        a second call is a no-op."""
        _LIVE_TELEMETRY.discard(self)
        window_truncated = self.profiler.active
        self.profiler.close(policy_step)
        if window_truncated and self._sink is not None and self.profiler.started_at is not None:
            # pair the earlier 'start': a window still open at loop exit is
            # finalized here, so consumers always see a start/stop pair
            self._sink.emit(
                "profiler",
                step=policy_step,
                action="stop",
                dir=self.profiler.dump_dir,
                covered_steps=(self.profiler.stopped_at or self.profiler.started_at)
                - self.profiler.started_at,
                truncated=True,
            )
            self._emit_profile_analysis(policy_step)
        if not self.enabled:
            return
        if (
            policy_step is not None
            and self._anchor_step is not None
            and policy_step > self._anchor_step
        ):
            self._emit_window(policy_step, final=True)
        if self._sink is not None:
            total_steps = (
                (policy_step - self._start_step)
                if (policy_step is not None and self._start_step is not None)
                else 0
            )
            wall = time.perf_counter() - self._start_time if self._start_step is not None else 0.0
            snap = compile_snapshot()
            hbm = mesh_device_memory(self._devices)
            peak_hbm = max(self._peak_hbm, (hbm or {}).get("peak_bytes", 0)) or None
            overall_mfu = None
            if (
                self._mfu_flops_per_unit
                and self._peak_flops
                and self._total_train_seconds > 0
                and self._total_train_units > 0
            ):
                overall_mfu = (
                    self._mfu_flops_per_unit * self._total_train_units / self._total_train_seconds
                ) / self._peak_flops
            phases_total = {k: round(v, 3) for k, v in self._total_phases.items()}
            attributed = None
            if self._total_wall_seconds > 0:
                named = sum(v for k, v in self._total_phases.items() if k != "other")
                attributed = round(min(named / self._total_wall_seconds, 1.0), 4)
            self._sink.emit(
                "summary",
                step=policy_step,
                clean_exit=bool(clean_exit),
                windows=self._window_idx,
                total_steps=total_steps,
                wall_seconds=round(wall, 3),
                sps=round(total_steps / wall, 3) if wall > 0 else None,
                train_units=self._total_train_units,
                train_seconds=round(self._total_train_seconds, 3),
                phases=phases_total or None,
                attributed_fraction=attributed,
                mfu=overall_mfu,
                compile={
                    "count": snap["count"] - self._compile_base["count"],
                    "seconds": round(snap["seconds"] - self._compile_base["seconds"], 3),
                    # persistent-cache hits counted inside `count`: count minus
                    # cache_hits is the COLD compiles (the fleet cold-start gauge)
                    "cache_hits": snap.get("cache_hits", 0)
                    - self._compile_base.get("cache_hits", 0),
                },
                hbm_peak_bytes=peak_hbm,
                rss_peak_bytes=rss_peak_bytes(),
                prefetch=self._prefetch_total or None,
                env_restarts=self._env_restarts,
                health=self._health_status,
                # end-of-run dataflow state (weight lag, row ages, queue): the
                # numbers bench.py attaches under conditions.dataflow; absent
                # entirely on runs without an experience plane
                dataflow=self._dataflow_snapshot() or None,
                # run-level learning rollup: per-stat run means, grad-norm run
                # maxes, episode totals + the last window's block — what
                # bench.py attaches under conditions.learning and the fleet
                # leaderboard rolls up
                learning=self._learning_summary() or None,
                programs={k: v for k, v in self._programs.items()},
                # final error-budget accounting; None when no objective ever
                # saw its signal (pure training stream with default objectives)
                slo=(
                    self._slo_evaluator.slo_block()
                    if self._slo_evaluator is not None
                    else None
                ),
            )
            try:
                self._write_spans(spans_path(self._sink.path))
            except OSError as exc:  # the stream and the endpoint still close
                warnings.warn(f"telemetry: the raw spans could not be written: {exc!r}")
            self._sink.close()
            self._sink = None
        if self.metrics_endpoint is not None:
            self.metrics_endpoint.close()
            self.metrics_endpoint = None
        self.enabled = False

    # -- internals ---------------------------------------------------------------

    def _timer_delta(self, name: str) -> float:
        """Non-destructive delta of a named timer's accumulated seconds since the
        last harvest, exact across the log sites' ``to_dict(reset=True)``: the
        timer's reset generation tells a reset apart from plain accrual (a
        magnitude heuristic would miss a reset whose post-reset accrual already
        caught up with the pre-reset total, e.g. log_every <= steps-per-iter)."""
        t = timer.timers.get(name)
        if t is None:
            return 0.0
        cur, resets = float(t._total), t._resets
        last, last_resets = self._timer_last.get(name, (0.0, resets))
        # after a reset the whole current total is fresh accrual; harvesting
        # every step() (right before the loops' log block, the only reset site)
        # makes the pre-reset remainder since the last harvest zero
        delta = cur if resets != last_resets else cur - last
        self._timer_last[name] = (cur, resets)
        return max(delta, 0.0)

    def _harvest_timers(self) -> None:
        """Accumulate the named phase timers' fresh seconds into the current
        window (see ``_PHASE_TIMERS``; loops that lack a span simply contribute
        zero to that phase)."""
        for phase, name in _PHASE_TIMERS.items():
            self._window_phases[phase] += self._timer_delta(name)

    def _window_spans(self) -> Dict[str, list]:
        """Aggregate of the spans that ended since the last window."""
        spans = timer.spans_since(self._span_cursor)
        if spans:
            self._span_cursor = spans[-1][2]
        return {k: [v[0], round(v[1], 6), round(v[2], 6)] for k, v in aggregate_spans(spans).items()}

    def _window_counters(self) -> Dict[str, list]:
        """What the timer's counters gained since the last window."""
        out = {}
        for name, (count, total) in list(timer.counters.items()):
            last_count, last_total = self._counter_last.get(name, (0, 0.0))
            self._counter_last[name] = (count, total)
            if count != last_count:
                out[name] = [count - last_count, total - last_total]
        return out

    def _write_spans(self, path: str) -> None:
        """The raw spans that ended since this object was built (of the ring's last
        ``timer.RING_CAPACITY``; the ring is the process's, so two loops in one process
        each write both threads' spans), one JSON object a line, start and end as
        wall-clock seconds like the events' ``time``: what ``sheeprl.py trace`` draws.
        Appended and stamped with ``rank`` and ``attempt`` as the stream's events are:
        a supervised restart into the same log dir keeps the attempt before it."""
        spans = timer.spans_since(self._born)
        if not spans or os.path.abspath(path) == os.path.abspath(self._sink.path):
            return
        to_wall = time.time() - time.perf_counter()
        with open(path, "a") as fh:
            for name, start, end, parent, iteration in spans:
                fh.write(json.dumps({
                    "name": name, "start": round(start + to_wall, 6), "end": round(end + to_wall, 6),
                    "parent": parent, "iter": iteration, "rank": self._rank, "attempt": self._attempt,
                }) + "\n")

    def _append_history(self, event: str, payload: Dict[str, Any]) -> None:
        """Feed the in-loop diagnosis history (bounded; same payloads the sink
        writes — including the wall-clock ``time`` the sink would stamp, which
        the env-restart clustering detector reads — so the offline and live
        detectors see the same shapes)."""
        self._history.append({"event": event, "time": round(time.time(), 3), **payload})
        if len(self._history) > _HISTORY_CAP:
            del self._history[: len(self._history) - _HISTORY_CAP]

    def _run_live_diagnosis(self, policy_step: int) -> None:
        """Run the detector catalog over this run's own window/health history and
        emit a ``health`` event (``status=diagnosis``) when the finding set
        changes — the live half of ``obs/diagnose.py``'s offline CLI."""
        from sheeprl_tpu.obs.diagnose import run_detectors

        findings = run_detectors(self._history)
        key = tuple(sorted((f["detector"], f["severity"]) for f in findings))
        if findings and key != self._last_diagnosis_key and self._sink is not None:
            self._sink.emit(
                "health",
                step=policy_step,
                status="diagnosis",
                findings=[
                    {k: f[k] for k in ("detector", "severity", "summary", "suggestion")}
                    for f in findings
                ],
            )
        self._last_diagnosis_key = key

    def _emit_profile_analysis(self, policy_step: Optional[int]) -> None:
        """Parse the window capture the profiler just finalized and emit the
        schema-registered ``profile_analysis`` event (obs/xprof.py). The
        fractions are cached so the next window's ``Perf/xla_*`` gauges carry
        them to TB + the Prometheus endpoint. Parsing a capture must never take
        the run down — any failure leaves the raw capture for the offline
        ``sheeprl.py profile`` verb."""
        if self._sink is None:
            return
        try:
            from sheeprl_tpu.obs.xprof import analyze_capture, profile_event_payload

            analysis = analyze_capture(
                self.profiler.dump_dir,
                self._programs,
                peak_flops=self._peak_flops,
                device_kind=getattr(self._device, "device_kind", None),
            )
        except Exception:
            return
        if analysis is None:
            return
        self._last_profile = analysis
        self._sink.emit("profile_analysis", step=policy_step, **profile_event_payload(analysis))

    def _prefetch_delta(self) -> Optional[Dict[str, Any]]:
        if self._sampler is None:
            return None
        try:
            snap = self._sampler.telemetry_snapshot()
        except Exception:
            return None
        last = self._prefetch_last or {}
        delta = {k: float(snap.get(k, 0.0)) - float(last.get(k, 0.0)) for k in _PREFETCH_COUNTERS}
        self._prefetch_last = {k: float(snap.get(k, 0.0)) for k in _PREFETCH_COUNTERS}
        for k, v in delta.items():
            self._prefetch_total[k] = self._prefetch_total.get(k, 0.0) + v
        calls = max(delta["sample_calls"], 1.0)
        units = max(delta["units"], 1.0)
        out = {
            "wait_seconds": delta["wait_seconds"],
            "sample_calls": int(delta["sample_calls"]),
            "units": int(delta["units"]),
            "occupancy": delta["occupancy_sum"] / calls,
            "staleness": delta["staleness_sum"] / units,
            "empty_waits": int(delta["empty_waits"]),
            "pipeline_len": int(snap.get("pipeline_len", 0)),
            "depth": int(snap.get("depth", 0)),
            "is_async": bool(snap.get("is_async", False)),
        }
        # device-ring storage gauges (DeviceRingSampler.telemetry_snapshot):
        # occupancy = fill/capacity, overwritten = slots lost to wraparound
        if snap.get("ring_capacity"):
            capacity = float(snap["ring_capacity"])
            out["ring"] = {
                "fill": int(snap.get("ring_fill", 0)),
                "capacity": int(capacity),
                "occupancy": float(snap.get("ring_fill", 0)) / max(capacity, 1.0),
                "overwritten": int(snap.get("ring_overwritten", 0)),
            }
        return out

    def _dataflow_snapshot(self) -> Optional[Dict[str, Any]]:
        if self._dataflow is None:
            return None
        try:
            snap = self._dataflow.dataflow_snapshot()
        except Exception:
            return self._last_dataflow  # a dying KV plane must not kill the window
        self._last_dataflow = snap
        return snap

    @staticmethod
    def _dataflow_gauges(dataflow: Optional[Mapping[str, Any]]) -> Dict[str, float]:
        """The ``Service/*`` gauge projection of one dataflow block (only the
        keys the role actually reports)."""
        if not dataflow:
            return {}
        gauges: Dict[str, float] = {}
        lag = dataflow.get("weight_lag")
        if isinstance(lag, Mapping):
            lag = lag.get("max")
        if isinstance(lag, (int, float)):
            gauges["Service/weight_lag"] = float(lag)
        row_age = (dataflow.get("row_age") or {}).get("seconds") if dataflow.get("row_age") else None
        if isinstance(row_age, Mapping):
            if row_age.get("p50") is not None:
                gauges["Service/row_age_p50"] = float(row_age["p50"])
            if row_age.get("p99") is not None:
                gauges["Service/row_age_p99"] = float(row_age["p99"])
        latency = dataflow.get("ingest_latency_ms")
        if isinstance(latency, Mapping) and latency.get("p99") is not None:
            gauges["Service/ingest_latency_p99_ms"] = float(latency["p99"])
        for key, gauge in (
            ("queue_depth", "Service/queue_depth"),
            ("rows_per_sec", "Service/rows_per_sec"),
            ("inflight", "Service/ingest_inflight"),
        ):
            value = dataflow.get(key)
            if isinstance(value, (int, float)):
                gauges[gauge] = float(value)
        return gauges

    def _learning_block(self) -> Optional[Dict[str, Any]]:
        """Fetch the window's learn-stat reservoir (ONE ``jax.device_get`` of
        scalar buffers — the only host transfer the learning plane ever pays)
        and distill it plus the episode sample into the window event's
        ``learning`` block. Resets the per-window state. None when the window
        saw neither train stats nor episodes."""
        if not self._learn_window and self._ep_count_window == 0:
            return None
        stats: Dict[str, Optional[float]] = {}
        nonfinite: list = []
        if self._learn_window:
            try:
                import jax

                host = jax.device_get(self._learn_window)
            except Exception:
                host = []
            if host:
                keys = sorted({k for entry in host for k in entry})
                series: Dict[str, np.ndarray] = {}
                for k in keys:
                    vals = np.asarray(
                        [float(np.asarray(e[k])) for e in host if k in e], dtype=np.float64
                    )
                    series[k] = vals
                    finite = vals[np.isfinite(vals)]
                    if finite.size < vals.size:
                        nonfinite.append(k)
                    if finite.size == 0:
                        stats[k] = None
                    elif k.startswith("grad_norm_max/"):
                        stats[k] = round(float(finite.max()), 6)
                    else:
                        stats[k] = round(float(finite.mean()), 6)
                # single-step programs emit no per-round max: synthesize the
                # window max from the per-round grad norms so the explosion
                # detector always has a spike-sensitive series to read
                for k, vals in series.items():
                    if not k.startswith("grad_norm/"):
                        continue
                    group = k[len("grad_norm/") :]
                    max_key = f"grad_norm_max/{group}"
                    if max_key not in stats:
                        finite = vals[np.isfinite(vals)]
                        if finite.size:
                            stats[max_key] = round(float(finite.max()), 6)
        episodes: Optional[Dict[str, Any]] = None
        if self._ep_count_window:
            r = np.asarray(self._ep_returns, dtype=np.float64)
            episodes = {
                "count": int(self._ep_count_window),
                "return_mean": round(float(r.mean()), 4),
                "return_p10": round(float(np.quantile(r, 0.1)), 4),
                "return_p50": round(float(np.quantile(r, 0.5)), 4),
                "return_p90": round(float(np.quantile(r, 0.9)), 4),
            }
            if self._ep_lengths:
                episodes["len_mean"] = round(float(np.mean(self._ep_lengths)), 2)
        samples = len(self._learn_window)
        for k, v in stats.items():
            if v is None:
                continue
            if k.startswith("grad_norm_max/"):
                self._learn_run_max[k] = max(self._learn_run_max.get(k, float("-inf")), v)
            else:
                self._learn_run_sums[k] = self._learn_run_sums.get(k, 0.0) + v * samples
                self._learn_run_counts[k] = self._learn_run_counts.get(k, 0) + samples
        block: Dict[str, Any] = {"rounds": int(self._learn_seen)}
        if stats:
            block["stats"] = stats
        if episodes is not None:
            block["episodes"] = episodes
        if nonfinite:
            block["nonfinite"] = nonfinite
        self._last_learning = block
        # reset the per-window state
        self._learn_window = []
        self._learn_stride = 1
        self._learn_seen = 0
        self._ep_returns = []
        self._ep_lengths = []
        self._ep_count_window = 0
        return block

    @staticmethod
    def _learning_gauges(learning: Optional[Mapping[str, Any]]) -> Dict[str, float]:
        """The ``Learn/*`` gauge projection of one learning block (finite stats
        plus the episode-return mean/count — what the Prometheus endpoint and
        the metric logger see)."""
        if not learning:
            return {}
        gauges: Dict[str, float] = {}
        for k, v in (learning.get("stats") or {}).items():
            if isinstance(v, (int, float)) and np.isfinite(v):
                gauges[f"{LEARN_PREFIX}{k}"] = float(v)
        episodes = learning.get("episodes") or {}
        if isinstance(episodes.get("return_mean"), (int, float)):
            gauges[f"{LEARN_PREFIX}ep_return_mean"] = float(episodes["return_mean"])
        if episodes.get("count"):
            gauges[f"{LEARN_PREFIX}ep_count"] = float(episodes["count"])
        return gauges

    def _learning_summary(self) -> Optional[Dict[str, Any]]:
        """Run-level learning rollup for the summary event: per-stat run means
        (sample-weighted across windows), run-max grad norms, exact episode
        totals, and the last window's block (the freshest state — what the
        fleet leaderboard ranks on)."""
        if self._learn_rounds_total == 0 and self._ep_count_total == 0:
            return None
        stats = {
            k: round(s / max(self._learn_run_counts.get(k, 1), 1), 6)
            for k, s in self._learn_run_sums.items()
        }
        stats.update({k: round(v, 6) for k, v in self._learn_run_max.items()})
        out: Dict[str, Any] = {"rounds": int(self._learn_rounds_total)}
        if stats:
            out["stats"] = stats
        if self._ep_count_total:
            out["episodes"] = {
                "count": int(self._ep_count_total),
                "return_mean": round(self._ep_return_total / self._ep_count_total, 4),
            }
        if self._last_learning is not None:
            out["last"] = {
                k: v for k, v in self._last_learning.items() if k in ("stats", "episodes")
            }
        return out

    def _check_health(self, policy_step: int) -> Optional[Dict[str, Any]]:
        if self._window_idx % self.health_every != 0:
            return None
        if self._last_losses is None:
            self._health_status = "no-train"
            return {"status": "no-train"}
        bad = _nonfinite_losses(self._last_losses)
        self._health_status = "nonfinite" if bad else "ok"
        event = {"status": self._health_status}
        if bad:
            event["nonfinite"] = bad
        return event

    def _emit_window(self, policy_step: int, final: bool = False) -> None:
        now = time.perf_counter()
        steps = policy_step - (self._anchor_step or 0)
        wall = max(now - self._anchor_time, 1e-9)
        sps = steps / wall

        self._harvest_timers()  # pick up anything accrued since the last step()
        train_seconds = self._window_phases["train"]
        env_seconds = self._window_phases["env"]
        self._total_train_seconds += train_seconds

        snap = compile_snapshot()
        window_compiles = snap["count"] - self._compile_last["count"]
        window_compile_seconds = snap["seconds"] - self._compile_last["seconds"]
        self._compile_last = dict(snap)
        total_compiles = snap["count"] - self._compile_base["count"]
        total_compile_seconds = snap["seconds"] - self._compile_base["seconds"]
        if (
            window_compiles > 0
            and not final  # the close-time window absorbs the end-of-run
            # test's first-time eval compiles — legitimate, not shape churn
            and self.compile_warmup_steps > 0
            and policy_step > self.compile_warmup_steps
        ):
            warnings.warn(
                f"telemetry: {window_compiles} unexpected XLA recompile(s) "
                f"({window_compile_seconds:.1f}s) after warmup (policy step {policy_step}) — "
                "look for shape churn (varying gradient-step counts, env batch changes)"
            )

        hbm = mesh_device_memory(self._devices)
        if hbm and hbm.get("peak_bytes"):
            self._peak_hbm = max(self._peak_hbm, hbm["peak_bytes"])
        rss = _rss_bytes()
        rss_peak = rss_peak_bytes()

        mfu = None
        if (
            self._mfu_flops_per_unit
            and self._peak_flops
            and train_seconds > 0
            and self._window_train_units > 0
        ):
            mfu = (self._mfu_flops_per_unit * self._window_train_units / train_seconds) / self._peak_flops
        self._last_mfu = mfu

        prefetch = self._prefetch_delta()
        dataflow = self._dataflow_snapshot()
        learning = self._learning_block()
        health = self._check_health(policy_step)

        # phase attribution: replay/prefetch wait is carved OUT of the train span
        # (sampler.sample runs inside `with timer("Time/train_time")` in every
        # off-policy loop), so `train` below is pure device-train time and the
        # named phases tile the window: sum(phases) + other ≈ wall_seconds.
        # `train_seconds`/MFU keep the PR 2 semantics (wait included) unchanged.
        replay_wait = 0.0
        if prefetch is not None:
            replay_wait = min(max(float(prefetch["wait_seconds"]), 0.0), train_seconds)
        phases = {
            "env": env_seconds,
            "rollout": self._window_phases["rollout"],
            "replay_wait": replay_wait,
            "train": train_seconds - replay_wait,
            "checkpoint": self._window_phases["checkpoint"],
            "logging": self._window_phases["logging"],
            "eval": self._window_phases["eval"],
            "analysis": self._window_phases["analysis"],
        }
        phases["other"] = max(wall - sum(phases.values()), 0.0)
        phases = {k: round(v, 4) for k, v in phases.items()}
        for k, v in phases.items():
            self._total_phases[k] = self._total_phases.get(k, 0.0) + v
        self._total_wall_seconds += wall

        gauges: Dict[str, float] = {
            "Perf/sps": sps,
            "Compile/count": float(total_compiles),
            "Compile/seconds": float(total_compile_seconds),
        }
        if hbm is not None:
            if "bytes_in_use" in hbm:
                gauges["Mem/hbm_bytes_in_use"] = float(hbm["bytes_in_use"])
            if "peak_bytes" in hbm:
                gauges["Mem/hbm_peak"] = float(hbm["peak_bytes"])
        if rss is not None:
            gauges["Mem/host_rss_bytes"] = float(rss)
        if rss_peak is not None:
            gauges["Mem/host_rss_peak"] = float(rss_peak)
        if mfu is not None:
            gauges["Perf/mfu"] = float(mfu)
        if prefetch is not None:
            gauges["Time/prefetch_wait"] = float(prefetch["wait_seconds"])
            gauges["Buffer/pipeline_occupancy"] = float(prefetch["occupancy"])
            gauges["Buffer/pipeline_staleness"] = float(prefetch["staleness"])
            ring = prefetch.get("ring")
            if ring is not None:
                gauges["Buffer/ring_fill"] = float(ring["fill"])
                gauges["Buffer/ring_occupancy"] = float(ring["occupancy"])
                gauges["Buffer/ring_overwritten"] = float(ring["overwritten"])
        if self._last_profile is not None:
            # the latest window capture's attribution (obs/xprof.py): fractions
            # of device time, so TB/Prometheus trend them across captures
            fractions = self._last_profile.get("fractions") or {}
            gauges["Perf/xla_comm_fraction"] = float(fractions.get("comm", 0.0))
            gauges["Perf/xla_mxu_fraction"] = float(fractions.get("mxu", 0.0))
            gauges["Perf/xla_idle_fraction"] = float(fractions.get("idle", 0.0))
        if self._env_restarts > 0:
            gauges["Health/env_restarts"] = float(self._env_restarts)
        gauges.update(self._dataflow_gauges(dataflow))
        gauges.update(self._learning_gauges(learning))
        if self._logger is not None:
            self._logger.log_metrics(gauges, policy_step)
        if self.metrics_endpoint is not None:
            self.metrics_endpoint.update({**gauges, "Run/policy_step": float(policy_step)})

        window_event: Dict[str, Any] = dict(
            step=policy_step,
            window=self._window_idx,
            final=bool(final),
            steps=steps,
            wall_seconds=round(wall, 4),
            sps=round(sps, 3),
            train_units=self._window_train_units,
            train_seconds=round(train_seconds, 4),
            env_seconds=round(env_seconds, 4),
            phases=phases,
            # the timer's real spans that ended in this window, {name: [count,
            # seconds, self_seconds]}, and its counters, {name: [count, total]}
            spans=self._window_spans() or None,
            counters=self._window_counters() or None,
            mfu=mfu,
            hbm=hbm,
            rss_bytes=rss,
            rss_peak_bytes=rss_peak,
            compile={
                "count": total_compiles,
                "seconds": round(total_compile_seconds, 3),
                "window_count": window_compiles,
                "window_seconds": round(window_compile_seconds, 3),
            },
            prefetch=prefetch,
        )
        if dataflow is not None:
            window_event["dataflow"] = dataflow
        if learning is not None:
            window_event["learning"] = learning
        # SLO plane: feed this window to the burn-rate evaluator, attach the
        # budget block, advance the stateful alert engine — the same machinery
        # `sheeprl.py slo` replays offline, so verdicts cannot drift
        alert_transitions: list = []
        slo_snapshot: Dict[str, Any] = {}
        if self._slo_evaluator is not None:
            self._slo_evaluator.observe_window(window_event)
            slo_block = self._slo_evaluator.slo_block()
            if slo_block is not None:
                window_event["slo"] = slo_block
            slo_snapshot = self._slo_evaluator.snapshot()
            alert_transitions = self._alert_engine.evaluate(slo_snapshot)
        self._append_history("window", window_event)
        if self._sink is not None:
            self._sink.emit("window", **window_event)
            if health is not None:
                self._append_history("health", {"step": policy_step, **health})
                self._sink.emit("health", step=policy_step, **health)
            for transition in alert_transitions:
                self._sink.emit("alert", step=policy_step, **transition)
                # critical alerts escalate through the existing health path
                if (
                    transition["status"] == "firing"
                    and transition.get("severity") == "critical"
                ):
                    self._sink.emit(
                        "health",
                        step=policy_step,
                        status="alert",
                        findings=[
                            {
                                "detector": f"slo:{transition['name']}",
                                "severity": "critical",
                                "summary": (
                                    f"SLO alert {transition['name']} firing "
                                    f"(budget remaining {transition.get('budget_remaining')})"
                                ),
                                "suggestion": "see `sheeprl.py slo` for the budget breakdown",
                            }
                        ],
                    )
        if self.metrics_endpoint is not None and slo_snapshot:
            # merged on top of this window's replace=True push; the NEXT window's
            # full push wipes anything resolved, so firing gauges never linger
            slo_gauges: Dict[str, float] = {}
            worst_remaining = None
            for name, stats in slo_snapshot.items():
                if not stats.get("samples"):
                    continue
                remaining = stats.get("budget_remaining")
                slo_gauges[f"Slo/budget_remaining/{name}"] = remaining
                if worst_remaining is None or remaining < worst_remaining:
                    worst_remaining = remaining
            if worst_remaining is not None:
                slo_gauges["Slo/worst_budget_remaining"] = worst_remaining
            firing = self._alert_engine.firing()
            slo_gauges["Alerts/firing"] = float(len(firing))
            for name in firing:
                slo_gauges[f"Alerts/firing/{name}"] = 1.0
            self.metrics_endpoint.update(slo_gauges, replace=False)
        if self.diagnosis:
            self._run_live_diagnosis(policy_step)

        self._window_idx += 1
        self._window_train_units = 0
        self._window_phases = {**{k: 0.0 for k in _PHASE_TIMERS}, "analysis": 0.0}
        self._anchor_step = policy_step
        self._anchor_time = now

        if health is not None and health.get("nonfinite") and self.abort_on_nonfinite:
            raise RuntimeError(
                f"telemetry.abort_on_nonfinite: non-finite training losses at policy step "
                f"{policy_step}: {health['nonfinite']}"
            )


def build_telemetry(fabric: Any, cfg: Any, log_dir: Optional[str], logger: Any = None):
    """Build the run's telemetry facade from the ``metric.telemetry`` +
    ``metric.profiler`` config groups. Rank-0-only (SPMD: one controller process
    observes the whole mesh; MPMD roles build their own). Returns the
    :class:`NullTelemetry` no-op when neither full telemetry nor a windowed
    profiler capture is configured — the zero-overhead off path."""
    if not getattr(fabric, "is_global_zero", True):
        return NullTelemetry()
    metric_cfg = cfg.metric
    tcfg = metric_cfg.get("telemetry") or {}
    enabled = bool(tcfg.get("enabled", False))
    pcfg = resolve_profiler_config(metric_cfg)
    if not enabled and pcfg["mode"] != "window":
        return NullTelemetry()
    return RunTelemetry(fabric, cfg, log_dir, logger, enabled=enabled, profiler_cfg=pcfg, http=True)


def role_stream_path(cfg: Any, role: str) -> str:
    """Per-role sibling of the run's main telemetry stream: the configured
    ``jsonl_path`` with ``.<role>`` spliced in before the extension, or
    ``telemetry.<role>.jsonl`` in the run-base dir — either way a path
    ``obs/streams.py`` discovers next to the player's stream."""
    tcfg = (cfg.metric.get("telemetry") or {}) if cfg.metric is not None else {}
    base = tcfg.get("jsonl_path")
    if base:
        root, ext = os.path.splitext(str(base))
        return f"{root}.{role}{ext or '.jsonl'}"
    from sheeprl_tpu.utils.logger import run_base_dir

    return str(run_base_dir(cfg.root_dir, cfg.run_name) / f"telemetry.{role}.jsonl")


def build_role_telemetry(fabric: Any, cfg: Any, role: str, *, rank: int, leader: bool = True):
    """Telemetry stream for a decoupled MPMD role process (the learner slice of
    sac_decoupled / ppo_decoupled / dv3_decoupled). The player's rank-0 stream
    cannot see learner-side train time, HBM or compiles — this gives the role
    its own ``telemetry.<role>.jsonl`` (one per role: only the slice ``leader``
    writes; the other slice members get the no-op), merged with the player's by
    ``obs/streams.py``. No logger, no profiler — the JSONL stream only."""
    tcfg = cfg.metric.get("telemetry") or {}
    if not (bool(tcfg.get("enabled", False)) and bool(tcfg.get("jsonl", True)) and leader):
        return NullTelemetry()
    return RunTelemetry(
        fabric,
        cfg,
        None,
        None,
        enabled=True,
        profiler_cfg={"mode": "off", "start_step": 0, "num_steps": 0, "dir": None},
        jsonl_path=role_stream_path(cfg, role),
        rank=rank,
    )
