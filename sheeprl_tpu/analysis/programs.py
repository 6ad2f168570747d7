"""Fused-program registry + AOT contract sweep (the ``lint --aot`` gate).

Every donated ``jax.jit`` program in the tree (the fused train phases, the
serving slot-table step/attach, the Anakin fused rollout+train) registers an
**AOT builder** via :func:`register_fused_program`: a zero-argument callable
that constructs the jitted program on tiny shapes (composing a tiny config and
building the real agent — the same factories the training loops use) and
returns ``(jitted_fn, example_args)``. The sweep then, per program and WITHOUT
executing anything:

1. ``jit(...).trace(abstract_args).lower(lowering_platforms=(...))`` — the full
   jaxpr→StableHLO pipeline for BOTH the cpu and tpu platforms, off-chip (the
   ``test_tpu_lowering.py`` trick generalized: a branch that only ever lowered
   on CPU cannot hide a TPU trace error until the first paid chip window);
2. asserts the declared :class:`ProgramContract` on the lowered MLIR: donation
   survives (``jax.buffer_donor``/``tf.aliasing_output``), no host-transfer
   markers (``callback``/``infeed``/``outfeed``), no custom calls beyond the
   declared allowlist, expected custom calls present (the Pallas GRU's Mosaic
   ``tpu_custom_call``);
3. optionally backend-compiles on the host CPU mesh and asserts the OPTIMIZED
   HLO too: ``input_output_alias`` (XLA actually honored the donation) and the
   expected collective families (the dp psum of a data-parallel program).

This generalizes the three hand-written AOT tests (anakin, serve slots,
test_tpu_lowering) into one registry pass: those tests now parametrize over
:data:`FUSED_PROGRAMS` (``tests/test_analysis/test_aot_contracts.py``), and
``python sheeprl.py lint --aot`` runs the identical sweep operationally.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

Finding = Dict[str, Any]

__all__ = [
    "ProgramContract",
    "ProgramSpec",
    "FUSED_PROGRAMS",
    "register_fused_program",
    "check_program_contract",
    "aot_sweep",
]

# host-transfer markers that must never appear in a fused program's lowering
HOST_TRANSFER_MARKERS = ("callback", "infeed", "outfeed")

COLLECTIVE_FAMILIES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_CUSTOM_CALL_MLIR_RE = re.compile(r"custom_call\s+@([\w$.]+)")
_CUSTOM_CALL_TARGET_RE = re.compile(r'custom_call_target\s*=\s*"([^"]+)"')


@dataclass(frozen=True)
class ProgramContract:
    """What the lowered/compiled program must look like.

    ``donated``: donation aliasing must survive lowering (and, with
    ``compile_on_cpu``, the XLA optimization pipeline). ``min_donated`` guards
    against donation quietly narrowing to a subset of the state leaves.
    ``allow_custom_calls`` is the closed allowlist of custom-call targets
    (anything else is an unexpected host/runtime dependency);
    ``expect_custom_calls`` must each appear (e.g. the Mosaic kernel).
    ``expect_collectives`` are checked in the optimized HLO — declaring one
    implies ``compile_on_cpu``."""

    donated: bool = True
    min_donated: int = 1
    forbidden: Tuple[str, ...] = HOST_TRANSFER_MARKERS
    allow_custom_calls: Tuple[str, ...] = ()
    expect_custom_calls: Tuple[str, ...] = ()
    expect_collectives: Tuple[str, ...] = ()
    platforms: Tuple[str, ...] = ("cpu", "tpu")
    compile_on_cpu: bool = False


@dataclass
class ProgramSpec:
    name: str
    builder: Callable[[], Tuple[Any, Sequence[Any]]]
    contract: ProgramContract
    devices: int = 1
    origin: str = ""  # repo-relative file of the registration site
    doc: str = ""
    tags: Tuple[str, ...] = field(default_factory=tuple)


# name -> spec; populated at import time by the registering modules
# (``import sheeprl_tpu`` pulls in every algo module; serve/ops registrations
# ride the imports in ensure_registry()).
FUSED_PROGRAMS: Dict[str, ProgramSpec] = {}


def register_fused_program(
    name: str,
    *,
    donated: bool = True,
    min_donated: int = 1,
    allow_custom_calls: Sequence[str] = (),
    expect_custom_calls: Sequence[str] = (),
    expect_collectives: Sequence[str] = (),
    platforms: Sequence[str] = ("cpu", "tpu"),
    compile_on_cpu: bool = False,
    devices: int = 1,
    doc: str = "",
    tags: Sequence[str] = (),
) -> Callable:
    """Decorator: register ``builder() -> (jitted_fn, example_args)`` under
    ``name`` with its declared contract. The builder must be cheap enough for a
    tier-1 test (tiny shapes) and must construct the program through the SAME
    factory the training loop uses — the sweep's value is that it lowers
    exactly what production runs."""

    contract = ProgramContract(
        donated=donated,
        min_donated=min_donated,
        allow_custom_calls=tuple(allow_custom_calls),
        expect_custom_calls=tuple(expect_custom_calls),
        expect_collectives=tuple(expect_collectives),
        platforms=tuple(platforms),
        compile_on_cpu=bool(compile_on_cpu) or bool(expect_collectives),
    )

    def wrap(builder: Callable) -> Callable:
        if name in FUSED_PROGRAMS:
            raise ValueError(f"fused program {name!r} registered twice")
        module = getattr(builder, "__module__", "") or ""
        origin = module.replace(".", "/") + ".py" if module else ""
        FUSED_PROGRAMS[name] = ProgramSpec(
            name=name,
            builder=builder,
            contract=contract,
            devices=int(devices),
            origin=origin,
            doc=doc or (builder.__doc__ or "").strip().split("\n")[0],
            tags=tuple(tags),
        )
        return builder

    return wrap


def ensure_registry() -> Dict[str, ProgramSpec]:
    """Import every registering module (idempotent) and return the registry."""
    import importlib

    importlib.import_module("sheeprl_tpu")  # all algo modules
    for extra in ("sheeprl_tpu.serve.slots", "sheeprl_tpu.ops.aot"):
        importlib.import_module(extra)
    return FUSED_PROGRAMS


def _custom_call_targets(text: str) -> List[str]:
    targets = _CUSTOM_CALL_MLIR_RE.findall(text) + _CUSTOM_CALL_TARGET_RE.findall(text)
    return sorted(set(targets))


def _finding(spec: ProgramSpec, summary: str, suggestion: str, severity: str = "critical") -> Finding:
    return {
        "rule": "aot-contract",
        "severity": severity,
        "file": spec.origin or "sheeprl_tpu/analysis/programs.py",
        "line": 0,
        "summary": f"[{spec.name}] {summary}",
        "suggestion": suggestion,
    }


def check_program_contract(spec: ProgramSpec) -> List[Finding]:
    """Build, lower and (optionally) compile one registered program; return the
    contract violations as findings (empty list = contract holds)."""
    import jax

    from sheeprl_tpu.utils.mfu import abstractify

    contract = spec.contract
    findings: List[Finding] = []

    if spec.devices > 1 and len(jax.local_devices(backend="cpu")) < spec.devices:
        return [
            _finding(
                spec,
                f"skipped: needs a {spec.devices}-device host mesh "
                f"(set XLA_FLAGS=--xla_force_host_platform_device_count={spec.devices})",
                "run under the tier-1 harness or `python sheeprl.py lint --aot` "
                "(which pins the virtual host mesh before jax initializes)",
                severity="info",
            )
        ]

    try:
        fn, args = spec.builder()
    except Exception as exc:  # noqa: BLE001 - a failing builder IS the finding
        return [
            _finding(
                spec,
                f"AOT builder raised: {exc!r:.300}",
                "the builder must construct the program the loop runs; fix it or "
                "unregister the program",
            )
        ]

    abstract_args = abstractify(tuple(args))
    try:
        lowered = fn.trace(*abstract_args).lower(lowering_platforms=contract.platforms)
        mlir = lowered.as_text()
    except Exception as exc:  # noqa: BLE001
        return [
            _finding(
                spec,
                f"failed to lower for platforms {contract.platforms}: {exc!r:.300}",
                "this is exactly the class of error that otherwise surfaces on the "
                "first paid chip window — fix the lowering-sensitive branch",
            )
        ]

    lower_text = mlir.lower()
    if contract.donated:
        donors = mlir.count("jax.buffer_donor") + mlir.count("tf.aliasing_output")
        if donors < contract.min_donated:
            findings.append(
                _finding(
                    spec,
                    f"donation was dropped in lowering ({donors} donor annotation(s), "
                    f"expected >= {contract.min_donated})",
                    "check for host views (np.asarray) of donated inputs and for "
                    "out_shardings/jit wrappers that drop donate_argnums",
                )
            )
    for marker in contract.forbidden:
        if marker in lower_text:
            findings.append(
                _finding(
                    spec,
                    f"host-transfer marker {marker!r} in the lowered program",
                    "a fused program must not round-trip through the host in steady "
                    "state; hunt the callback/outfeed and move it out of the jit",
                )
            )
    allowed = set(contract.allow_custom_calls) | {"Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape"}
    unexpected = [t for t in _custom_call_targets(mlir) if t not in allowed]
    if unexpected:
        findings.append(
            _finding(
                spec,
                f"unexpected custom call(s) in lowering: {unexpected}",
                "declare deliberate kernels via allow_custom_calls=...; anything "
                "else is an undeclared runtime dependency",
            )
        )
    for expected in contract.expect_custom_calls:
        if expected not in mlir:
            findings.append(
                _finding(
                    spec,
                    f"expected custom call {expected!r} absent from the lowering",
                    "the declared kernel did not survive lowering (dispatch gate "
                    "changed? precision inherited?)",
                )
            )

    if contract.compile_on_cpu:
        try:
            compiled = fn.lower(*abstract_args).compile()
            hlo = compiled.as_text()
        except Exception as exc:  # noqa: BLE001
            findings.append(
                _finding(
                    spec,
                    f"failed to backend-compile on the host mesh: {exc!r:.300}",
                    "the CPU-mesh compile is the off-chip stand-in for the real "
                    "backend compile; fix before burning chip time",
                )
            )
            return findings
        hlo_lower = hlo.lower()
        if contract.donated and "input_output_alias" not in hlo:
            findings.append(
                _finding(
                    spec,
                    "XLA dropped the input/output aliasing in the optimized HLO",
                    "donation survived lowering but not compilation — look for "
                    "layout-change copies or output resharding on the donated leaves",
                )
            )
        for marker in contract.forbidden:
            if marker in hlo_lower:
                findings.append(
                    _finding(
                        spec,
                        f"host-transfer marker {marker!r} in the optimized HLO",
                        "the compiled steady-state program must keep the host out of "
                        "the loop",
                    )
                )
        for family in contract.expect_collectives:
            if family not in hlo_lower:
                findings.append(
                    _finding(
                        spec,
                        f"expected collective family {family!r} absent from the "
                        "optimized HLO",
                        "the mesh program no longer reduces across the declared axis "
                        "— sharding rules or mesh shape drifted",
                    )
                )
    return findings


# ---- shared tiny-construction helpers for the AOT builders --------------------
# The builders must construct REAL programs through the loops' own factories,
# but on shapes small enough that lowering the whole registry stays a tier-1
# test. These helpers hold the construction the dreamer-family builders share
# (the __graft_entry__ dryrun recipe); everything imports lazily so the module
# stays jax-free until a sweep actually runs.

DREAMER_TINY_OVERRIDES = (
    "env=dummy",
    "fabric.accelerator=cpu",
    "env.num_envs=2",
    "env.capture_video=False",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.cnn_keys.decoder=[rgb]",
    "algo.mlp_keys.encoder=[state]",
    "algo.mlp_keys.decoder=[state]",
    "algo.per_rank_batch_size=2",
    "algo.per_rank_sequence_length=4",
    "algo.horizon=4",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.world_model.stochastic_size=4",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "buffer.memmap=False",
    "metric.log_level=0",
    # the AOT gate must lower the GROWN programs: the Learn/* stats block is
    # compiled in only under the telemetry learning plane (utils/learn_stats.py)
    "metric.telemetry.enabled=true",
)


def tiny_dreamer_cfg(exp: str, extra: Sequence[str] = ()):
    """Compose ``exp`` at the tiny shapes every dreamer-family AOT builder uses."""
    from sheeprl_tpu.config import compose

    return compose([f"exp={exp}", *DREAMER_TINY_OVERRIDES, *extra])


def tiny_fabric():
    """Single-device CPU fabric, set up (pins the platform before any device op)."""
    from sheeprl_tpu.parallel.fabric import Fabric

    fabric = Fabric(devices=1, accelerator="cpu")
    fabric._setup()
    return fabric


def tiny_obs_space(screen: int = 64, state_dim: int = 10):
    import gymnasium as gym
    import numpy as np

    return gym.spaces.Dict(
        {
            "rgb": gym.spaces.Box(0, 255, (3, screen, screen), np.uint8),
            "state": gym.spaces.Box(-np.inf, np.inf, (state_dim,), np.float32),
        }
    )


def tiny_dreamer_batch(cfg, n_actions: int = 4, screen: int = 64, state_dim: int = 10):
    """One ``[T, B, ...]`` replay slice matching :func:`tiny_dreamer_cfg`'s
    shapes — the single-gradient-step unit the fused ``train_step`` consumes."""
    import numpy as np

    T = int(cfg.algo.per_rank_sequence_length)
    B = int(cfg.algo.per_rank_batch_size)
    rng = np.random.default_rng(0)
    return {
        "rgb": rng.integers(0, 255, (T, B, 3, screen, screen)).astype(np.uint8),
        "state": rng.normal(size=(T, B, state_dim)).astype(np.float32),
        "actions": np.eye(n_actions, dtype=np.float32)[rng.integers(0, n_actions, (T, B))],
        "rewards": rng.normal(size=(T, B, 1)).astype(np.float32),
        "terminated": np.zeros((T, B, 1), np.float32),
        "truncated": np.zeros((T, B, 1), np.float32),
        "is_first": np.zeros((T, B, 1), np.float32),
    }


def aot_sweep(names: Optional[Sequence[str]] = None) -> Tuple[List[Finding], int]:
    """Run the contract check over every registered program (or ``names``).
    Returns ``(findings, programs_checked)``. Each program check restores the
    process-wide partitioned-mesh gate to its prior value (see
    :func:`check_program_contract`), so the sweep never changes which kernels
    the hosting process — or the next program in the sweep — lowers."""
    registry = ensure_registry()
    specs = [registry[n] for n in names] if names else list(registry.values())
    findings: List[Finding] = []
    for spec in specs:
        findings.extend(check_program_contract(spec))
    return findings, len(specs)
