"""Model-FLOPs-utilization accounting.

The reference publishes wall-clocks only (README.md:99-189); on TPU the honest
efficiency metric is MFU: FLOPs the compiled program performs per second, over the
chip's peak. XLA already knows the program's FLOPs — ``compiled.cost_analysis()``
— so no analytic per-layer counting is needed; this works for any jitted program
(train steps, act steps, kernels alike).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Mapping, Optional, Sequence

# bf16 peak FLOP/s per chip (public spec sheets). Keyed by lowercase substrings of
# jax's Device.device_kind.
_TPU_PEAK_BF16: Dict[str, float] = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def peak_flops(device) -> Optional[float]:
    """Peak bf16 FLOP/s for ``device``. The host CPU has no meaningful peak and
    returns None; a TPU whose ``device_kind`` is not in the table is an error,
    not a default — a utilization against a guessed peak is worse than none."""
    if device.platform != "tpu":
        return None
    kind = (getattr(device, "device_kind", "") or "").lower()
    for tag, peak in sorted(_TPU_PEAK_BF16.items(), key=lambda kv: -len(kv[0])):
        if tag in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s entry for TPU device_kind {device.device_kind!r}; "
        f"add it to _TPU_PEAK_BF16 (known: {sorted(_TPU_PEAK_BF16)})"
    )


def compiled_flops(compiled) -> Optional[float]:
    """Total FLOPs of a lowered or compiled program, from XLA's own cost model
    (None when the backend reports none)."""
    flops = (compiled.cost_analysis() or {}).get("flops")
    return float(flops) if flops and flops > 0 else None


def abstractify(tree: Any) -> Any:
    """Replace every array leaf of a pytree with a ``jax.ShapeDtypeStruct`` so a
    jitted program can be re-lowered from METADATA only — no device reads, and
    safe to build from values that were donated to the program being analyzed.
    ``jax.Array`` leaves keep their sharding (a dp-sharded program must be
    analyzed as the sharded program XLA actually runs); non-array leaves
    (python scalars) pass through untouched.
    """
    import jax
    import numpy as np

    def _leaf(x: Any) -> Any:
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        if isinstance(x, jax.Array):
            try:
                from jax.sharding import NamedSharding

                # only mesh shardings carry placement the program depends on; a
                # SingleDeviceSharding (e.g. an uncommitted scalar that landed on
                # device 0) must stay unspecified, or lowering rejects the mix of
                # device sets that the real call happily accepts
                if isinstance(x.sharding, NamedSharding):
                    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            except Exception:
                pass
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        if isinstance(x, np.ndarray):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(_leaf, tree)


def unit_avals(tree: Any) -> Any:
    """Per-unit avals of a ``[G, ...]`` replay block: each leaf's leading
    (gradient-step) axis dropped, SHARDING PRESERVED for the remaining axes.

    The dreamer-family loops drive a single-step jitted program over the block's
    leading axis, so the program's batch aval is the ``a[0]`` slice — and on a dp
    mesh that slice is still batch-axis sharded. Rebuilding the aval from
    ``(a.shape[1:], a.dtype)`` alone would make :func:`program_analysis` lower a
    REPLICATED variant: wrong FLOPs/memory for MFU, and a compile-cache MISS that
    turns the analysis compile into a cold one. The loops stage blocks with the
    leading axis unsharded, so dropping the spec's first entry yields the live
    per-unit sharding exactly.
    """
    import jax
    import numpy as np

    def _leaf(a: Any) -> Any:
        shape, dtype = a.shape[1:], a.dtype
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            try:
                from jax.sharding import NamedSharding, PartitionSpec

                sharding = a.sharding
                if isinstance(sharding, NamedSharding):
                    spec = tuple(sharding.spec)
                    unit_spec = PartitionSpec(*spec[1:]) if len(spec) > 1 else PartitionSpec()
                    return jax.ShapeDtypeStruct(
                        shape, dtype, sharding=NamedSharding(sharding.mesh, unit_spec)
                    )
            except Exception:
                pass
        if isinstance(a, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(shape, dtype)
        return a

    return jax.tree_util.tree_map(_leaf, tree)


def program_analysis(
    fn: Callable,
    args: Sequence[Any],
    kwargs: Optional[Mapping[str, Any]] = None,
    *,
    compile: bool = True,
) -> Dict[str, Any]:
    """One-shot static analysis of a jitted program at the given argument shapes:
    FLOPs/bytes from XLA's cost model plus (when ``compile``) the compiled
    executable's ``memory_analysis()`` buffer sizes.

    The arguments are abstracted to avals first (see :func:`abstractify`), so
    nothing executes and donated inputs are never touched. With ``compile`` the
    lowering is backend-compiled — on a run that already compiled the same
    program this hits the in-process/persistent compile cache rather than paying
    a second cold compile; the observed compile wall time is returned either way
    (``compile_seconds``).
    """
    lowered = fn.lower(*abstractify(tuple(args)), **(kwargs or {}))
    out: Dict[str, Any] = {
        "flops": None,
        "bytes_accessed": None,
        "compile_seconds": None,
        "memory": None,
    }
    cost_src = lowered
    if compile:
        t0 = time.perf_counter()
        compiled = lowered.compile()
        out["compile_seconds"] = time.perf_counter() - t0
        cost_src = compiled
        try:
            mem = compiled.memory_analysis()
            if mem is not None:
                out["memory"] = {
                    "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                    "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                    "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                    "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
                    "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
                }
        except Exception:
            pass
    out["flops"] = compiled_flops(cost_src)
    ba = (cost_src.cost_analysis() or {}).get("bytes accessed")
    out["bytes_accessed"] = float(ba) if ba else None
    return out


def measure_mfu(
    fn: Callable,
    args: Sequence[Any],
    *,
    warmup: int = 2,
    reps: int = 5,
    device=None,
) -> Dict[str, Any]:
    """Jit ``fn``, read its FLOPs from the compiled cost model, time ``reps``
    steady-state executions, and relate the achieved FLOP/s to the chip peak.

    Returns flops_per_step / step_seconds / flops_per_sec always; ``mfu`` is None
    off-TPU (no meaningful peak) or when XLA reports no FLOPs.
    """
    import jax

    jitted = jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    flops = compiled_flops(compiled)
    for _ in range(max(1, warmup)):
        out = compiled(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = compiled(*args)
    jax.block_until_ready(out)
    step_seconds = (time.perf_counter() - t0) / reps

    if device is None:
        leaves = jax.tree_util.tree_leaves(out)
        # local_devices, not jax.devices(): the global list spans every process
        # of a multi-process run, so index 0 may be ANOTHER process's chip — a
        # non-rank-0 caller must fall back to a device it actually owns
        # (graftlint jax-devices-global-view)
        device = next(iter(leaves[0].devices())) if leaves else jax.local_devices()[0]
    peak = peak_flops(device)
    flops_per_sec = (flops / step_seconds) if flops else None
    return {
        "flops_per_step": flops,
        "step_seconds": step_seconds,
        "flops_per_sec": flops_per_sec,
        "peak_flops": peak,
        "device_kind": getattr(device, "device_kind", device.platform),
        "mfu": (flops_per_sec / peak) if (flops_per_sec and peak) else None,
    }
