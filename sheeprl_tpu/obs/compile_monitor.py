"""Process-global XLA compile accounting via ``jax.monitoring``.

Every backend compile (first trace of a jitted program, or a RECOMPILE from shape
churn) fires the ``/jax/core/compile/backend_compile_duration`` monitoring event.
A single listener — installed once per process; ``jax.monitoring`` has no
per-listener removal — accumulates count and wall seconds into a module-global
struct, and :func:`compile_snapshot` reads it. :class:`RunTelemetry` diffs
snapshots per log window to drive the ``Compile/count`` / ``Compile/seconds``
gauges and the unexpected-recompile warning.

A cold compile of a fused train program is tens of seconds to minutes
(CHANGES.md, PR 21, has the Dreamer-V3 S figure on a v5e), so an unnoticed
steady-state recompile loop is the single most expensive silent failure this
repo has; this counter is what makes it visible.
"""

from __future__ import annotations

import threading
from typing import Dict

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Fired by jax's compilation cache on a PERSISTENT-cache hit. The backend-compile
# duration event above wraps compile_or_get_cached, so a cache hit still counts
# there (with near-zero seconds) — `count - cache_hits` is the COLD compile count,
# the number the fleet runner's shared-compile-cache rollup gates on.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_state: Dict[str, float] = {"count": 0, "seconds": 0.0, "cache_hits": 0}
_installed = False


def _listener(event: str, duration_secs: float, **_kwargs) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    with _lock:
        _state["count"] += 1
        _state["seconds"] += float(duration_secs)


def _event_listener(event: str, **_kwargs) -> None:
    if event != _CACHE_HIT_EVENT:
        return
    with _lock:
        _state["cache_hits"] += 1


def install_compile_monitor() -> None:
    """Idempotently register the backend-compile duration + cache-hit listeners."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_listener)
    jax.monitoring.register_event_listener(_event_listener)


def compile_snapshot() -> Dict[str, float]:
    """Cumulative ``{"count", "seconds", "cache_hits"}`` of backend compiles seen
    so far (``count`` includes persistent-cache hits — their compile seconds are
    the cache *lookup*; ``count - cache_hits`` is the cold compiles)."""
    with _lock:
        return {
            "count": int(_state["count"]),
            "seconds": float(_state["seconds"]),
            "cache_hits": int(_state["cache_hits"]),
        }
