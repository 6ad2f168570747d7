"""Persistent XLA compilation cache policy, shared by every entry point (CLI,
serve, tests, driver hooks). The fused train programs take tens of seconds to
minutes to compile; caching them on disk lets later processes skip the compile.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module sets no
directory in code: the caller places the cache. Where it is not, the cache lives
at ONE fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored): the
directory must not move between runs, so it is never derived from ``~``, a temp
name, a pid or a time, and every process of a fleet or gang inherits the same one.

The cache is not meant to travel between machines: XLA:CPU entries are compiled
against the build host's CPU features. It stays out of git (``.gitignore``) and
out of the chip tool's copy (``.chiprunignore``)."""

from __future__ import annotations

import os
from typing import Mapping

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"
)


def cache_dir(env: Mapping[str, str] = os.environ) -> str:
    """The directory a process started with ``env`` keeps its cache in (needs no JAX)."""
    return env.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # Persistence threshold: programs compiling faster than this are not
    # written to the cache (default 1 s — sub-second CPU programs are cheaper
    # to recompile than to deserialize on a real chip). The fleet runner
    # (sheeprl_tpu/fleet) sets the env override to 0 so EVERY member program
    # persists and the sweep's later members cold-start as pure cache hits.
    try:
        min_secs = float(os.environ.get("SHEEPRL_JAX_CACHE_MIN_COMPILE_SECS", "1.0"))
    except ValueError:
        min_secs = 1.0
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    return str(jax.config.jax_compilation_cache_dir)
