"""Fleet spec: the file ``python sheeprl.py fleet <spec>`` consumes.

YAML (or JSON — YAML is a superset) with this shape::

    name: cartpole_seeds            # fleet name (fs-safe)
    base:                           # overrides every member shares
      - exp=ppo
      - env=dummy
      - env.id=discrete_dummy
      - fabric.accelerator=cpu
    sweep:                          # cartesian expansion -> members
      seed: [42, 43, 44]
    # and/or explicit members (appended after the sweep expansion):
    members:
      - name: control
        overrides: [seed=1, algo.total_steps=2048]
    max_parallel: 1                 # member slots (1 = sequential)
    stagger_first: true             # first member runs ALONE to warm the cache
    compile_cache: true             # shared persistent XLA cache for the sweep
    restarts:                       # per-member restart policy (resilience.supervisor keys)
      max_restarts: 1
      backoff: 1.0
    rank_by: sps                    # leaderboard ranking metric (telemetry summary key)
    compare:
      baseline: first               # or an explicit member name
      fail_on: null                 # null | warning | critical (CLI --fail-on overrides)
    env:                            # extra environment variables per member
      JAX_PLATFORMS: cpu            # (a null value DELETES the variable instead)

Sweep expansion: the cartesian product of the ``sweep`` axes, each member named
``key-value[_key-value...]`` (dots dropped from keys) and carrying one
``key=value`` override per axis. Member names must be unique and filesystem-safe
— they become directories under ``<fleet dir>/members/``.
"""

from __future__ import annotations

import itertools
import json
import os
import re
from typing import Any, Dict, List

FLEET_MARKER = "fleet.json"

_NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._-]*$")
_RESERVED = {"members", "gang", "checkpoint"}

_SEVERITIES = (None, "warning", "critical")


def _fs_name(raw: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]+", "-", str(raw)).strip("-") or "member"


def expand_members(spec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Members from the ``sweep`` cartesian product plus the explicit
    ``members`` list, validated (unique fs-safe names, overrides are strings)."""
    members: List[Dict[str, Any]] = []
    sweep = spec.get("sweep") or {}
    if sweep:
        axes = [(str(k), list(v)) for k, v in sweep.items()]
        for combo in itertools.product(*(values for _, values in axes)):
            overrides = [f"{key}={value}" for (key, _), value in zip(axes, combo)]
            name = "_".join(
                f"{key.replace('.', '')}-{_fs_name(value)}" for (key, _), value in zip(axes, combo)
            )
            members.append({"name": name, "overrides": overrides})
    for raw in spec.get("members") or []:
        if isinstance(raw, str):
            raise ValueError(
                f"fleet member {raw!r} must be a mapping with 'name'/'overrides' keys"
            )
        name = str(raw.get("name") or f"member{len(members)}")
        members.append({"name": name, "overrides": [str(o) for o in raw.get("overrides") or []]})
    if not members:
        raise ValueError("fleet spec produced no members (give a 'sweep' and/or 'members')")
    seen = set()
    for member in members:
        name = member["name"]
        if not _NAME_RE.match(name) or name in _RESERVED:
            raise ValueError(
                f"fleet member name {name!r} is not filesystem-safe (letters, digits, "
                f"'._-', not one of {sorted(_RESERVED)})"
            )
        if name in seen:
            raise ValueError(f"duplicate fleet member name {name!r}")
        seen.add(name)
    return members


def load_spec(path: str) -> Dict[str, Any]:
    """Load + validate a fleet spec file; returns the normalized spec with
    ``members`` fully expanded."""
    import yaml

    if not os.path.isfile(path):
        raise FileNotFoundError(f"fleet spec {path!r}: no such file")
    with open(path) as fh:
        raw = yaml.safe_load(fh)
    if not isinstance(raw, dict):
        raise ValueError(f"fleet spec {path!r} must be a mapping, got {type(raw).__name__}")
    spec = dict(raw)
    spec["name"] = _fs_name(spec.get("name") or os.path.splitext(os.path.basename(path))[0])
    spec["base"] = [str(o) for o in spec.get("base") or []]
    spec["members"] = expand_members(spec)
    spec.pop("sweep", None)
    spec["max_parallel"] = max(int(spec.get("max_parallel") or 1), 1)
    spec["stagger_first"] = bool(spec.get("stagger_first", True))
    spec["compile_cache"] = bool(spec.get("compile_cache", True))
    spec["restarts"] = dict(spec.get("restarts") or {"max_restarts": 1})
    spec["rank_by"] = str(spec.get("rank_by") or "sps")
    compare = dict(spec.get("compare") or {})
    compare.setdefault("baseline", "first")
    fail_on = compare.get("fail_on")
    if fail_on not in _SEVERITIES:
        raise ValueError(f"compare.fail_on must be one of {_SEVERITIES}, got {fail_on!r}")
    spec["compare"] = compare
    env = spec.get("env") or {}
    if not isinstance(env, dict):
        raise ValueError("fleet spec 'env' must be a mapping of environment variables")
    spec["env"] = {str(k): (None if v is None else str(v)) for k, v in env.items()}
    return spec


def write_marker(fleet_dir: str, spec: Dict[str, Any]) -> str:
    """The ``fleet.json`` marker that makes a fleet dir self-describing for
    discovery (``obs/streams.py``), ``watch``, ``diagnose`` and the rollups."""
    payload = {
        "schema": 1,
        "name": spec["name"],
        "members": {m["name"]: os.path.join("members", m["name"]) for m in spec["members"]},
        "rank_by": spec["rank_by"],
        "compare": spec["compare"],
    }
    path = os.path.join(fleet_dir, FLEET_MARKER)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    os.replace(tmp, path)
    return path


def read_marker(path: str) -> Dict[str, Any] | None:
    """The fleet marker of ``path`` (a fleet dir), or None when ``path`` is not
    a fleet dir / the marker is unreadable."""
    marker = os.path.join(str(path), FLEET_MARKER)
    if not os.path.isfile(marker):
        return None
    try:
        with open(marker) as fh:
            payload = json.load(fh)
        return payload if isinstance(payload, dict) else None
    except (OSError, ValueError):
        return None
