"""Device-resident replay ring: the on-mesh experience plane (``buffer.backend=device``).

Every host-replay off-policy loop pays one host→device round trip per
environment step (write) plus one per train round (sample + ``device_put``) —
the structural bound once train programs are fast,
and the boundary the Podracer architectures (arxiv 2104.06272) and MindSpeed RL
(arxiv 2507.19017) both erase by keeping the RL stages device-resident. This
module puts the replay buffer itself ON the mesh:

- the ring is a plain donated pytree ``{"data": {key: [capacity, n_envs, ...]},
  "pos": int32, "fill": int32}``, sharded ``P(None, "data")`` over the env axis
  on multi-device fabrics (same env-axis split as the Anakin rollout state);
- :func:`ring_write` is a pure in-program wraparound write — mod-``capacity``
  scatter at the carried cursor, cursor + fill count carried in the pytree — so
  a fused rollout can append its ``[T, E, ...]`` trajectory without the host;
- :func:`ring_sample` draws a ``[n_samples, batch, ...]`` block uniformly over
  the valid region using the Feistel :func:`~sheeprl_tpu.utils.prp.prp_permutation`
  (``utils/prp.py``): ONE O(slots) bijective index shuffle per call, so a
  full ring is sampled uniformly *without replacement* — no sort, no rejection
  loop, nothing that cannot live inside a jit.

The host-facing :class:`DeviceRingSampler` exposes the exact
``make_replay_sampler`` surface (``add`` / ``sample`` / ``lock`` / ``buffer`` /
``telemetry_snapshot`` / ``close``) over the ring, with a host
:class:`~sheeprl_tpu.data.buffers.ReplayBuffer` as the durability twin:
``sync_to_host()`` snapshots the ring into it at checkpoint cadence (cursor and
fill included, so ``rb._pos``/``rb._full`` round-trip), and
``restore_from_host()`` is the resume path — one ``device_put`` of the
snapshot back onto the mesh. ``local`` and ``service`` remain the
checkpoint-durable compatibility backends; the ring is the fused-topology hot
path (``algos/sac/anakin.py``).

Shape contract: ``capacity * n_envs`` (the slot count) must be a power of two —
that is what makes the Feistel shuffle a bijection. The capacity helper
:func:`ring_capacity` rounds a requested transition budget UP to the nearest
compliant row count, so ``buffer.size`` keeps its usual "at least this many
transitions" meaning.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Mapping, Optional, Tuple

import numpy as np

from sheeprl_tpu.utils.prp import prp_permutation

__all__ = [
    "DeviceRingSampler",
    "ring_capacity",
    "ring_init",
    "ring_sample",
    "ring_to_buffer",
    "ring_write",
    "buffer_to_ring",
]


def _next_pow2(n: int) -> int:
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def ring_capacity(size: int, n_envs: int) -> int:
    """Rows per env for a total transition budget of ``size``: at least
    ``ceil(size / n_envs)``, rounded up so ``capacity * n_envs`` is a power of
    two (the :func:`ring_sample` bijection constraint). ``n_envs`` itself must
    be a power of two — with any other env count no row count can make the slot
    count compliant."""
    if n_envs < 1 or (n_envs & (n_envs - 1)):
        raise ValueError(
            f"buffer.backend=device needs a power-of-two env count, got {n_envs}; "
            "the Feistel sampler permutes capacity*n_envs slots and a bijection "
            "needs a power-of-two domain (see howto/device_replay.md)"
        )
    rows = -(-int(size) // int(n_envs))  # ceil
    return max(_next_pow2(rows * n_envs) // n_envs, 1)


def ring_init(
    capacity: int,
    n_envs: int,
    row_specs: Mapping[str, Tuple[Tuple[int, ...], Any]],
    sharding: Any = None,
) -> Dict[str, Any]:
    """Allocate an empty ring: ``row_specs`` maps key -> (per-env trailing
    shape, dtype). ``sharding`` (a ``P(None, "data")`` NamedSharding) lands the
    storage env-sharded over the mesh at init — the donated carry then stays
    put for the life of the run."""
    import jax
    import jax.numpy as jnp

    slots = int(capacity) * int(n_envs)
    if slots < 2 or (slots & (slots - 1)):
        raise ValueError(
            f"device ring needs a power-of-two slot count (capacity*n_envs), got "
            f"{capacity}*{n_envs}={slots}; use ring_capacity() to round the budget up"
        )
    data = {
        k: jnp.zeros((int(capacity), int(n_envs), *shape), dtype=dtype)
        for k, (shape, dtype) in row_specs.items()
    }
    if sharding is not None:
        data = jax.device_put(data, sharding)
    return {"data": data, "pos": jnp.int32(0), "fill": jnp.int32(0)}


def ring_write(ring: Dict[str, Any], rows: Mapping[str, Any]) -> Dict[str, Any]:
    """Pure wraparound append of a ``[T, n_envs, ...]`` block at the carried
    cursor (jit-safe; ``T`` is static from the block shape). Oversize blocks
    keep their trailing ``capacity`` rows — the same overwrite semantics as the
    host :class:`~sheeprl_tpu.data.buffers.ReplayBuffer.add`."""
    import jax.numpy as jnp

    data = ring["data"]
    first = next(iter(rows.values()))
    steps = int(first.shape[0])
    capacity = int(next(iter(data.values())).shape[0])
    if steps > capacity:
        rows = {k: v[-capacity:] for k, v in rows.items()}
        steps = capacity
    idx = (ring["pos"] + jnp.arange(steps, dtype=jnp.int32)) % capacity
    new_data = {k: data[k].at[idx].set(rows[k].astype(data[k].dtype)) for k in data}
    return {
        "data": new_data,
        "pos": (ring["pos"] + steps) % capacity,
        "fill": jnp.minimum(ring["fill"] + steps, capacity),
    }


def ring_sample(
    ring: Dict[str, Any], key: Any, batch_size: int, n_samples: int = 1
) -> Dict[str, Any]:
    """Uniform ``[n_samples, batch_size, ...]`` draw over the valid region.

    One Feistel permutation of ALL ``capacity * n_envs`` slots per call, of
    which the first ``n_samples * batch_size`` entries are taken and folded
    into the filled region by a modulo. On a full ring the fold is the
    identity, so the draw is exactly uniform **without replacement** (a
    bijection of the slot space); during the fill ramp each filled slot is hit
    with multiplicity within ±1 of uniform. Draws larger than the slot count
    wrap around the permutation (with-replacement across wraps)."""
    import jax.numpy as jnp

    data = ring["data"]
    ref = next(iter(data.values()))
    capacity, n_envs = int(ref.shape[0]), int(ref.shape[1])
    slots = capacity * n_envs
    n = int(n_samples) * int(batch_size)
    if n <= 0:
        raise ValueError(f"n_samples*batch_size must be > 0, got {n}")
    perm = prp_permutation(key, slots)
    flat_idx = perm[jnp.arange(n) % slots]
    # valid slots are the first fill*n_envs of the row-major flat layout: before
    # the first wrap pos == fill (prefix rows), after it fill == capacity (all)
    valid = jnp.maximum(ring["fill"], 1) * n_envs
    flat_idx = (flat_idx % valid).astype(jnp.int32)
    out: Dict[str, Any] = {}
    for k, v in data.items():
        flat = v.reshape(slots, *v.shape[2:])
        taken = jnp.take(flat, flat_idx, axis=0)
        out[k] = taken.reshape(int(n_samples), int(batch_size), *v.shape[2:])
    return out


def ring_to_buffer(ring: Dict[str, Any], rb: Optional[Any] = None) -> Any:
    """Snapshot the ring into a host :class:`ReplayBuffer` (ONE device→host pull
    per key) with the write cursor and fill state mapped onto ``rb._pos`` /
    ``rb._full`` — the checkpoint-durability bridge: the snapshot pickles
    through the existing ``_ckpt_rb`` protocol exactly like a host-replay run."""
    from sheeprl_tpu.data.buffers import ReplayBuffer

    data = {k: np.asarray(v) for k, v in ring["data"].items()}
    ref = next(iter(data.values()))
    capacity, n_envs = int(ref.shape[0]), int(ref.shape[1])
    if rb is None:
        rb = ReplayBuffer(capacity, n_envs, obs_keys=("observations",), memmap=False)
    fill = int(ring["fill"])
    rb._buf = {k: v.copy() for k, v in data.items()}
    rb._pos = int(ring["pos"])
    rb._full = fill >= capacity
    return rb


def buffer_to_ring(rb: Any, sharding: Any = None) -> Dict[str, Any]:
    """Resume path: ``device_put`` a host :class:`ReplayBuffer` snapshot back
    onto the mesh as a ring, cursor and fill intact."""
    import jax
    import jax.numpy as jnp

    data = {k: np.asarray(v) for k, v in rb.buffer.items()}
    if sharding is not None:
        data = jax.device_put(data, sharding)
    else:
        data = {k: jnp.asarray(v) for k, v in data.items()}
    capacity = int(rb.buffer_size)
    fill = capacity if rb.full else int(rb._pos)
    return {"data": data, "pos": jnp.int32(int(rb._pos) % capacity), "fill": jnp.int32(fill)}


class DeviceRingSampler:
    """``buffer.backend=device`` behind the ``make_replay_sampler`` surface.

    The replay storage is the device ring; the wrapped host
    :class:`ReplayBuffer` is only the durability twin (checkpoint snapshot /
    resume restore). ``add``/``sample`` run as small jitted device programs —
    useful for tests and non-fused loops; the fused ``sac_anakin`` topology
    bypasses them entirely by carrying ``self.ring`` through its own donated
    program and rebinding it (:attr:`ring` is plain mutable state).
    """

    is_async = False

    def __init__(
        self,
        rb: Any,
        sample_kwargs: Optional[Mapping[str, Any]] = None,
        sharding: Any = None,
        lock: Optional[threading.Lock] = None,
        seed: int = 0,
        **_: Any,
    ) -> None:
        import jax

        self._rb = rb
        self._sample_kwargs = dict(sample_kwargs or {})
        self._sample_kwargs.pop("n_samples", None)
        if self._sample_kwargs.pop("sample_next_obs", False):
            raise ValueError(
                "buffer.backend=device stores next_observations explicitly; "
                "sample_next_obs=True is a host-replay feature (buffer.sample_next_obs=False)"
            )
        self._batch_size = int(self._sample_kwargs.pop("batch_size"))
        self._sharding = sharding
        self.lock = lock or threading.Lock()
        self.ring: Optional[Dict[str, Any]] = None
        self._key = jax.random.PRNGKey(seed)
        self._write = jax.jit(ring_write, donate_argnums=(0,))
        self._sample = jax.jit(ring_sample, static_argnames=("batch_size", "n_samples"))
        self._tele_wait_seconds = 0.0
        self._tele_sample_calls = 0
        self._tele_units = 0
        self._tele_rows_written = 0
        if not rb.empty:
            # a restored (resume_from) buffer re-lands on the mesh immediately
            self.ring = buffer_to_ring(rb, sharding=sharding)

    # -- sampler surface ---------------------------------------------------------------

    @property
    def buffer(self) -> Any:
        return self._rb

    def add(self, data: Mapping[str, Any], *args: Any, **kwargs: Any) -> None:
        rows = {k: np.asarray(v) for k, v in data.items()}
        if self.ring is None:
            first = next(iter(rows.values()))
            n_envs = int(first.shape[1])
            specs = {k: (tuple(v.shape[2:]), v.dtype) for k, v in rows.items()}
            self.ring = ring_init(self._rb.buffer_size, n_envs, specs, sharding=self._sharding)
        self.note_writes(int(next(iter(rows.values())).shape[0]))
        self.ring = self._write(self.ring, rows)

    def note_writes(self, steps: int) -> None:
        """Account ``steps`` ring rows written. ``add`` self-accounts; the fused
        topologies that bypass it (``sac_anakin`` carries the ring through its
        own donated program and rebinds :attr:`ring`) call this once per
        iteration so the overwrite gauge stays honest — pure host bookkeeping,
        no device sync."""
        self._tele_rows_written += max(int(steps), 0)

    def sample(self, n_samples: int) -> Dict[str, Any]:
        import jax
        import time

        if self.ring is None:
            raise RuntimeError("No sample has been added to the device ring; call add() first")
        t0 = time.perf_counter()
        self._key, sample_key = jax.random.split(self._key)
        block = self._sample(
            self.ring, sample_key, batch_size=self._batch_size, n_samples=int(n_samples)
        )
        self._tele_wait_seconds += time.perf_counter() - t0
        self._tele_sample_calls += 1
        self._tele_units += int(n_samples)
        return block

    def telemetry_snapshot(self) -> Dict[str, Any]:
        """Same schema as the host samplers' — the sync-path semantics apply
        (the consumer blocks for the full sample dispatch) — plus the ring
        storage gauges: ``ring_fill``/``ring_capacity`` (occupancy in rows) and
        the cumulative ``ring_overwritten`` slot count (rows written past
        capacity × envs — experience lost to wraparound). Reading ``fill``
        costs one device sync; this runs at telemetry-window cadence, not on
        the hot path."""
        snap = {
            "is_async": False,
            "wait_seconds": self._tele_wait_seconds,
            "sample_calls": self._tele_sample_calls,
            "units": self._tele_units,
            "occupancy_sum": 0.0,
            "staleness_sum": 0.0,
            "empty_waits": 0,
            "pipeline_len": 0,
            "depth": 0,
            "ring_fill": 0,
            "ring_capacity": 0,
            "ring_overwritten": 0,
        }
        if self.ring is not None:
            ref = next(iter(self.ring["data"].values()))
            capacity, n_envs = int(ref.shape[0]), int(ref.shape[1])
            snap["ring_fill"] = int(self.ring["fill"])
            snap["ring_capacity"] = capacity
            snap["ring_overwritten"] = max(self._tele_rows_written - capacity, 0) * n_envs
        return snap

    def close(self) -> None:
        pass

    def __enter__(self) -> "DeviceRingSampler":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # -- durability bridge -------------------------------------------------------------

    def sync_to_host(self) -> Any:
        """Snapshot the live ring into the wrapped host buffer (checkpoint
        cadence); returns the buffer for the checkpoint callback."""
        if self.ring is not None:
            ring_to_buffer(self.ring, self._rb)
        return self._rb

    def restore_from_host(self) -> None:
        """Re-land the host snapshot on the mesh (resume path)."""
        if not self._rb.empty:
            self.ring = buffer_to_ring(self._rb, sharding=self._sharding)
