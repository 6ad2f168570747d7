"""The runtime/distribution layer (L8): a mesh-based replacement for Lightning Fabric.

The reference drives everything through ``lightning.fabric.Fabric`` (instantiated from
config at sheeprl/cli.py:148, strategies policed at cli.py:281-331). The TPU-native
equivalent keeps the same *user surface* (``fabric.devices``, ``strategy``,
``precision``, ``fabric.launch(main, cfg)``, ``fabric.call(...)``, ``fabric.save``)
but is built on:

- a ``jax.sharding.Mesh`` with a ``data`` axis over the selected chips — DP is sharding
  inside one jitted program (psum over ICI), not multi-process DDP;
- "ranks" = mesh devices for batch-size math (``per_rank_batch_size`` keeps meaning:
  the per-device shard), while host-process rank gates logging/checkpoint IO;
- a precision policy (param/compute dtypes) replacing AMP strings;
- callbacks (CheckpointCallback) invoked via ``fabric.call`` exactly like the reference.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.parallel import distributed


def normalize_mesh_spec(
    mesh_shape: Any, axis_names: Any
) -> "tuple[List[int], tuple[str, ...]]":
    """Canonicalize a (mesh_shape, axis_names) pair from any config container
    (tuple, list, Hydra ListConfig, a bare int) into ``([int, ...], (str, ...))``
    and validate the invariants every consumer relies on:

    - one axis name per mesh dimension, names unique;
    - at most one wildcard (``-1``) dimension, every other dimension >= 1;
    - the batch axis ``"data"`` must exist — activations are P("data") sharded
      and the per-rank batch math divides by its extent.

    The canonical form is also the FINGERPRINT form (obs/fingerprint.py): two
    configs that build the same mesh must serialize identically regardless of
    which container type carried them.
    """
    if mesh_shape is None:
        mesh_shape = [-1]
    if isinstance(mesh_shape, (int, np.integer)):
        mesh_shape = [int(mesh_shape)]
    try:
        shape = [int(s) for s in mesh_shape]
    except (TypeError, ValueError) as exc:
        raise ValueError(f"fabric.mesh_shape must be a list of ints, got {mesh_shape!r}") from exc
    if axis_names is None:
        axis_names = ["data"]
    if isinstance(axis_names, str):
        axis_names = [axis_names]
    names = tuple(str(a) for a in axis_names)
    if len(names) != len(shape):
        raise ValueError(
            f"fabric.axis_names {list(names)} must name every fabric.mesh_shape "
            f"dimension {shape} (got {len(names)} names for {len(shape)} dims)"
        )
    if len(set(names)) != len(names):
        raise ValueError(f"fabric.axis_names must be unique, got {list(names)}")
    if "data" not in names:
        raise ValueError(
            f"fabric.axis_names must include 'data' (the batch axis), got {list(names)}"
        )
    if sum(1 for s in shape if s == -1) > 1:
        raise ValueError(f"fabric.mesh_shape allows at most one -1 wildcard, got {shape}")
    if any(s == 0 or s < -1 for s in shape):
        raise ValueError(f"fabric.mesh_shape dimensions must be >= 1 (or one -1), got {shape}")
    return shape, names


@functools.lru_cache(maxsize=None)
def _announce_auto_resolution(platform: str, device_kind: str) -> None:
    """``accelerator: auto`` takes whatever backend JAX made the default; say
    which one that was, once per process (every later fabric resolves the same)."""
    if distributed.process_index() == 0:
        print(f"[sheeprl-fabric] accelerator=auto resolved to {platform} ({device_kind})")


class Fabric:
    def __init__(
        self,
        devices: int | str = 1,
        num_nodes: int = 1,
        strategy: str = "auto",
        accelerator: str = "auto",
        precision: str = "32-true",
        callbacks: Optional[Sequence[Any]] = None,
        checkpoint_backend: str = "pickle",
        checkpoint_async: bool = False,
        local_mesh: bool = False,
        mesh_shape: Any = None,
        axis_names: Any = None,
    ) -> None:
        # local_mesh=True restricts the mesh to THIS process's devices — the MPMD
        # role topology (player process / learner process run different programs on
        # their own devices); False keeps the global SPMD mesh across processes.
        # process_group (set post-init by decoupled topologies) overrides both: the
        # mesh spans the devices of THOSE processes — the learner-slice DP mesh
        # (reference trainer DDP subgroup, sheeprl/algos/ppo/ppo_decoupled.py:645-666).
        # Every process in the group must run the same jitted programs (multi-
        # controller SPMD); processes outside the group never touch this mesh.
        self.local_mesh = local_mesh
        self.process_group: Optional[Sequence[int]] = None
        self.requested_devices = devices
        # named N-D mesh request (default [-1]/["data"]: the whole selection on a
        # 1-D data axis — byte-identical to the pre-mesh_shape fabric). A "model"
        # axis turns on parameter sharding via parallel/sharding.py.
        self.mesh_shape, self.axis_names = normalize_mesh_spec(mesh_shape, axis_names)
        self.num_nodes = num_nodes
        self.strategy = strategy
        self.accelerator = accelerator
        self.precision = precision
        self.checkpoint_backend = checkpoint_backend
        self.checkpoint_async = checkpoint_async
        self._callbacks = []
        for cb in callbacks or []:
            if isinstance(cb, dict) and "_target_" in cb:
                from sheeprl_tpu.config import instantiate

                cb = instantiate(dict(cb))
            self._callbacks.append(cb)
        self._mesh: Optional[Mesh] = None
        self._launched = False

    # -- topology ------------------------------------------------------------------

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._setup()
        return self._mesh  # type: ignore[return-value]

    @property
    def devices(self) -> List[jax.Device]:
        return list(self.mesh.devices.reshape(-1))

    @property
    def world_size(self) -> int:
        """Number of devices on the ``data`` axis — the unit 'per_rank' sizes refer
        to (global batch = per_rank_batch_size x world_size, policy counters scale
        by it). On the default 1-D mesh this is every device; on a 2-D
        ``data``x``model`` mesh only the data extent — the model axis splits
        parameters, not the batch."""
        return int(self.mesh.shape.get("data", self.num_devices))

    @property
    def num_devices(self) -> int:
        """Total devices in the mesh across ALL axes (= world_size on a 1-D mesh)."""
        return int(self.mesh.devices.size)

    @property
    def model_axis_size(self) -> int:
        """Extent of the ``model`` (parameter-sharding) axis; 1 when absent."""
        return int(self.mesh.shape.get("model", 1))

    @property
    def model_parallel(self) -> bool:
        """Whether this mesh shards parameters over a non-trivial ``model`` axis."""
        return self.model_axis_size > 1

    @property
    def global_rank(self) -> int:
        """Host-process rank: gates logger/checkpoint IO (single-controller JAX)."""
        return distributed.process_index()

    @property
    def node_rank(self) -> int:
        return distributed.process_index()

    @property
    def is_global_zero(self) -> bool:
        return self.global_rank == 0

    @property
    def is_group_zero(self) -> bool:
        """Leader of this fabric's PROCESS GROUP: ``is_global_zero`` on the
        default whole-job mesh, the lowest member rank under a ``process_group``
        role split. Gates IO owned by the group rather than the job — e.g. the
        experience-service learner's checkpoints (``buffer.backend=service``),
        written by a role whose leader is not process 0."""
        if self.process_group is None:
            return self.is_global_zero
        return self.global_rank == min(self.process_group)

    @property
    def device(self) -> jax.Device:
        return self.devices[0]

    # -- precision policy ----------------------------------------------------------

    @property
    def compute_dtype(self) -> jnp.dtype:
        return jnp.bfloat16 if str(self.precision).startswith("bf16") else jnp.float32

    @property
    def param_dtype(self) -> jnp.dtype:
        return jnp.bfloat16 if str(self.precision) == "bf16-true" else jnp.float32

    # -- setup / launch ------------------------------------------------------------

    def _resolve_platform(self) -> str:
        if self.accelerator in ("auto", None):
            device = jax.devices()[0]
            _announce_auto_resolution(device.platform, device.device_kind)
            return device.platform
        if self.accelerator in ("tpu", "cpu", "gpu"):
            return self.accelerator
        raise ValueError(f"unknown accelerator {self.accelerator!r}")

    def _setup(self) -> None:
        if self.accelerator == "cpu":
            # restrict platform discovery so a cpu run never initializes (or blocks on)
            # an accelerator backend
            try:
                jax.config.update("jax_platforms", "cpu")
            except Exception:
                pass
        platform = self._resolve_platform()
        try:
            all_devices = jax.devices(platform)
        except RuntimeError as err:
            # no fallback: a request for the chip that the CPU would silently
            # serve is the failure this layer exists to surface
            raise RuntimeError(
                f"fabric.accelerator={self.accelerator!r} but JAX has no {platform!r} backend: "
                f"jax.devices() found {jax.devices()} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
            ) from err
        if self.process_group is not None:
            # A process-group mesh spans every member process; ``devices`` counts
            # devices PER PROCESS (each member contributes the same number, so the
            # mesh is n × len(group) and every member owns a local slice of it).
            group = sorted(set(self.process_group))
            if jax.process_index() not in group:
                raise RuntimeError(
                    f"process {jax.process_index()} built a process_group mesh "
                    f"{group} it does not belong to"
                )
            if len(self.mesh_shape) > 1:
                raise RuntimeError(
                    "process-group meshes are 1-D data-parallel slices (every member "
                    "process contributes the same per-process devices); a multi-axis "
                    f"fabric.mesh_shape {self.mesh_shape} is not supported there"
                )
            per = self.requested_devices
            per = None if per in ("auto", -1, "-1", None) else int(per)
            selected: List[jax.Device] = []
            for p in group:
                devs = [d for d in all_devices if d.process_index == p]
                if per is not None:
                    if per > len(devs):
                        raise RuntimeError(
                            f"requested {per} devices per process but process {p} has "
                            f"only {len(devs)} {platform} devices"
                        )
                    devs = devs[:per]
                selected.extend(devs)
            mesh_devices = np.asarray(selected)
        else:
            if self.local_mesh:
                all_devices = [d for d in all_devices if d.process_index == jax.process_index()]
            n = self.requested_devices
            n = None if n in ("auto", -1, "-1", None) else int(n)
            shape = list(self.mesh_shape)
            known = int(np.prod([s for s in shape if s != -1])) if shape else 1
            if -1 in shape:
                # the wildcard dimension absorbs the rest of the device selection:
                # fabric.devices when given, every available device otherwise
                total = n if n is not None else len(all_devices)
                if total % known != 0:
                    # a 1-device host launching e.g. the 2d-cpu preset lands here
                    # (1 % 2 != 0) — carry the simulated-mesh remedy, not just
                    # the arithmetic
                    raise RuntimeError(
                        f"fabric.mesh_shape {self.mesh_shape} cannot tile {total} devices: "
                        f"{total} is not divisible by the explicit dims' product {known}; "
                        "for CPU-simulated meshes set "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=N"
                    )
                shape[shape.index(-1)] = total // known
            else:
                # an explicit mesh shape defines the device count; fabric.devices is
                # only cross-checked (1 is the untouched config default, so a bare
                # `fabric.mesh_shape=[2,4]` override works without also setting it)
                total = known
                if n is not None and n not in (1, total):
                    raise RuntimeError(
                        f"fabric.devices={n} disagrees with fabric.mesh_shape "
                        f"{self.mesh_shape} (= {total} devices); drop one of the two "
                        "or set fabric.devices=-1"
                    )
            if total > len(all_devices):
                raise RuntimeError(
                    f"requested {total} devices but only {len(all_devices)} {platform} devices are "
                    "available; for CPU-simulated meshes set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N"
                )
            mesh_devices = np.asarray(all_devices[:total]).reshape(shape)
        self._mesh = Mesh(mesh_devices, axis_names=self.axis_names)
        # make uncommitted computations follow the selected accelerator (otherwise a
        # `fabric.accelerator=cpu` run would still trace onto a default TPU device);
        # the default must be a LOCAL device — a process_group mesh interleaves
        # other processes' devices
        local = [d for d in mesh_devices.reshape(-1) if d.process_index == jax.process_index()]
        jax.config.update("jax_default_device", (local or list(mesh_devices.reshape(-1)))[0])

    def launch(self, fn: Callable, *args: Any, **kwargs: Any) -> Any:
        """Run ``fn(self, *args)`` with the mesh set up. Unlike torch DDP there is no
        process spawn: SPMD parallelism lives inside jitted programs; multi-host runs
        are N externally-launched identical processes (jax.distributed)."""
        self._setup()
        self._launched = True
        return fn(self, *args, **kwargs)

    # -- sharding helpers ----------------------------------------------------------

    def sharding(self, *axes: Optional[str]) -> NamedSharding:
        return NamedSharding(self.mesh, P(*axes))

    @property
    def data_sharding(self) -> NamedSharding:
        """Leading-axis sharding over the data axis of the mesh."""
        return NamedSharding(self.mesh, P("data"))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_pytree(self, tree: Any) -> Any:
        """Device-put a host pytree with its leading axis sharded over ``data``."""
        return jax.device_put(tree, self.data_sharding)

    def replicate_pytree(self, tree: Any) -> Any:
        return jax.device_put(tree, self.replicated)

    def param_shardings(self, tree: Any) -> Any:
        """Per-leaf :class:`NamedSharding` tree for a parameter pytree under the
        rule module (``parallel/sharding.py``): matmul/conv kernels split over the
        ``model`` axis when divisible, everything else replicated. On a mesh
        without a non-trivial ``model`` axis every leaf is replicated — i.e. this
        degrades to :attr:`replicated` exactly. ``tree`` may hold arrays or
        ``ShapeDtypeStruct`` avals (``jax.eval_shape`` output)."""
        from sheeprl_tpu.parallel.sharding import param_sharding_tree

        return param_sharding_tree(self.mesh, tree)

    def shard_params(self, tree: Any) -> Any:
        """Device-put a parameter pytree with the rule-derived shardings
        (:meth:`param_shardings`). Identical to :meth:`replicate_pytree` on a
        mesh without a ``model`` axis."""
        return jax.device_put(tree, self.param_shardings(tree))

    def all_gather(self, tree: Any) -> Any:
        """Host-visible gather of per-device data (reference fabric.all_gather,
        used for buffer.share_data at sheeprl/algos/ppo/ppo.py:362-369 and Moments
        quantiles at dreamer_v3/utils.py:57).

        For a fully-addressable array (single-host, any mesh sharding) this
        materializes the complete logical value on the host. On a multi-host mesh the
        local process only holds its shards — materializing would silently return
        wrong data — so it raises and points at the host object channel instead.
        """

        def gather(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                raise RuntimeError(
                    "all_gather of a non-addressable (multi-host) array: use "
                    "jax.experimental.multihost_utils.process_allgather or the host "
                    "object channel (sheeprl_tpu.parallel.distributed.host_allgather_object)"
                )
            return np.asarray(x)

        return jax.tree_util.tree_map(gather, tree)

    # -- callbacks / io ------------------------------------------------------------

    def call(self, hook: str, **kwargs: Any) -> None:
        for cb in self._callbacks:
            fn = getattr(cb, hook, None)
            if fn is not None:
                fn(fabric=self, **kwargs)

    def save(self, path: str, state: Dict[str, Any]) -> None:
        """Write a checkpoint with the configured backend: ``pickle`` (default, one
        consolidated file — reference fabric.save semantics) or ``sharded`` (orbax
        directory, optionally async — the XL/pod-scale option). The backend is set
        from ``cfg.checkpoint.backend`` by the CLI."""
        # group leader, not global zero: a process_group role whose leader is not
        # process 0 (the experience-service learner) still owns ITS checkpoints
        if self.is_group_zero:
            if self.checkpoint_backend == "sharded":
                from sheeprl_tpu.utils.checkpoint import save_checkpoint_sharded

                save_checkpoint_sharded(path, state, async_save=self.checkpoint_async)
            else:
                from sheeprl_tpu.utils.checkpoint import save_checkpoint

                save_checkpoint(path, state)
        # SPMD ranks sync so nobody races ahead of the write; under an MPMD role
        # split (local_mesh) only ONE role checkpoints — a global barrier here would
        # deadlock against the other role's data-plane broadcast
        if not self.local_mesh:
            distributed.barrier("checkpoint")

    def load(self, path: str) -> Dict[str, Any]:
        from sheeprl_tpu.utils.checkpoint import load_checkpoint

        return load_checkpoint(path)

    def print(self, *args: Any, **kwargs: Any) -> None:
        if self.is_global_zero:
            print(*args, **kwargs)

    # -- misc ----------------------------------------------------------------------

    def seed_everything(self, seed: int) -> jax.Array:
        import random

        random.seed(seed)
        np.random.seed(seed)
        return jax.random.PRNGKey(seed)


def get_single_device_fabric(fabric: Fabric) -> Fabric:
    """Single-device view sharing accelerator/precision (role of
    sheeprl/utils/fabric.py:8-36). Used by player-side code that must not shard."""
    f = Fabric(
        devices=1,
        num_nodes=1,
        strategy="single_device",
        accelerator=fabric.accelerator,
        precision=fabric.precision,
        callbacks=[],
        checkpoint_backend=fabric.checkpoint_backend,
        checkpoint_async=fabric.checkpoint_async,
    )
    return f
