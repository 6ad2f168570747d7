"""Core math + run utilities, JAX-native.

Re-provides the reference's math toolbox (sheeprl/utils/utils.py) with XLA-friendly
implementations: GAE is a ``lax.scan`` over reversed time instead of a Python loop
(reference: utils.py:63-100), twohot encode/decode use vectorized searchsorted/scatter
(reference: utils.py:156-207), and the replay-ratio governor ``Ratio`` keeps identical
host-side semantics (reference: utils.py:266-319).
"""

from __future__ import annotations

import copy
import os
import warnings
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.config.dotdict import dotdict
from sheeprl_tpu.utils.timer import timer


# ---------------------------------------------------------------------------------
# symlog / symexp (Dreamer-V3 eq. 10)
# ---------------------------------------------------------------------------------
def symlog(x: jax.Array) -> jax.Array:
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x: jax.Array) -> jax.Array:
    return jnp.sign(x) * (jnp.expm1(jnp.abs(x)))


# ---------------------------------------------------------------------------------
# twohot encoding (Dreamer-V3 eq. 9) — semantics match reference utils.py:156-207
# ---------------------------------------------------------------------------------
def two_hot_encoder(x: jax.Array, support_range: int = 300, num_buckets: Optional[int] = None) -> jax.Array:
    """Encode scalars (..., 1) into twohot vectors (..., num_buckets) over a symmetric
    linear support [-support_range, support_range]."""
    if x.ndim == 0:
        x = x[None]
    if num_buckets is None:
        num_buckets = support_range * 2 + 1
    if num_buckets % 2 == 0:
        raise ValueError("support_size must be odd")
    x = jnp.clip(x, -support_range, support_range)
    buckets = jnp.linspace(-support_range, support_range, num_buckets, dtype=x.dtype)
    bucket_size = (buckets[1] - buckets[0]) if num_buckets > 1 else jnp.asarray(1.0, x.dtype)

    right_idxs = jnp.searchsorted(buckets, x, side="left")
    left_idxs = jnp.clip(right_idxs - 1, 0, num_buckets - 1)
    right_idxs = jnp.clip(right_idxs, 0, num_buckets - 1)

    left_value = jnp.abs(buckets[right_idxs] - x) / bucket_size
    right_value = 1.0 - left_value

    left_oh = jax.nn.one_hot(left_idxs[..., 0], num_buckets, dtype=x.dtype)
    right_oh = jax.nn.one_hot(right_idxs[..., 0], num_buckets, dtype=x.dtype)
    return left_oh * left_value + right_oh * right_value


def two_hot_decoder(t: jax.Array, support_range: int) -> jax.Array:
    num_buckets = t.shape[-1]
    if num_buckets % 2 == 0:
        raise ValueError("support_size must be odd")
    support = jnp.linspace(-support_range, support_range, num_buckets, dtype=t.dtype)
    return jnp.sum(t * support, axis=-1, keepdims=True)


# ---------------------------------------------------------------------------------
# GAE — lax.scan over reversed time (reference python loop: utils.py:92-98)
# ---------------------------------------------------------------------------------
def gae(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    next_value: jax.Array,
    num_steps: int,
    gamma: float,
    gae_lambda: float,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (returns, advantages), shapes like ``rewards`` ([T, B, ...]).

    ``dones[t]`` flags termination *at* step t; the bootstrap value for the last step is
    ``next_value`` masked by ``1 - dones[-1]`` — identical recursion to the reference.
    """
    dtype = rewards.dtype
    not_dones = 1.0 - dones.astype(dtype)
    values = values.astype(dtype)
    next_values = jnp.concatenate([values[1:], next_value[None].astype(dtype)], axis=0)

    def step(carry, inp):
        lastgaelam = carry
        reward, value, next_val, nonterminal = inp
        delta = reward + gamma * next_val * nonterminal - value
        lastgaelam = delta + gamma * gae_lambda * nonterminal * lastgaelam
        return lastgaelam, lastgaelam

    init = jnp.zeros_like(rewards[0])
    _, adv_rev = jax.lax.scan(
        step,
        init,
        (rewards[::-1], values[::-1], next_values[::-1], not_dones[::-1]),
    )
    advantages = adv_rev[::-1]
    returns = advantages + values
    return returns, advantages


# ---------------------------------------------------------------------------------
# lambda returns (Dreamer) — scan form of the reversed loop
# ---------------------------------------------------------------------------------
def compute_lambda_values(
    rewards: jax.Array,
    values: jax.Array,
    continues: jax.Array,
    lmbda: float = 0.95,
) -> jax.Array:
    """TD(lambda) returns over an imagined trajectory — exact recursion of the
    reference's ``compute_lambda_values`` (sheeprl/algos/dreamer_v3/utils.py:67-78):
    ``ret[t] = r[t] + c[t] * ((1-lambda) * v[t] + lambda * ret[t+1])`` with carry
    initialized at ``v[T-1]``. Callers pass the inputs already shifted the way the
    reference does (rewards[1:], values[1:], continues[1:] * gamma).

    Return accumulation runs in float32 regardless of the compute precision (the
    same spirit as the reference's GAE-in-float64, ppo.py:350): it is a tiny
    tensor, the recursion compounds rounding over the horizon, and mixed
    bf16/fp32 inputs would otherwise break the scan's carry-type invariant."""
    rewards = rewards.astype(jnp.float32)
    values = values.astype(jnp.float32)
    continues = continues.astype(jnp.float32)
    interm = rewards + continues * values * (1 - lmbda)

    def step(carry, inp):
        ret = carry
        interm_t, cont_t = inp
        ret = interm_t + cont_t * lmbda * ret
        return ret, ret

    _, lv_rev = jax.lax.scan(step, values[-1], (interm[::-1], continues[::-1]))
    return lv_rev[::-1]


# ---------------------------------------------------------------------------------
# misc numerics
# ---------------------------------------------------------------------------------
def epoch_permutation(
    key: jax.Array,
    num_rows: int,
    world_size: int,
    share_data: bool,
    minibatch_size: Optional[int] = None,
) -> jax.Array:
    """Row-visit order for one optimization epoch over a ``data``-axis-sharded rollout.

    The TPU-native reading of the reference's ``buffer.share_data`` switch
    (sheeprl/algos/ppo/ppo.py:40-50,362-369): with ``share_data`` each rank optimizes a
    shard of the *globally shuffled* rollout (reference: ``fabric.all_gather`` +
    ``DistributedSampler``) — here a global permutation whose gathers XLA turns into
    ICI collectives; without it every device samples only its own rows (reference:
    ``RandomSampler`` on local data) — here a per-shard permutation, so minibatch
    gathers can stay device-local and no collective is needed for the data plane.

    Rows MUST be laid out contiguous per device shard — i.e. the flat axis carries a
    plain leading-axis ``P("data")`` sharding, shard ``s`` owning rows
    ``[s*rows_per_shard, (s+1)*rows_per_shard)``. (PPO flattens its ``(T, E)`` rollout
    env-major — ``swapaxes(0, 1)`` before the reshape — precisely so the env-axis
    sharding becomes this contiguous block layout.)

    When ``minibatch_size`` is given (and divisible by ``world_size`` with
    ``num_rows`` a multiple of it), each consecutive ``minibatch_size`` slice of the
    returned order is arranged as per-shard contiguous blocks
    ``[shard0 rows | shard1 rows | ...]`` — gathering such a minibatch from the
    block-sharded operand leaves each output block on the shard that owns its rows,
    so the take requires no cross-device movement. Otherwise the shards are
    interleaved cyclically (position ``i`` belongs to shard ``i % world_size``),
    which still draws equally from every shard per slice.
    """
    if share_data or world_size == 1 or num_rows % world_size != 0:
        return jax.random.permutation(key, num_rows)
    rows_per_shard = num_rows // world_size
    keys = jax.random.split(key, world_size)
    local = jnp.stack(
        [jax.random.permutation(k, rows_per_shard) for k in keys]
    ) + jnp.arange(world_size)[:, None] * rows_per_shard
    if (
        minibatch_size is not None
        and minibatch_size % world_size == 0
        and num_rows % minibatch_size == 0
    ):
        num_minibatches = num_rows // minibatch_size
        block = minibatch_size // world_size
        return local.reshape(world_size, num_minibatches, block).transpose(1, 0, 2).reshape(-1)
    return local.T.reshape(-1)


@jax.jit
def _pack_leaves(leaves):
    return jnp.concatenate([jnp.asarray(x).reshape(-1) for x in leaves])


def packed_device_get(tree: Any) -> Any:
    """Fetch a device pytree to host numpy with ONE transfer per dtype group.

    ``jax.device_get`` issues one device→host transfer per leaf, so a ~60-leaf
    params tree is ~60 transfers. Packing all leaves into a single flat device
    array first makes it one per distinct dtype (usually one). On an attached
    v5e chip the pack, the wait and the copy of Dreamer-V3 XL's 785 MB act view
    took 255 ms and L's 509 MB 154 ms (``act_view_sync_ms``, ledger, PR 27):
    right for a few values (metrics, a key), too dear for a big tree every
    train call, which is why the coupled Dreamer-V3 loop no longer fetches its
    view at all (:class:`ActPlacement`).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    out: list = [None] * len(leaves)
    by_dtype: Dict[Any, list] = {}
    for i, x in enumerate(leaves):
        if isinstance(x, np.ndarray) or np.isscalar(x):
            out[i] = np.asarray(x)
        else:
            by_dtype.setdefault(jnp.asarray(x).dtype, []).append(i)
    for idxs in by_dtype.values():
        flat = np.asarray(_pack_leaves([leaves[i] for i in idxs]))
        off = 0
        for i in idxs:
            size = int(np.prod(np.shape(leaves[i])))
            out[i] = flat[off : off + size].reshape(np.shape(leaves[i]))
            off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def host_cpu_device() -> jax.Device:
    """This process's host CPU device, for the programs that are placed there
    (act steps, host-stepped jax envs) while the train program runs on the
    accelerator. They need the CPU backend NEXT TO the accelerator's: a process
    started with ``JAX_PLATFORMS=tpu`` has none, and that is an error here, not
    a reason to move the work."""
    try:
        # local_devices: jax.devices() spans ALL processes of a multi-process run,
        # and a non-rank-0 role (a service actor) must pin ITS host device
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as err:
        raise RuntimeError(
            "the act path and the host-stepped envs run on the host CPU backend, which "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} leaves out: "
            "list it after the accelerator (JAX_PLATFORMS=tpu,cpu) or leave the variable unset"
        ) from err


class ActPlacement:
    """Where the per-step act program runs, and how the player's parameters get
    there. Shared by every per-step-acting algorithm; three placements:

    - **CPU fabric**: everything is the identity, so call sites need no branching.
    - **host** (an accelerator fabric, the default): the one-frame act program runs
      on the host CPU backend, next to the env it feeds, while the fused train
      program runs on the accelerator; the player-visible subtree (``select``) is
      packed on the device, fetched and placed on the CPU device once per train
      call. Every loop but the coupled Dreamer-V3 one acts this way, and so do the
      channel-backed Dreamer-V3 trainers, whose view arrives as host bytes. For a
      small player next to its env nothing measures the alternative (PERF.md
      section 7).
    - **aliased** (:meth:`alias_device`): the view is ``select(params)`` itself,
      the trainer's own device buffers, and keys stay where JAX made them, beside
      those buffers on the one-device fabric's device, so the player's programs
      compile for the accelerator and follow their arguments. Nothing is packed,
      fetched, placed or committed: one committed argument among uncommitted ones
      commits a jit's outputs, and the player's carry then compiles each of its
      programs a second time, the reset in mid-run (seen on the chip, PERF.md
      section 6, PR 28). Only a caller whose train program and player run one
      after the other on one thread, and which rebinds its view from every train
      call, may ask for it (a donated tree must never be read again):
      ``run_dreamer`` with an inline trainer on a one-device fabric. There the
      host placement cost Dreamer-V3 XL about 590 ms of a 990 ms cycle (ledger,
      PR 27); what the aliased one costs is in the ledger's PR 28 lines.
    """

    def __init__(self, fabric, select: Optional[Callable[[Any], Any]] = None) -> None:
        self.on_cpu = fabric.device.platform != "cpu"
        self.aliased = False
        self.cpu_device = host_cpu_device()
        self._select = select or (lambda p: p)

    def alias_device(self) -> None:
        """Act on the fabric's device, on the caller's own parameter buffers (the
        class docstring says who may). On a CPU fabric this changes nothing."""
        if self.on_cpu:
            self.on_cpu, self.aliased = False, True

    def view(self, params: Any) -> Any:
        """The player-visible act params: ``select(params)``, landed host-side
        unless aliased. The counter ``act_view_bytes`` counts the bytes COPIED.

        Note ``select`` narrows the tree on EVERY fabric, CPU included — a test()
        path that reads keys outside the act view would break identically on all
        placements, rather than only when an accelerator is attached."""
        view = self._select(params)
        if self.aliased:
            with timer("act_view"):  # kept: `act_first_use_ms` and the trace reader look for it
                timer.count("act_view_bytes", 0)
                return view
        if not self.on_cpu:
            return view
        # packed_device_put, spelled out so that its two halves are spans of the view
        # alone (place() moves keys the same way and opens none): the two lines below
        # must stay equal to packed_device_put's two
        with timer("act_view"):
            with timer("act_view.fetch"):  # pack on the device, wait for it, copy to the host
                host = packed_device_get(view)
            if not timer.disabled:
                timer.count("act_view_bytes", sum(x.nbytes for x in jax.tree_util.tree_leaves(host)))
            with timer("act_view.place"):  # one device_put a leaf onto the host CPU backend
                return jax.tree_util.tree_map(lambda x: jax.device_put(x, self.cpu_device), host)

    def place(self, tree: Any) -> Any:
        """Land an arbitrary pytree (PRNG key, frozen exploration params) host-side
        so the act program's dispatch and key chain never touch the accelerator.
        Aliased, the tree stays as it is: on the fabric's device, uncommitted."""
        return packed_device_put(tree, self.cpu_device) if self.on_cpu else tree


def packed_device_put(tree: Any, device: jax.Device) -> Any:
    """Move a pytree onto ``device`` with one bulk transfer off the source device
    (see :func:`packed_device_get`), then cheap local placements onto the target."""
    host = packed_device_get(tree)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, device), host)


def normalize_tensor(x: jax.Array, eps: float = 1e-8, mask: Optional[jax.Array] = None) -> jax.Array:
    if mask is None:
        return (x - x.mean()) / (x.std() + eps)
    n = jnp.maximum(mask.sum(), 1)
    mean = jnp.sum(x * mask) / n
    var = jnp.sum(jnp.square(x - mean) * mask) / n
    return (x - mean) / (jnp.sqrt(var) + eps)


def polynomial_decay(
    current_step: int,
    *,
    initial: float = 1.0,
    final: float = 0.0,
    max_decay_steps: int = 100,
    power: float = 1.0,
) -> float:
    if current_step > max_decay_steps or initial == final:
        return final
    return (initial - final) * ((1 - current_step / max_decay_steps) ** power) + final


class Ratio:
    """Replay-ratio governor: decides how many gradient steps to run per batch of new
    env steps (identical host-side semantics to reference utils.py:266-319)."""

    def __init__(self, ratio: float, pretrain_steps: int = 0):
        if pretrain_steps < 0:
            raise ValueError(f"'pretrain_steps' must be non-negative, got {pretrain_steps}")
        if ratio < 0:
            raise ValueError(f"'ratio' must be non-negative, got {ratio}")
        self._pretrain_steps = pretrain_steps
        self._ratio = ratio
        self._prev: Optional[float] = None

    def __call__(self, step: int) -> int:
        if self._ratio == 0:
            return 0
        if self._prev is None:
            self._prev = step
            repeats = int(step * self._ratio)
            if self._pretrain_steps > 0:
                if step < self._pretrain_steps:
                    warnings.warn(
                        "The number of pretrain steps is greater than the number of current steps. "
                        f"This could lead to a higher ratio than the one specified ({self._ratio}). "
                        "Setting the 'pretrain_steps' equal to the number of current steps."
                    )
                    self._pretrain_steps = step
                repeats = int(self._pretrain_steps * self._ratio)
            return repeats
        repeats = int((step - self._prev) * self._ratio)
        self._prev += repeats / self._ratio
        return repeats

    def state_dict(self) -> Dict[str, Any]:
        return {"_ratio": self._ratio, "_prev": self._prev, "_pretrain_steps": self._pretrain_steps}

    def load_state_dict(self, state: Mapping[str, Any]) -> "Ratio":
        self._ratio = state["_ratio"]
        self._prev = state["_prev"]
        self._pretrain_steps = state["_pretrain_steps"]
        return self


# ---------------------------------------------------------------------------------
# config helpers
# ---------------------------------------------------------------------------------
def print_config(cfg: Mapping[str, Any]) -> None:
    try:
        import yaml
        from rich.syntax import Syntax
        from rich.console import Console

        text = yaml.safe_dump(cfg.as_dict() if isinstance(cfg, dotdict) else dict(cfg), sort_keys=False)
        Console().print(Syntax(text, "yaml", theme="ansi_dark"))
    except Exception:
        import pprint

        pprint.pprint(cfg)


def save_configs(cfg: dotdict, log_dir: str) -> None:
    import yaml

    os.makedirs(log_dir, exist_ok=True)
    with open(os.path.join(log_dir, "config.yaml"), "w") as f:
        yaml.safe_dump(cfg.as_dict(), f, sort_keys=False)


def copy_cfg(cfg: dotdict) -> dotdict:
    return dotdict(copy.deepcopy(cfg.as_dict()))


def foreach_gradient_step(train_step, state, data, train_key, cum_steps=None):
    """Drive a jitted single-gradient-step program over a ``[G, ...]`` replay block
    with a host loop.

    This is the Dreamer-family training-phase harness (the role of the reference's
    per-gradient-step python loop, sheeprl/algos/dreamer_v3/dreamer_v3.py:741-783) —
    but around ONE fused XLA program per step instead of three torch.compile regions.
    A host loop beats an outer ``lax.scan`` over G here for two measured reasons:
    (a) ~3.6x faster steady-state on XLA CPU — scan-carried params/opt-state force
    layout copies and block fusion across the while-loop body; (b) the Ratio governor
    produces varying ``per_rank_gradient_steps``, and a scanned program recompiles for
    every distinct G (~45 s each on the benchmark model) while the single-step
    program compiles once.

    ``train_step`` takes ``(*state, batch, key)`` — or ``(*state, batch, cum, key)``
    when ``cum_steps`` is given — and returns ``(*new_state, metrics)``.
    Returns ``(*final_state, mean_metrics)``.

    Each step's call of ``train_step`` is the span ``train_dispatch.call``; the key
    split, the slices and the step counter are made outside it.
    """
    G = int(jax.tree_util.tree_leaves(data)[0].shape[0])
    if G == 0:
        raise ValueError("foreach_gradient_step needs a non-empty [G, ...] block (G >= 1)")
    keys = jax.random.split(jnp.asarray(train_key), G)
    cum = None if cum_steps is None else int(cum_steps)
    state = tuple(state)
    all_metrics = []
    for g in range(G):
        batch = jax.tree_util.tree_map(lambda a: a[g], data)
        args = (batch, keys[g]) if cum is None else (batch, jnp.asarray(cum + g), keys[g])
        with timer("train_dispatch.call"):
            *state, metrics = train_step(*state, *args)
        all_metrics.append(metrics)
    if len(all_metrics) > 1:
        metrics = jax.tree_util.tree_map(lambda *ms: jnp.stack(ms).mean(), *all_metrics)
    else:
        metrics = all_metrics[0]
    return (*state, metrics)


class BenchWindow:
    """Steady-state wall-clock window for bench.py: starts timing once the policy
    step passes SHEEPRL_BENCH_STEADY_START (set past warmup+compile) and writes
    {steps, seconds} to SHEEPRL_BENCH_STEADY_FILE at the end of the run. Inactive
    (zero overhead beyond two attribute checks per iteration) when the env vars are
    unset. Shared by the Dreamer-family training loops."""

    def __init__(self) -> None:
        self.file = os.environ.get("SHEEPRL_BENCH_STEADY_FILE")
        self.start_step = int(os.environ.get("SHEEPRL_BENCH_STEADY_START", "0"))
        self._t0: Optional[float] = None
        self._step0 = 0

    def maybe_start(self, policy_step: int, sync_tree: Any = None) -> None:
        if self.file and self._t0 is None and policy_step >= self.start_step:
            import time

            if sync_tree is not None:
                jax.block_until_ready(sync_tree)
            self._t0 = time.perf_counter()
            self._step0 = policy_step

    def finish(self, policy_step: int, sync_tree: Any = None) -> None:
        if self.file and self._t0 is not None:
            import json
            import time

            if sync_tree is not None:
                jax.block_until_ready(sync_tree)
            with open(self.file, "w") as f:
                json.dump(
                    {"steps": policy_step - self._step0, "seconds": time.perf_counter() - self._t0},
                    f,
                )
