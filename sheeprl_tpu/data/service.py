"""Standalone experience data-plane service: N actors feed, M learners sample.

Every decoupled topology before this module coupled acting and learning
one-to-one: the player samples its OWN replay buffer and blocks on the learner's
round (``BroadcastChannel`` lockstep alternation), so actor cores idle while the
learner's fused train program runs — the structural bound once
train programs are fast. MindSpeed RL (arxiv 2507.19017) argues the unit of
production RL is a fleet with a shared distributed dataflow, and the Podracer
architectures (arxiv 2104.06272) fill accelerators by decoupling actor and
learner pools. This module is that dataflow, built on the machinery already in
the tree:

- **Transport** is the jax.distributed coordination-service KV store — the same
  gRPC object plane the decoupled channels (``parallel/distributed.py``) and the
  distributed-resilience control plane (``resilience/distributed.py``) ride.
  Unlike the lockstep channels, ingestion is **append-only and asynchronous**:
  each actor writes sequence-numbered row blocks under its own keyspace, the
  service drains all actor streams at its own pace, and a learner's slow round
  never blocks an actor (until the bounded ``max_inflight`` watermark).
- **Liveness** reuses the PR 6 hooks: every blocking wait here runs in
  ``poll_s`` slices with the resilience layer's ``abort_check`` between slices
  (a declared-dead peer raises ``RankFailureError`` instead of hanging) and a
  hard ``timeout_s`` deadline (``ServiceTimeout``).
- **Learner-side sampling is unchanged**: the service feeds an ordinary replay
  buffer that ``make_replay_sampler`` (``data/prefetch.py``) samples and stages
  exactly as the in-process path does — sharded staging, prefetch pipeline,
  donation downstream all untouched. ``buffer.backend=local`` (the default)
  bypasses this module entirely.

Wire protocol (namespace ``ns``, all keys GC'd by their consumer):

==============================  ==================================================
``{ns}/ing/a{r}/{seq}/c{i}``    chunked pickled ingest message ``i`` of actor r
``{ns}/ing/a{r}/{seq}/n``       chunk count — written LAST, so its presence
                                means the whole message exists
``{ns}/ing/pub/r{r}``           actor r's latest published seq (one dir-get
                                tells the service every stream's frontier)
``{ns}/ing/ack/r{r}``           service's consumed frontier for actor r (the
                                writer's flow-control watermark)
``{ns}/ing/eos/r{r}``           actor r closed its stream (JSON: rows, steps,
                                preempted)
``{ns}/w/{v}/c{i}``, ``.../n``  weight payload version v (immutable once
                                written; versions <= v-2 GC'd by the publisher)
``{ns}/w/latest``               latest committed weight version
``{ns}/done``                   the learner finished (actors may exit)
==============================  ==================================================

Each ingest message carries ``{"rank", "seq", "env_ids", "steps", "rows",
"born", "weight_version"}`` — rank/stream-tagged provenance the service folds
into per-actor counters (and the buffer's env slots, keyed by the actor's env
ids), so a fleet of actors is attributable end-to-end. The last two fields are
the dataflow LINEAGE this plane's observability rides on (howto/observability.md
"Tracing the dataflow"): ``born`` is the wall-clock time the message's oldest
row left the env (ingest latency = drain time − born), and ``weight_version``
is the version the acting actor held when it produced the rows — the learner
derives per-actor weight LAG from it, and the :class:`_AgeBook` turns the
(rows, born) trail into the sampled-row age distribution (seconds and
add-rounds) a uniform replay draw would see.

For single-process unit tests :class:`LocalKV` implements the same surface over
a dict + condition variable; ``tests/test_data/test_service.py`` drives the
writer/service/weight plane against it without ``jax.distributed``.
"""

from __future__ import annotations

import json
import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "ActorDataflow",
    "ExperienceService",
    "ExperienceWriter",
    "LearnerDataflow",
    "LocalKV",
    "ServiceError",
    "ServiceTimeout",
    "WeightPublisher",
    "WeightSubscriber",
    "clear_local_service_plane",
    "coordination_kv",
    "install_local_service_plane",
    "service_layout",
    "service_namespace",
    "service_options",
]

_KV_CHUNK = 2 * 1024 * 1024  # stay under gRPC message-size defaults


class ServiceError(RuntimeError):
    """An experience-service operation failed (transport error, closed peer)."""


class ServiceTimeout(ServiceError):
    """A bounded service wait exhausted its deadline — the peer is slow, hung,
    or dead (liveness failures surface separately via ``abort_check``)."""


# ---------------------------------------------------------------------------------
# KV plane: one surface over the coordination-service client and the local fake
# ---------------------------------------------------------------------------------


class CoordinationKV:
    """The jax.distributed coordination-service KV store behind the one surface
    the service machinery speaks. Get methods are non-blocking probes (a missing
    key returns None); the callers own deadlines and abort checks."""

    def __init__(self, client: Any) -> None:
        self._client = client

    @staticmethod
    def _is_missing(exc: BaseException) -> bool:
        # the jaxlib client surfaces status only in the message text; a tiny
        # blocking-get deadline expiring means "not there yet"
        text = str(exc).upper()
        return (
            "DEADLINE" in text or "TIMED OUT" in text or "TIMEOUT" in text or "NOT_FOUND" in text
        )

    def set(self, key: str, value: str) -> None:
        self._client.key_value_set(key, value, allow_overwrite=True)

    def set_bytes(self, key: str, value: bytes) -> None:
        self._client.key_value_set_bytes(key, value)

    def get(self, key: str) -> Optional[str]:
        try:
            return self._client.blocking_key_value_get(key, 50)
        except Exception as exc:
            if self._is_missing(exc):
                return None
            raise

    def get_bytes(self, key: str) -> Optional[bytes]:
        try:
            return self._client.blocking_key_value_get_bytes(key, 50)
        except Exception as exc:
            if self._is_missing(exc):
                return None
            raise

    def dir(self, prefix: str) -> List[Tuple[str, str]]:
        try:
            return list(self._client.key_value_dir_get(prefix))
        except Exception:
            return []  # NOT_FOUND before the first write

    def delete(self, prefix: str) -> None:
        try:
            self._client.key_value_delete(prefix)
        except Exception:
            pass  # GC is best-effort; a dying coordinator ends the run anyway


class LocalKV:
    """In-process KV fake with the same surface (dict + condition variable):
    lets unit tests run writers, the service and the weight plane as threads of
    one process, without a jax.distributed session."""

    def __init__(self) -> None:
        self._data: Dict[str, Any] = {}
        self._cond = threading.Condition()

    def set(self, key: str, value: str) -> None:
        with self._cond:
            self._data[key] = str(value)
            self._cond.notify_all()

    def set_bytes(self, key: str, value: bytes) -> None:
        with self._cond:
            self._data[key] = bytes(value)
            self._cond.notify_all()

    def get(self, key: str) -> Optional[str]:
        with self._cond:
            value = self._data.get(key)
            return None if value is None else str(value)

    def get_bytes(self, key: str) -> Optional[bytes]:
        with self._cond:
            value = self._data.get(key)
            return None if value is None else bytes(value)

    def dir(self, prefix: str) -> List[Tuple[str, str]]:
        with self._cond:
            return [(k, v) for k, v in self._data.items() if k.startswith(prefix)]

    def delete(self, prefix: str) -> None:
        with self._cond:
            for k in [k for k in self._data if k.startswith(prefix)]:
                del self._data[k]


# in-process service-plane override (`sheeprl_tpu/live`): the live flywheel
# runs serve and learner ROLES as threads of one process, so they must share a
# single KV instance and a single namespace. `coordination_kv()` and
# `service_namespace()` consult these before their multi-process defaults —
# which is enough, because `_service_learner` imports both lazily at call time.
_kv_override: Optional[Any] = None
_namespace_override: Optional[str] = None


def install_local_service_plane(
    kv: Optional[Any] = None, namespace: Optional[str] = None
) -> Tuple[Any, str]:
    """Pin every subsequent ``coordination_kv()`` / ``service_namespace()``
    call of this process to one shared in-process plane (a :class:`LocalKV` by
    default, with one freshly-derived namespace). Returns ``(kv, namespace)``;
    undo with :func:`clear_local_service_plane`."""
    global _kv_override, _namespace_override
    _kv_override = kv if kv is not None else LocalKV()
    if namespace is None:
        # derive ONE namespace through the normal nonce path, then pin it so
        # every role of the gang resolves the same keyspace
        _namespace_override = None
        namespace = service_namespace()
    _namespace_override = str(namespace)
    return _kv_override, _namespace_override


def clear_local_service_plane() -> None:
    global _kv_override, _namespace_override
    _kv_override = None
    _namespace_override = None


def coordination_kv() -> Optional[CoordinationKV]:
    """The process's coordination-service KV plane, or None outside a
    jax.distributed session (callers fail with an actionable message — the
    service backend is a multi-process construct by design). An installed
    in-process plane (:func:`install_local_service_plane`) wins."""
    if _kv_override is not None:
        return _kv_override
    from sheeprl_tpu.parallel.distributed import _kv_client

    client = _kv_client()
    return CoordinationKV(client) if client is not None else None


# per-process count of service planes built, namespacing the keyspace so a later
# run in the same jax.distributed session (sequential tests in one interpreter)
# never reads the previous run's stale streams — the BroadcastChannel pattern.
# Stays aligned across processes because every role builds exactly one plane per
# run at the same protocol point (its service construction in the algo's main).
_service_builds = 0


def service_namespace() -> str:
    import os

    if _namespace_override is not None:
        return _namespace_override
    global _service_builds
    nonce = _service_builds
    _service_builds += 1
    attempt = os.environ.get("SHEEPRL_GANG_ATTEMPT", "0")
    return f"sheeprl_xp/i{nonce}/a{attempt}"


def service_options(cfg: Any) -> Dict[str, Any]:
    """The ``buffer.service`` knobs plus the PR 6 channel liveness hooks
    (``resilience.distributed.channel`` timeout/poll + the dead-peer abort
    check), as keyword arguments for the classes below."""
    from sheeprl_tpu.resilience.distributed import channel_abort_check

    scfg = (cfg.buffer.get("service") or {}) if cfg.buffer is not None else {}
    ccfg = (((cfg.get("resilience") or {}).get("distributed") or {}).get("channel")) or {}
    return {
        "max_inflight": int(scfg.get("max_inflight") or 8),
        "flush_every": int(scfg.get("flush_every") or 1),
        "poll_s": float(scfg.get("poll") or 0.05),
        "timeout_s": float(ccfg.get("timeout") or 1800.0),
        "abort_check": channel_abort_check,
        # actors refresh weights from the plane by default; false freezes them on
        # their init weights — the deliberate stale-weight injection the
        # weight_staleness detector smoke rides (howto/observability.md)
        "poll_weights": bool(scfg.get("poll_weights", True)),
    }


def service_layout(cfg: Any) -> Dict[str, Any]:
    """The service topology derived from config + the live process count:
    ranks ``0..actors-1`` act, ranks ``actors..nprocs-1`` learn. Raises with an
    actionable message when the config cannot form a service plane."""
    from sheeprl_tpu.parallel import distributed

    nprocs = distributed.process_count()
    actors = int((cfg.buffer.get("service") or {}).get("actors") or 1)
    if nprocs < 2:
        raise ValueError(
            "buffer.backend=service needs a multi-process run (the service decouples "
            "actor PROCESSES from learner processes): launch a gang with "
            "resilience.distributed.gang.processes=<actors+learners> or bring up "
            "jax.distributed externally; buffer.backend=local is the in-process path"
        )
    if not (1 <= actors <= nprocs - 1):
        raise ValueError(
            f"buffer.service.actors={actors} leaves no learner rank in a "
            f"{nprocs}-process run (need 1 <= actors <= {nprocs - 1})"
        )
    return {
        "nprocs": nprocs,
        "actors": actors,
        "learners": nprocs - actors,
        "actor_ranks": tuple(range(actors)),
        "learner_ranks": tuple(range(actors, nprocs)),
        "leader": actors,  # the learner rank hosting the service/buffer
    }


def _bounded_wait(
    predicate: Callable[[], Optional[Any]],
    *,
    timeout_s: float,
    poll_s: float,
    abort_check: Optional[Callable[[], None]],
    what: str,
) -> Any:
    """Poll ``predicate`` until it returns non-None, with the PR 6 liveness
    contract: ``abort_check`` between slices (raises on a declared-dead peer),
    ``ServiceTimeout`` when the hard deadline expires."""
    deadline = time.monotonic() + timeout_s
    while True:
        if abort_check is not None:
            abort_check()
        value = predicate()
        if value is not None:
            return value
        if time.monotonic() >= deadline:
            raise ServiceTimeout(
                f"experience service wait for {what} timed out after {timeout_s:.0f}s "
                "— the peer is slow, hung, or dead"
            )
        time.sleep(poll_s)


# ---------------------------------------------------------------------------------
# Actor side: append-only ingestion writer
# ---------------------------------------------------------------------------------


class ExperienceWriter:
    """One actor's append-only ingestion stream.

    ``add(rows, env_ids)`` accumulates ``[1, E, ...]`` step blocks host-side and
    every ``flush_every`` adds ships them as ONE chunked message (pickled
    ``{"rank", "seq", "env_ids", "steps", "rows"}`` — rows stacked on the time
    axis, images staying uint8 across the wire). Flow control: the service acks
    its consumed frontier per actor; a writer more than ``max_inflight``
    messages ahead blocks (bounded, abort-checked) — acting can outrun a learner
    hiccup by the watermark but never flood the KV store. ``close()`` publishes
    the end-of-stream marker."""

    def __init__(
        self,
        kv: Any,
        ns: str,
        rank: int,
        *,
        max_inflight: int = 8,
        flush_every: int = 1,
        poll_s: float = 0.05,
        timeout_s: float = 1800.0,
        abort_check: Optional[Callable[[], None]] = None,
    ) -> None:
        if max_inflight < 1:
            raise ValueError(f"'max_inflight' must be >= 1, got {max_inflight}")
        if flush_every < 1:
            raise ValueError(f"'flush_every' must be >= 1, got {flush_every}")
        self.kv = kv
        self.ns = ns
        self.rank = int(rank)
        self.max_inflight = int(max_inflight)
        self.flush_every = int(flush_every)
        self.poll_s = float(poll_s)
        self.timeout_s = float(timeout_s)
        self.abort_check = abort_check
        self._seq = 0
        self._pending: List[Tuple[Dict[str, np.ndarray], Optional[Sequence[int]], float]] = []
        self._closed = False
        # the weight version this actor currently ACTS with — the loop updates it
        # after every successful refresh, and every shipped message carries it, so
        # the learner can account per-actor weight lag (dataflow lineage)
        self.weight_version = 0
        # consumer-side counters for telemetry (rows = env transitions shipped)
        self._tele_rows = 0
        self._tele_messages = 0
        self._tele_bytes = 0
        self._tele_block_seconds = 0.0

    # -- internals ---------------------------------------------------------------

    def _acked(self) -> int:
        value = self.kv.get(f"{self.ns}/ing/ack/r{self.rank}")
        return int(value) if value else 0

    def _wait_for_credit(self) -> None:
        if self._seq - self._acked() < self.max_inflight:
            return
        t0 = time.perf_counter()
        _bounded_wait(
            lambda: True if self._seq - self._acked() < self.max_inflight else None,
            timeout_s=self.timeout_s,
            poll_s=self.poll_s,
            abort_check=self.abort_check,
            what=f"ingest credit (actor {self.rank}, {self.max_inflight} in flight)",
        )
        self._tele_block_seconds += time.perf_counter() - t0

    def _put_message(self, payload: bytes) -> None:
        tag = f"{self.ns}/ing/a{self.rank}/{self._seq}"
        n = max(1, -(-len(payload) // _KV_CHUNK))
        for i in range(n):
            self.kv.set_bytes(f"{tag}/c{i}", payload[i * _KV_CHUNK : (i + 1) * _KV_CHUNK])
        self.kv.set(f"{tag}/n", str(n))
        # the frontier key commits the message: one dir-get over {ns}/ing/pub/
        # tells the service every actor's latest complete seq
        self.kv.set(f"{self.ns}/ing/pub/r{self.rank}", str(self._seq))
        self._seq += 1
        self._tele_messages += 1
        self._tele_bytes += len(payload)

    # -- actor-loop API ----------------------------------------------------------

    @property
    def seq(self) -> int:
        return self._seq

    def add(
        self,
        rows: Mapping[str, np.ndarray],
        env_ids: Optional[Sequence[int]] = None,
        steps: Optional[int] = None,
    ) -> None:
        """Queue one ``[1, E, ...]`` step block (``env_ids``: the service-buffer
        env slots these columns belong to; None = this actor's full span) and
        flush when ``flush_every`` blocks are pending."""
        if self._closed:
            raise ServiceError("add() on a closed ExperienceWriter")
        # COPY, not view: with flush_every > 1 the pending blocks outlive the
        # caller's iteration, and vector envs reuse their observation storage —
        # an aliased view would stack flush_every copies of the LAST step
        block = {k: np.array(v) for k, v in rows.items()}
        n_rows = int(next(iter(block.values())).shape[0] * next(iter(block.values())).shape[1])
        self._tele_rows += n_rows
        # birth stamp: when the rows left the env, not when the message ships —
        # with flush_every > 1 the oldest pending block sets the message's age
        self._pending.append((block, tuple(env_ids) if env_ids is not None else None, time.time()))
        if len(self._pending) >= self.flush_every:
            self.flush(steps=steps)

    def flush(self, steps: Optional[int] = None) -> None:
        if not self._pending:
            return
        self._wait_for_credit()
        # one message per (env_ids) group, preserving order: full-span rows ship
        # together (stacked on the time axis), partial adds (dreamer's SAME_STEP
        # reset rows) ship as their own messages so env alignment survives
        groups: List[Tuple[Optional[Tuple[int, ...]], List[Dict[str, np.ndarray]], float]] = []
        for block, ids, born in self._pending:
            if groups and groups[-1][0] == ids:
                groups[-1][1].append(block)
            else:
                groups.append((ids, [block], born))
        self._pending = []
        for ids, blocks, born in groups:
            rows = (
                blocks[0]
                if len(blocks) == 1
                else {k: np.concatenate([b[k] for b in blocks], axis=0) for k in blocks[0]}
            )
            payload = pickle.dumps(
                {
                    "rank": self.rank,
                    "seq": self._seq,
                    "env_ids": ids,
                    "steps": int(steps) if steps is not None else None,
                    "rows": rows,
                    "born": born,
                    "weight_version": int(self.weight_version),
                }
            )
            self._put_message(payload)

    def close(self, preempted: bool = False) -> None:
        """Flush pending rows and publish the end-of-stream marker."""
        if self._closed:
            return
        try:
            self.flush()
        finally:
            self._closed = True
            self.kv.set(
                f"{self.ns}/ing/eos/r{self.rank}",
                json.dumps(
                    {"rows": self._tele_rows, "messages": self._seq, "preempted": bool(preempted)}
                ),
            )

    def wait_done(self, timeout_s: Optional[float] = None) -> bool:
        """Block (bounded, abort-checked) until the learner publishes the run's
        ``done`` marker — actors exit together with the learner, so a gang's
        teardown grace window never SIGTERMs a learner still draining. Returns
        False on timeout instead of raising: a missing done marker at exit is a
        warning, not a failure (heartbeats catch a DEAD learner much earlier)."""
        try:
            _bounded_wait(
                lambda: self.kv.get(f"{self.ns}/done"),
                timeout_s=float(timeout_s if timeout_s is not None else self.timeout_s),
                poll_s=self.poll_s,
                abort_check=self.abort_check,
                what="the learner's done marker",
            )
            return True
        except ServiceTimeout:
            return False

    def telemetry_snapshot(self) -> Dict[str, Any]:
        return {
            "rows": self._tele_rows,
            "messages": self._tele_messages,
            "bytes": self._tele_bytes,
            "flow_block_seconds": round(self._tele_block_seconds, 4),
            "inflight": self._seq - self._acked(),
            "weight_version": int(self.weight_version),
        }


# ---------------------------------------------------------------------------------
# Learner side: the service draining actor streams into a replay buffer
# ---------------------------------------------------------------------------------


def _weighted_percentiles(entries: Sequence[Tuple[int, float]]) -> Optional[Dict[str, float]]:
    """{p50, p99, mean, max} of a row-weighted value sample: ``entries`` are
    (rows, value) pairs, each value counting ``rows`` times — the exact
    distribution a uniform draw over those rows would see, without expanding
    the sample row-by-row."""
    pairs = sorted((float(v), int(n)) for n, v in entries if n > 0)
    total = sum(n for _, n in pairs)
    if total <= 0:
        return None
    out: Dict[str, float] = {}
    targets = {"p50": 0.5 * total, "p99": 0.99 * total}
    seen = 0
    acc = 0.0
    for value, n in pairs:
        acc += value * n
        seen += n
        for name, target in list(targets.items()):
            if seen >= target:
                out[name] = round(value, 4)
                del targets[name]
    out["mean"] = round(acc / total, 4)
    out["max"] = round(pairs[-1][0], 4)
    return out


class _AgeBook:
    """Capacity-bounded trail of what the replay buffer currently holds, kept by
    the ingest thread: one entry per ingested message ``(rows, born, round)``
    where ``round`` is the message's global add-round index. Entries beyond the
    buffer's row capacity are evicted from the left — the same FIFO the ring
    buffer overwrites in — so :meth:`age_snapshot` is the age distribution of
    the rows a uniform sample draws from, in seconds (wall clock since the rows
    left the env) and in add-rounds (how many ingest messages ago)."""

    def __init__(self, capacity_rows: Optional[int]) -> None:
        from collections import deque

        # None = unknown capacity: fall back to a generous entry cap so the
        # book cannot grow without bound on exotic buffers. A deque: eviction
        # runs on the ingest-drain path (which contends with the sampler lock),
        # so the FIFO must be O(1) per message even at the entry cap. The lock
        # covers writer (ingest thread) vs snapshot reader (the learner's
        # telemetry window emit) — an unguarded deque iteration would raise
        # "mutated during iteration" under load and freeze the gauges.
        self.capacity_rows = int(capacity_rows) if capacity_rows else None
        self._entries: "deque[Tuple[int, float, int]]" = deque()
        self._lock = threading.Lock()
        self._rows = 0
        self._round = 0

    def record(self, rows: int, born: Optional[float]) -> None:
        with self._lock:
            self._round += 1
            if born is None:
                return  # a pre-lineage writer: age unknown, never guessed
            self._entries.append((int(rows), float(born), self._round))
            self._rows += int(rows)
            cap = self.capacity_rows
            while (cap is not None and self._rows > cap) or len(self._entries) > 65536:
                evicted = self._entries.popleft()
                self._rows -= evicted[0]

    def age_snapshot(self, now: Optional[float] = None) -> Optional[Dict[str, Any]]:
        with self._lock:
            if not self._entries:
                return None
            entries = list(self._entries)
            current_round = self._round
        now = time.time() if now is None else float(now)
        seconds = _weighted_percentiles([(n, max(now - born, 0.0)) for n, born, _ in entries])
        rounds = _weighted_percentiles(
            [(n, float(current_round - rnd)) for n, _, rnd in entries]
        )
        return {"seconds": seconds, "rounds": rounds, "add_rounds": current_round}


class ExperienceService:
    """Drains every actor's ingestion stream into a replay buffer.

    Runs an ingest thread (start/stop) that polls the publication frontier,
    fetches complete messages in actor order, and ``rb.add``s their rows under
    ``lock`` — the same mutex the learner's replay sampler gathers under, so a
    sampled block is never a torn read of a half-written row (the
    ``data/prefetch.py`` contract). Consumed messages are acked (the writers'
    flow-control credit) and deleted (KV GC).

    ``rb`` is any buffer with the ``add(rows, env_ids?, validate_args=...)``
    surface (``EnvIndependentReplayBuffer`` for per-actor env slots, plain
    ``ReplayBuffer`` for a single flat span). Counters are per-actor
    (provenance) and aggregate; ``queue_depth`` is the published-minus-consumed
    backlog across actors — the "is the learner keeping up" gauge the
    ``fleet_ingest`` bench records."""

    def __init__(
        self,
        rb: Any,
        kv: Any,
        ns: str,
        actor_ranks: Sequence[int],
        *,
        lock: Optional[threading.Lock] = None,
        poll_s: float = 0.05,
        env_ids_of: Optional[Callable[[int], Sequence[int]]] = None,
        validate_args: bool = False,
    ) -> None:
        self.rb = rb
        self.kv = kv
        self.ns = ns
        self.actor_ranks = tuple(int(r) for r in actor_ranks)
        self.lock = lock or threading.Lock()
        self.poll_s = float(poll_s)
        self._env_ids_of = env_ids_of
        self._validate_args = bool(validate_args)
        self._consumed: Dict[int, int] = {r: 0 for r in self.actor_ranks}
        self._eos: Dict[int, Dict[str, Any]] = {}
        self._rows: Dict[int, int] = {r: 0 for r in self.actor_ranks}
        self._messages = 0
        self._bytes = 0
        self._error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._depth_sum = 0.0
        self._depth_polls = 0
        self._depth_max = 0
        self._started_at: Optional[float] = None
        # dataflow lineage (howto/observability.md "Tracing the dataflow"):
        # sampled-row ages over the buffer's retained span, per-message ingest
        # latency (drain − born, bounded reservoir), and each actor's last
        # reported acting weight version (the learner-side lag source)
        try:
            capacity = int(rb.buffer_size) * int(rb.n_envs)
        except (AttributeError, TypeError, ValueError):
            capacity = None
        self._ages = _AgeBook(capacity)
        self._ingest_latency_s: List[Tuple[int, float]] = []  # (rows, seconds)
        self._actor_weight_version: Dict[int, int] = {}

    # -- draining ----------------------------------------------------------------

    def _frontier(self) -> Dict[int, int]:
        out: Dict[int, int] = {}
        for key, value in self.kv.dir(f"{self.ns}/ing/pub/"):
            name = key.rsplit("/", 1)[-1]
            if name.startswith("r"):
                try:
                    out[int(name[1:])] = int(value)
                except (TypeError, ValueError):
                    continue
        return out

    def _fetch(self, rank: int, seq: int) -> Optional[Dict[str, Any]]:
        tag = f"{self.ns}/ing/a{rank}/{seq}"
        n_raw = self.kv.get(f"{tag}/n")
        if n_raw is None:
            return None
        chunks = []
        for i in range(int(n_raw)):
            chunk = self.kv.get_bytes(f"{tag}/c{i}")
            if chunk is None:  # the frontier said complete; transient KV lag
                return None
            chunks.append(chunk)
        payload = pickle.loads(b"".join(chunks))
        self._bytes += sum(len(c) for c in chunks)
        self.kv.delete(tag + "/")
        return payload

    def drain_once(self) -> int:
        """One drain pass over every actor stream; returns rows ingested. Called
        by the ingest thread (or directly in tests/synchronous callers)."""
        frontier = self._frontier()
        ingested = 0
        depth = sum(
            max(frontier.get(r, -1) + 1 - self._consumed[r], 0) for r in self.actor_ranks
        )
        self._depth_sum += depth
        self._depth_polls += 1
        self._depth_max = max(self._depth_max, depth)
        for rank in self.actor_ranks:
            latest = frontier.get(rank, -1)
            while self._consumed[rank] <= latest:
                message = self._fetch(rank, self._consumed[rank])
                if message is None:
                    break
                rows = message["rows"]
                env_ids = message.get("env_ids")
                if env_ids is None and self._env_ids_of is not None:
                    env_ids = self._env_ids_of(rank)
                with self.lock:
                    if env_ids is not None:
                        self.rb.add(dict(rows), list(env_ids), validate_args=self._validate_args)
                    else:
                        self.rb.add(dict(rows), validate_args=self._validate_args)
                first = next(iter(rows.values()))
                n_rows = int(
                    first.shape[0] * (len(env_ids) if env_ids is not None else first.shape[1])
                )
                self._rows[rank] += n_rows
                ingested += n_rows
                self._messages += 1
                born = message.get("born")
                self._ages.record(n_rows, born)
                if born is not None:
                    self._ingest_latency_s.append((n_rows, max(time.time() - float(born), 0.0)))
                    if len(self._ingest_latency_s) > 4096:
                        del self._ingest_latency_s[:2048]
                if message.get("weight_version") is not None:
                    self._actor_weight_version[rank] = int(message["weight_version"])
                self._consumed[rank] += 1
                self.kv.set(f"{self.ns}/ing/ack/r{rank}", str(self._consumed[rank]))
        # end-of-stream markers (poll AFTER draining so eos with a drained
        # backlog really means "everything this actor ever sent is in the buffer")
        for key, value in self.kv.dir(f"{self.ns}/ing/eos/"):
            name = key.rsplit("/", 1)[-1]
            if name.startswith("r"):
                try:
                    self._eos[int(name[1:])] = json.loads(value)
                except (TypeError, ValueError):
                    self._eos[int(name[1:])] = {}
        return ingested

    def _ingest_loop(self) -> None:
        try:
            while not self._stop.is_set():
                if self.drain_once() == 0:
                    self._stop.wait(self.poll_s)
        except BaseException as exc:  # surface on the learner thread
            self._error = exc

    # -- lifecycle / learner API -------------------------------------------------

    def start(self) -> "ExperienceService":
        if self._thread is None:
            self._started_at = time.perf_counter()
            self._thread = threading.Thread(
                target=self._ingest_loop, name="experience-ingest", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None
        self.raise_pending()

    def raise_pending(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise ServiceError("experience ingest thread failed") from err

    def mark_done(self) -> None:
        """Publish the run's done marker (the actors' exit gate)."""
        self.kv.set(f"{self.ns}/done", "1")

    @property
    def rows_total(self) -> int:
        return sum(self._rows.values())

    def rows_of(self, rank: int) -> int:
        return self._rows.get(int(rank), 0)

    def eos_all(self) -> bool:
        """Every actor published end-of-stream AND its backlog is fully drained."""
        if set(self._eos) != set(self.actor_ranks):
            return False
        frontier = self._frontier()
        return all(self._consumed[r] > frontier.get(r, -1) for r in self.actor_ranks)

    def eos_preempted(self) -> bool:
        return any(bool(e.get("preempted")) for e in self._eos.values())

    def row_ages(self) -> Optional[Dict[str, Any]]:
        """Sampled-row age distribution (seconds and add-rounds) over what the
        buffer currently retains; None before the first lineage-stamped row."""
        return self._ages.age_snapshot()

    def ingest_latency(self) -> Optional[Dict[str, float]]:
        """Row-weighted env→buffer latency percentiles in SECONDS (born stamp →
        drain) over a bounded recent reservoir."""
        return _weighted_percentiles(list(self._ingest_latency_s))

    def actor_weight_versions(self) -> Dict[int, int]:
        """Each actor's last reported acting weight version (from the ingest
        messages) — the learner computes per-actor lag against the publisher."""
        return dict(self._actor_weight_version)

    def telemetry_snapshot(self) -> Dict[str, Any]:
        elapsed = (
            time.perf_counter() - self._started_at if self._started_at is not None else None
        )
        return {
            "rows": self.rows_total,
            "rows_per_actor": {str(r): self._rows[r] for r in self.actor_ranks},
            "messages": self._messages,
            "bytes": self._bytes,
            "rows_per_sec": (
                round(self.rows_total / elapsed, 2) if elapsed and elapsed > 0 else None
            ),
            "queue_depth_mean": (
                round(self._depth_sum / self._depth_polls, 3) if self._depth_polls else 0.0
            ),
            "queue_depth_max": self._depth_max,
            "eos": sorted(self._eos),
        }


# ---------------------------------------------------------------------------------
# Weight plane: learner publishes, actors poll
# ---------------------------------------------------------------------------------


class WeightPublisher:
    """Version-keyed weight publication. Payloads are immutable once written
    (``{ns}/w/{v}/c{i}`` + ``n``), the ``latest`` pointer commits a version, and
    versions ``<= v-2`` are GC'd — a reader holding ``latest`` therefore always
    fetches complete chunks (a very late reader whose version was GC'd simply
    re-polls ``latest``). Non-blocking for the learner."""

    def __init__(self, kv: Any, ns: str) -> None:
        self.kv = kv
        self.ns = ns
        self.version = 0
        self._tele_bytes = 0

    def publish(self, tree: Any, final: bool = False) -> int:
        self.version += 1
        payload = pickle.dumps({"version": self.version, "final": bool(final), "tree": tree})
        tag = f"{self.ns}/w/{self.version}"
        n = max(1, -(-len(payload) // _KV_CHUNK))
        for i in range(n):
            self.kv.set_bytes(f"{tag}/c{i}", payload[i * _KV_CHUNK : (i + 1) * _KV_CHUNK])
        self.kv.set(f"{tag}/n", str(n))
        self.kv.set(f"{self.ns}/w/latest", str(self.version))
        if self.version > 2:
            self.kv.delete(f"{self.ns}/w/{self.version - 2}/")
        self._tele_bytes += len(payload)
        return self.version

    def telemetry_snapshot(self) -> Dict[str, Any]:
        return {"version": self.version, "bytes": self._tele_bytes}


class WeightSubscriber:
    """Actor-side weight reader: ``poll()`` is non-blocking (None when nothing
    newer than the held version exists), ``wait(min_version)`` blocks bounded
    for the first publication (abort-checked, so a dead learner breaks the wait
    instead of hanging the actor)."""

    def __init__(
        self,
        kv: Any,
        ns: str,
        *,
        poll_s: float = 0.05,
        timeout_s: float = 1800.0,
        abort_check: Optional[Callable[[], None]] = None,
    ) -> None:
        self.kv = kv
        self.ns = ns
        self.version = 0
        # newest version OBSERVED on the plane (>= self.version): held vs latest
        # is this actor's weight lag, honest even when the actor never fetches
        self.latest = 0
        self.poll_s = float(poll_s)
        self.timeout_s = float(timeout_s)
        self.abort_check = abort_check

    def _fetch(self, version: int) -> Optional[Dict[str, Any]]:
        tag = f"{self.ns}/w/{version}"
        n_raw = self.kv.get(f"{tag}/n")
        if n_raw is None:
            return None
        chunks = []
        for i in range(int(n_raw)):
            chunk = self.kv.get_bytes(f"{tag}/c{i}")
            if chunk is None:
                return None  # GC raced a very late read: re-poll latest
            chunks.append(chunk)
        payload = pickle.loads(b"".join(chunks))
        return payload if payload.get("version") == version else None

    def peek_latest(self) -> int:
        """Read (and remember) the newest published version WITHOUT fetching a
        payload — the lag probe for actors that are not refreshing this tick."""
        latest_raw = self.kv.get(f"{self.ns}/w/latest")
        if latest_raw is not None:
            self.latest = max(self.latest, int(latest_raw))
        return self.latest

    def poll(self) -> Optional[Dict[str, Any]]:
        latest = self.peek_latest()
        if latest <= self.version:
            return None
        payload = self._fetch(latest)
        if payload is None:
            return None
        self.version = latest
        return payload

    def telemetry_snapshot(self) -> Dict[str, Any]:
        return {
            "version": int(self.version),
            "latest": int(self.latest),
            "lag": max(int(self.latest) - int(self.version), 0),
        }

    def wait(self, min_version: int = 1, timeout_s: Optional[float] = None) -> Dict[str, Any]:
        def pred() -> Optional[Dict[str, Any]]:
            payload = self.poll()
            if self.version >= min_version:
                return payload if payload is not None else {"version": self.version}
            return None

        return _bounded_wait(
            pred,
            timeout_s=float(timeout_s if timeout_s is not None else self.timeout_s),
            poll_s=self.poll_s,
            abort_check=self.abort_check,
            what=f"weight version >= {min_version}",
        )


# ---------------------------------------------------------------------------------
# Dataflow observability providers: what RunTelemetry.attach_dataflow consumes.
# One snapshot per telemetry window — the `dataflow` block on window/summary
# events and the Service/* gauges (obs/telemetry.py) read straight from these,
# no second bookkeeping path.
# ---------------------------------------------------------------------------------


class ActorDataflow:
    """The actor role's dataflow view: its ingestion counters (writer) and its
    weight staleness (held vs newest published — ``peek_latest`` keeps the lag
    honest even for an actor that never refreshes)."""

    role = "actor"

    def __init__(self, writer: ExperienceWriter, subscriber: WeightSubscriber) -> None:
        self._writer = writer
        self._subscriber = subscriber

    def dataflow_snapshot(self) -> Dict[str, Any]:
        try:
            self._subscriber.peek_latest()
        except Exception:
            pass  # a dying coordinator must not take the telemetry window down
        w = self._writer.telemetry_snapshot()
        s = self._subscriber.telemetry_snapshot()
        return {
            "role": "actor",
            "weight_version": s["version"],
            "weight_latest": s["latest"],
            "weight_lag": s["lag"],
            "rows": w["rows"],
            "messages": w["messages"],
            "inflight": w["inflight"],
            "flow_block_seconds": w["flow_block_seconds"],
        }


class LearnerDataflow:
    """The learner role's dataflow view: ingest latency + sampled-row ages from
    the service's lineage trail, queue depth, and per-actor weight lag against
    the publisher's current version."""

    role = "learner"

    def __init__(self, service: ExperienceService, publisher: WeightPublisher) -> None:
        self._service = service
        self._publisher = publisher

    def dataflow_snapshot(self) -> Dict[str, Any]:
        snap = self._service.telemetry_snapshot()
        current = int(self._publisher.version)
        versions = self._service.actor_weight_versions()
        lags = {str(r): max(current - v, 0) for r, v in sorted(versions.items())}
        latency = self._service.ingest_latency()
        return {
            "role": "learner",
            "weight_version": current,
            "weight_lag": (
                {
                    "per_actor": lags,
                    "max": max(lags.values()),
                    "mean": round(sum(lags.values()) / len(lags), 3),
                }
                if lags
                else None
            ),
            "row_age": self._service.row_ages(),
            "ingest_latency_ms": (
                {k: round(v * 1000.0, 3) for k, v in latency.items()} if latency else None
            ),
            "queue_depth": snap["queue_depth_mean"],
            "queue_depth_max": snap["queue_depth_max"],
            "rows": snap["rows"],
            "rows_per_actor": snap["rows_per_actor"],
            "rows_per_sec": snap["rows_per_sec"],
        }
