"""ActivationSpool — the tensor cache's I/O engine (paper §3.2–3.3.2).

Two FIFO thread pools (store / load), exactly the paper's structure:

  * offload(key, arrays): enqueue an async store; the spool holds the only
    strong reference to the arrays, so device memory is reclaimed the moment
    the write completes and the reference is dropped (pack-hook semantics).
  * prefetch(key): enqueue an async load (issued by the backward walker one
    module ahead, §3.3.2).
  * fetch(key): blocking acquire for backward. If the store is still queued
    or in flight, the in-memory reference is *forwarded* (§3.3.2) and the
    pending store is cancelled (adaptive-offloading feature 1, §3.3.3).
  * deduplication: arrays whose storage is already tracked (or registered as
    parameters) are recorded as aliases and not written twice (§3.3.1).

The "SSD" behind the spool is a pluggable `repro.io.StorageBackend`:
a real directory (default, the seed behavior), a striped multi-SSD
array, a host-RAM tier, or a capacity-budgeted RAM-over-SSD hierarchy.
Payloads go through a pluggable `Codec` (raw / zlib). An optional
bandwidth_limit still simulates a slower tier for the ROK sweeps.
"""
from __future__ import annotations

import queue
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Union

import numpy as np

import jax

from repro import obs
from repro.core.accounting import MemoryTracker
from repro.core.adaptive import TierBandwidth
from repro.core.ids import TensorIdRegistry, _buffer_key
from repro.io import (Codec, FilesystemBackend, StorageBackend,
                      encode_parts, get_codec, pack_parts, unpack,
                      unpack_aliased)
from repro.io.backend import classify_io_error
from repro.io.bufpool import DEFAULT_ALIGNMENT, AlignedBufferPool
from repro.io.serde import (deserialize_leaves, serialize_leaves,
                            serialize_parts)
from repro.resilience.health import BackendHealth
from repro.resilience.retry import RetryPolicy

# job states
QUEUED, RUNNING, DONE, CANCELED = range(4)


def build_spool(io_config=None, *, backend=None, spool_dir=None,
                codec=None, store_threads=None, load_threads=None,
                bandwidth_limit=None, tracker=None,
                min_offload_elements=None, pool_bytes=None,
                alignment=None):
    """One spool-construction path for every engine.

    Storage selection, most specific wins: an explicit StorageBackend >
    a declarative SpoolIoConfig > the seed behavior (filesystem backend
    in spool_dir / a fresh temp dir). Explicit keyword arguments win
    over the config's fields. Returns (spool, owned_tmpdirs) — the
    caller must rmtree the listed temp dirs on close."""
    owned = []
    retry = None
    if io_config is not None and hasattr(io_config, "retry_attempts"):
        retry = RetryPolicy(
            max_attempts=io_config.retry_attempts,
            backoff_s=io_config.retry_backoff_s,
            backoff_max_s=getattr(io_config, "retry_backoff_max_s",
                                  0.25))
    if backend is None and io_config is not None:
        from repro.io import build_backend
        io_config.validate()
        backend = build_backend(io_config, default_dir=spool_dir)
        owned += list(getattr(backend, "owned_tmpdirs", ()))
        codec = io_config.codec if codec is None else codec
        if store_threads is None:
            store_threads = io_config.store_threads
        if load_threads is None:
            load_threads = io_config.load_threads
        if bandwidth_limit is None:
            bandwidth_limit = io_config.bandwidth_limit
        if pool_bytes is None:
            pool_bytes = getattr(io_config, "pool_bytes", None)
        if alignment is None:
            alignment = getattr(io_config, "alignment", None)
    if backend is None:
        if spool_dir is None:
            spool_dir = tempfile.mkdtemp(prefix="tba_spool_")
            owned.append(spool_dir)
        backend = spool_dir
    spool = ActivationSpool(
        backend, codec=codec,
        store_threads=(4 if store_threads is None else store_threads),
        load_threads=(4 if load_threads is None else load_threads),
        bandwidth_limit=bandwidth_limit, tracker=tracker,
        min_offload_elements=(MIN_OFFLOAD_ELEMENTS
                              if min_offload_elements is None
                              else min_offload_elements),
        pool_bytes=(256 << 20 if pool_bytes is None else pool_bytes),
        alignment=(DEFAULT_ALIGNMENT if alignment is None
                   else alignment),
        retry=retry)
    return spool, owned

# paper Algorithm 2 line 12: tensors smaller than 2**20 elements stay put
MIN_OFFLOAD_ELEMENTS = 2 ** 20

# back-compat aliases for the serialization helpers that used to live here
_serialize = serialize_leaves
_deserialize = deserialize_leaves


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


@dataclass
class SpoolStats:
    bytes_offloaded: int = 0
    # pre-codec residual bytes behind bytes_offloaded — their ratio is
    # the codec's measured compression on real activations
    bytes_offloaded_logical: int = 0
    bytes_loaded: int = 0
    bytes_forwarded: int = 0
    bytes_deduped: int = 0
    # records served from memory instead of the backend (tensor
    # forwarding, or a failed store whose arrays were still held)
    num_forwarded: int = 0
    stores_canceled: int = 0
    store_time: float = 0.0
    load_time: float = 0.0
    # seconds inside backend.write_parts, summed over store workers:
    # store_time without encode, retry back-off or the bandwidth cap,
    # so bytes_offloaded / write_time is the rate one write sees
    write_time: float = 0.0
    num_stores: int = 0
    num_loads: int = 0
    # time the *consumer* (backward pass) spent blocked waiting for a
    # load — the paper's "I/O latency exposed in the critical path".
    fetch_wait_time: float = 0.0
    # resilience: transient-failure retries the workers rode out, and
    # fetches the engines degraded to recompute after a lost blob
    store_retries: int = 0
    load_retries: int = 0
    fetch_fallbacks: int = 0
    # write-back policy: opt-state bytes whose SSD rewrite was skipped
    # because the moments were byte-identical to the staged copy
    # (zero-grad layers, frozen params)
    opt_skipped_bytes: int = 0

    @property
    def write_bandwidth(self) -> float:
        # 0.0, not inf, before the first store completes: dryrun /
        # roofline reports print this, and "inf GB/s" is a lie
        return self.bytes_offloaded / self.store_time \
            if self.store_time else 0.0

    def add(self, other: "SpoolStats") -> "SpoolStats":
        """Field-wise sum — aggregate stats across spools (e.g. one
        spool per shard group, or per-step snapshots)."""
        import dataclasses as _dc
        return SpoolStats(**{
            f.name: getattr(self, f.name) + getattr(other, f.name)
            for f in _dc.fields(SpoolStats)})

    __add__ = add

    def sub(self, other: "SpoolStats") -> "SpoolStats":
        """Field-wise difference — turns two cumulative snapshots into
        a per-step delta (`new.sub(old)`)."""
        import dataclasses as _dc
        return SpoolStats(**{
            f.name: getattr(self, f.name) - getattr(other, f.name)
            for f in _dc.fields(SpoolStats)})

    __sub__ = sub

    def snapshot(self) -> "SpoolStats":
        """Value copy of a live (mutating) stats object, safe to diff
        against later."""
        import dataclasses as _dc
        return _dc.replace(self)


class _Job:
    __slots__ = ("key", "arrays", "state", "cond", "kind", "orphaned",
                 "error", "reg_keys", "prefetched", "t_enq", "cause")

    def __init__(self, key, arrays, kind):
        self.key = key
        self.arrays = arrays
        self.state = QUEUED
        self.cond = threading.Condition()
        self.kind = kind  # "store" | "load"
        self.orphaned = False  # dropped while the store was running
        self.error = None      # exception raised by the worker, if any
        # load jobs: issued by an explicit prefetch() hint (vs. fetch's
        # own demand load) — the distinction behind prefetch hit/late/
        # ghost accounting in repro.obs
        self.prefetched = False
        # dedup-registry keys for the spooled leaves; released by
        # whoever drops the last reference to self.arrays (the store
        # worker on success, drop() otherwise) — releasing later than
        # the buffer free would let a recycled allocation false-dedup
        # against a dead entry
        self.reg_keys: tuple = ()
        # enqueue time (the worker's span records how long the job
        # queued) and the span that enqueued it (its `cause`)
        self.t_enq = time.perf_counter()
        self.cause = obs.current_span()


class SpoolStepTransaction:
    """Transactional lease on one training step's spool records.

    The spool's raw protocol (offload/keep/prefetch/fetch/drop on string
    keys) left key construction and drop bookkeeping to every caller —
    and an exception mid-step leaked every record still live. A
    transaction owns both: stages are addressed by index, keys are
    derived once (``{step_id}_s{stage}``, byte-identical to the seed's
    hand-rolled ``f"mb{mb}_s{si}"``), and closing the transaction drops
    every record the caller did not consume — on success *and* on
    exception, so an aborted step never strands blobs on the backend.

        with spool.step(f"mb{mb}") as tx:
            tx.offload(si, residuals)     # forward
            ...
            tx.prefetch(si - 1)           # backward, one module ahead
            residuals = tx.fetch(si)
            tx.drop(si)
    """

    __slots__ = ("_spool", "step_id", "_live", "_closed", "_tlock",
                 "_consumers", "_stage_locks")

    def __init__(self, spool: "ActivationSpool", step_id: str):
        self._spool = spool
        self.step_id = step_id
        self._live: Dict[Any, str] = {}     # stage -> spool key
        # stage -> remaining consume() calls before the stage is dropped
        # (shard-aware leases: a record replicated across N mesh shards
        # is stored once and consumed N times, one fetch per shard)
        self._consumers: Dict[Any, int] = {}
        # stage -> lock serializing concurrent consumers of ONE stage,
        # so a non-final peek never races the final fetch's drop (the
        # drop releases the pooled load buffer the peek's zero-copy
        # views still borrow)
        self._stage_locks: Dict[Any, threading.Lock] = {}
        self._closed = False
        # the jit engine's hooks drive one transaction from XLA
        # host-callback threads; stage bookkeeping must be re-entrant
        self._tlock = threading.Lock()

    def key(self, stage) -> str:
        return f"{self.step_id}_s{stage}"

    def _record(self, stage, consumers: int = 1) -> str:
        if consumers < 1:
            raise ValueError(f"consumers must be >= 1, got {consumers}")
        with self._tlock:
            if self._closed:
                raise RuntimeError(
                    f"spool transaction {self.step_id!r} is closed")
            key = self.key(stage)
            if stage in self._live:
                raise KeyError(f"stage {stage!r} already live in step "
                               f"{self.step_id!r}")
            self._live[stage] = key
            self._consumers[stage] = consumers
            self._stage_locks[stage] = threading.Lock()
        return key

    def offload(self, stage, tree, *, consumers: int = 1) -> None:
        """Async-store a stage's residual pytree under this lease.
        `consumers` is how many `consume()` calls the stage expects
        before it is dropped (one per mesh shard holding a replica)."""
        self._spool.offload(self._record(stage, consumers), tree)

    def keep(self, stage, tree, *, consumers: int = 1) -> None:
        """Record a stage's residuals as kept-in-memory under this
        lease (same drop/accounting lifecycle as offloaded ones)."""
        self._spool.keep(self._record(stage, consumers), tree)

    def has_stage(self, stage) -> bool:
        """True while the stage is recorded and not fully consumed."""
        with self._tlock:
            return stage in self._live

    def prefetch(self, stage) -> None:
        """Hint an async load; a stage this lease never recorded is
        ignored (recompute stages have nothing to load)."""
        with self._tlock:
            key = self._live.get(stage)
        if key is not None:
            self._spool.prefetch(key)

    def fetch(self, stage, *, to_device: bool = True):
        """Blocking: the stage's full residual pytree (forwarded from
        the in-flight store or reloaded from the backend).
        to_device=False keeps reloaded leaves as host numpy arrays —
        for callers (the jit engine's host callbacks) that must not
        enter the jax runtime on their thread."""
        with self._tlock:
            key = self._live.get(stage)
        if key is None:
            raise KeyError(f"stage {stage!r} not recorded in step "
                           f"{self.step_id!r}")
        return self._spool.fetch(key, to_device=to_device)

    def peek(self, stage, *, to_device: bool = True):
        """Non-consuming fetch: materialize the pytree WITHOUT
        cancelling a still-queued store, so a later fetch/drop still
        finds the blob on the backend (checkpoint materialization)."""
        with self._tlock:
            key = self._live.get(stage)
        if key is None:
            raise KeyError(f"stage {stage!r} not recorded in step "
                           f"{self.step_id!r}")
        return self._spool.fetch(key, cancel_pending=False,
                                 to_device=to_device)

    def consume(self, stage, *, to_device: bool = True):
        """Fetch the stage's pytree and count one consumer down; the
        LAST consumer's call also drops the record (memory + blob).
        Concurrent consumers of one stage serialize on a per-stage
        lock, so a non-final materialization never races the final
        drop's pool-lease release."""
        with self._tlock:
            if stage not in self._live:
                raise KeyError(f"stage {stage!r} not recorded in step "
                               f"{self.step_id!r}")
            slock = self._stage_locks[stage]
        with slock:
            with self._tlock:
                remaining = self._consumers.get(stage, 0)
                if remaining <= 0:        # dropped by a racing consumer
                    raise KeyError(f"stage {stage!r} already consumed "
                                   f"in step {self.step_id!r}")
                self._consumers[stage] = remaining - 1
                last = remaining == 1
            if last:
                out = self.fetch(stage, to_device=to_device)
                self.drop(stage)
            else:
                out = self.peek(stage, to_device=to_device)
        return out

    def drop(self, stage) -> None:
        """Consume the stage: free memory and delete the blob."""
        with self._tlock:
            key = self._live.pop(stage, None)
            self._consumers.pop(stage, None)
            self._stage_locks.pop(stage, None)
        if key is not None:
            self._spool.drop(key)

    @property
    def live_stages(self):
        with self._tlock:
            return sorted(self._live)

    def close(self) -> None:
        """Drop every record not consumed yet and release the lease.
        Idempotent; this is the leak-on-exception backstop."""
        with self._tlock:
            if self._closed:
                return
            self._closed = True
            leftover = list(self._live)
        for stage in leftover:
            self.drop(stage)
        self._spool._release_step(self.step_id)

    def __enter__(self) -> "SpoolStepTransaction":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


class ActivationSpool:
    def __init__(self, backend: Union[str, StorageBackend], *,
                 store_threads: int = 4,
                 load_threads: int = 4,
                 codec: Union[str, Codec, None] = None,
                 bandwidth_limit: Optional[float] = None,
                 tracker: Optional[MemoryTracker] = None,
                 registry: Optional[TensorIdRegistry] = None,
                 min_offload_elements: int = MIN_OFFLOAD_ELEMENTS,
                 pool: Optional[AlignedBufferPool] = None,
                 pool_bytes: int = 256 << 20,
                 alignment: int = DEFAULT_ALIGNMENT,
                 retry: Optional[RetryPolicy] = None,
                 health: Optional[BackendHealth] = None):
        # A bare directory string keeps the seed call shape:
        # ActivationSpool("/path/to/dir") == filesystem backend there.
        if isinstance(backend, str):
            backend = FilesystemBackend(backend)
        self.backend = backend
        self.dir = getattr(backend, "directory", None)
        # A cache-manager backend (repro.cache.CacheManager, duck-typed
        # on hint_next) gets the spool's tensor classes declared up
        # front and its reuse-distance hints fed from prefetch: the same
        # horizon that drives load scheduling drives tier placement.
        self.cache_manager = backend if hasattr(backend, "hint_next") \
            else None
        if self.cache_manager is not None:
            self.cache_manager.register_class("activation")
            self.cache_manager.register_class("opt_state", prefix="opt")
        self.codec = get_codec(codec)
        # One aligned pool serves the whole data plane: loads readinto
        # leased buffers (no per-load blob allocation), and an aio
        # backend stages its O_DIRECT writes from the same pool.
        backend_pool = getattr(backend, "pool", None)
        self.pool = pool or backend_pool or \
            AlignedBufferPool(alignment=alignment, max_bytes=pool_bytes)
        self._owns_pool = pool is None and backend_pool is None
        self.min_offload_elements = min_offload_elements
        self.tracker = tracker or MemoryTracker()
        self.registry = registry or TensorIdRegistry()
        self.stats = SpoolStats()
        # resilience: every backend call in the workers goes through
        # _with_retry, which classifies failures (repro.io.backend),
        # rides out transient ones with bounded backoff, and feeds the
        # health monitor that AdaptivePolicy re-plans from
        self.retry = retry or RetryPolicy()
        self.retry.validate()
        self.health = health or BackendHealth(self.backend.kind)
        if self.cache_manager is not None \
                and hasattr(self.cache_manager, "attach_health"):
            # SSD-tier write failures inside the manager (fallback to
            # host RAM) surface as health events next to spool retries
            self.cache_manager.attach_health(self.health)
        self._bw = bandwidth_limit
        self._lock = threading.Lock()
        self._records: Dict[Any, Dict] = {}     # key -> record
        self._store_q: "queue.Queue[_Job]" = queue.Queue()
        self._load_q: "queue.Queue[_Job]" = queue.Queue()
        self._stop = False
        self._closed = False
        self._store_threads = store_threads
        self._load_threads = load_threads
        self._active_steps: set = set()
        self._threads: List[threading.Thread] = []
        for i in range(store_threads):
            t = threading.Thread(target=self._worker,
                                 args=(self._store_q,), daemon=True,
                                 name=f"spool-store-{i}")
            t.start()
            self._threads.append(t)
        for i in range(load_threads):
            t = threading.Thread(target=self._worker,
                                 args=(self._load_q,), daemon=True,
                                 name=f"spool-load-{i}")
            t.start()
            self._threads.append(t)

    # ------------------------------------------------------------- API

    def step(self, step_id) -> SpoolStepTransaction:
        """Open a transactional lease for one training step's records
        (see `SpoolStepTransaction`). At most one live lease per
        step_id — a collision means the previous step leaked."""
        if self._closed:
            raise RuntimeError("spool is closed")
        step_id = str(step_id)
        with self._lock:
            if step_id in self._active_steps:
                raise RuntimeError(
                    f"step lease {step_id!r} is already active")
            self._active_steps.add(step_id)
        return SpoolStepTransaction(self, step_id)

    def lease(self, lease_id) -> SpoolStepTransaction:
        """Alias of `step` for non-training users. A lease is not tied
        to a training step: the paged KV cache (repro.kvcache) opens one
        long-lived lease per served sequence and uses logical page
        indices as stages, so retiring the sequence (`close`) drops
        every page it ever spooled — the same leak-proof contract, a
        different lifetime."""
        return self.step(lease_id)

    def _release_step(self, step_id: str) -> None:
        with self._lock:
            self._active_steps.discard(step_id)

    def register_parameters(self, params) -> int:
        return self.registry.register_parameters(params)

    def offload(self, key, tree) -> None:
        """Async-store a pytree of arrays under `key`. Small tensors and
        parameter/duplicate storages stay in memory (recorded, not
        written)."""
        leaves, treedef = jax.tree.flatten(tree)
        keep_idx, spool_idx, acquired, spooled_keys = [], [], [], []
        kept_act_bytes = alias_bytes = 0
        for i, leaf in enumerate(leaves):
            if self.registry.is_parameter(leaf):
                keep_idx.append(i)
                continue
            if leaf.size < self.min_offload_elements:
                keep_idx.append(i)
                kept_act_bytes += leaf.size * leaf.dtype.itemsize
                continue
            tid, dup = self.registry.acquire(leaf)
            if dup:
                # alias of a still-live tracked buffer: keep the
                # reference, never write it twice; its key is released
                # when the record drops
                acquired.append(_buffer_key(leaf))
                keep_idx.append(i)
                alias_bytes += leaf.size * leaf.dtype.itemsize
            else:
                # spooled leaves' keys ride the store job instead: the
                # worker frees the array the moment the write lands,
                # and the registry entry must die WITH the buffer or a
                # recycled allocation would false-dedup against it
                spooled_keys.append(_buffer_key(leaf))
                spool_idx.append(i)
        self.stats.bytes_deduped += alias_bytes

        spooled = [leaves[i] for i in spool_idx]
        nbytes = _nbytes(spooled)
        if kept_act_bytes:
            self.tracker.alloc((key, "k"), kept_act_bytes,
                               tag=f"kept_small:{key}")
        if not spool_idx:               # nothing above the threshold
            with self._lock:
                self._records[key] = {
                    "treedef": treedef,
                    "keep": {i: leaves[i] for i in keep_idx},
                    "spool_idx": [], "n_leaves": len(leaves), "job": None,
                    "nbytes": 0, "loaded": None, "load_job": None,
                    "load_lease": None, "acquired": acquired,
                }
            return
        self.tracker.alloc((key, "s"), nbytes, tag=f"residual:{key}")
        job = _Job(key, spooled, "store")
        job.reg_keys = tuple(spooled_keys)
        with self._lock:
            self._records[key] = {
                "treedef": treedef, "keep": {i: leaves[i] for i in keep_idx},
                "spool_idx": spool_idx, "n_leaves": len(leaves),
                "job": job, "nbytes": nbytes, "loaded": None,
                "load_job": None, "load_lease": None,
                "acquired": acquired,
            }
        self._store_q.put(job)
        if obs.is_enabled():
            obs.instant("spool.offload", cat="spool", key=str(key),
                        bytes=nbytes)
            obs.gauge("spool.store_backlog", self._store_q.qsize())

    def keep(self, key, tree) -> None:
        """Record a kept-in-memory pytree (adaptive offloading keeps the
        last modules on device, §3.3.3)."""
        leaves, treedef = jax.tree.flatten(tree)
        nbytes = sum(x.size * x.dtype.itemsize for x in leaves
                     if not self.registry.is_parameter(x))
        self.tracker.alloc((key, "k"), nbytes, tag=f"kept:{key}")
        with self._lock:
            self._records[key] = {
                "treedef": treedef, "keep": dict(enumerate(leaves)),
                "spool_idx": [], "n_leaves": len(leaves), "job": None,
                "nbytes": nbytes, "loaded": None, "load_job": None,
                "load_lease": None, "acquired": [],
            }

    def prefetch(self, key, *, _demand: bool = False) -> None:
        if self.cache_manager is not None:
            # the reuse horizon doubles as the placement hint: protect
            # the blob from eviction and let the manager promote it off
            # SSD ahead of the load worker's read
            self.cache_manager.hint_next([str(key)])
        with self._lock:
            rec = self._records.get(key)
            if rec is None or not rec["spool_idx"]:
                return
            job = rec["job"]
            with job.cond:
                if job.state in (QUEUED, RUNNING):
                    return          # still in memory; forwarding will hit
                if job.arrays is not None:
                    # CANCELED (or failed) store with its arrays still
                    # resident: the blob was never written, so a load
                    # would ghost-read the backend and bury the real
                    # error — fetch() forwards the in-memory reference
                    return
            if rec["load_job"] is not None or rec["loaded"] is not None:
                return
            lj = _Job(key, None, "load")
            lj.prefetched = not _demand
            rec["load_job"] = lj
        if not _demand:
            obs.count("prefetch.issued")
            obs.instant("spool.prefetch", cat="spool", key=str(key))
        self._load_q.put(lj)

    def fetch(self, key, *, cancel_pending: bool = True,
              to_device: bool = True):
        """Blocking: return the full pytree for backward.

        cancel_pending=False is the non-consuming ("peek") variant: a
        still-queued store is forwarded but NOT cancelled, so the write
        still lands and a later consuming fetch finds the blob —
        required when the caller materializes a record it will fetch
        again (e.g. checkpointing a spooled optimizer state).

        to_device=False leaves reloaded arrays as host numpy (still
        detached from pooled buffers) instead of jnp arrays — XLA
        host-callback threads must hand bytes straight back to XLA
        without re-entering the jax runtime."""
        with self._lock:
            rec = self._records.get(key)
            if rec is None:
                raise KeyError(key)
            job = rec["job"]
        spooled = None
        if job is not None and rec["spool_idx"]:
            with job.cond:
                if job.state in (QUEUED, RUNNING) or \
                        (job.state == CANCELED and job.arrays is not None):
                    # ---- tensor forwarding (§3.3.2): the store has not
                    # finished (or was cancelled with its arrays still
                    # resident — a re-fetch after forwarding); upgrade
                    # the in-flight reference. Cancel the write if it
                    # has not started (§3.3.3 feature 1).
                    spooled = job.arrays
                    if not rec.get("fwd_counted"):
                        # a peek-then-fetch (or re-fetch) of one record
                        # is one forwarding event, not two
                        rec["fwd_counted"] = True
                        self.stats.bytes_forwarded += _nbytes(spooled)
                        self.stats.num_forwarded += 1
                    if job.state == QUEUED and cancel_pending:
                        job.state = CANCELED
                        self.stats.stores_canceled += 1
                        # memory stays resident; keep tracker entry
                elif job.error is not None and job.arrays is not None:
                    # the store failed (e.g. ENOSPC) but the arrays are
                    # still referenced — forward them rather than chase
                    # a blob that was never written
                    spooled = job.arrays
                    if not rec.get("fwd_counted"):
                        # same one-event rule as the healthy branch: a
                        # peek-then-fetch of a failed store is ONE
                        # forwarding, not two
                        rec["fwd_counted"] = True
                        self.stats.bytes_forwarded += _nbytes(spooled)
                        self.stats.num_forwarded += 1
            if spooled is None:
                with self._lock:
                    lj = rec["load_job"]
                if lj is None:
                    self.prefetch(key, _demand=True)
                    with self._lock:
                        lj = rec["load_job"]
                if lj is not None:
                    if lj.prefetched:
                        # hit: the prefetched load already landed when
                        # the consumer arrived; late: issued but the
                        # consumer still has to wait for it
                        with lj.cond:
                            ready = lj.state in (DONE, CANCELED)
                        obs.count("prefetch.hit" if ready
                                  else "prefetch.late")
                    t_wait = time.perf_counter()
                    with obs.span("spool.fetch_wait", cat="spool",
                                  key=str(key)):
                        with lj.cond:
                            while lj.state not in (DONE, CANCELED):
                                lj.cond.wait()
                    self.stats.fetch_wait_time += (time.perf_counter()
                                                   - t_wait)
                    if lj.error is not None:
                        raise RuntimeError(
                            f"spool load failed for {key!r}") from lj.error
                with self._lock:
                    spooled = rec["loaded"]
                    rec["load_used"] = True
                self.tracker.alloc((key, "s"), rec["nbytes"],
                                   tag=f"reloaded:{key}")
        leaves = [None] * rec["n_leaves"]
        for i, leaf in rec["keep"].items():
            leaves[i] = leaf
        if rec["spool_idx"]:
            for i, leaf in zip(rec["spool_idx"], spooled):
                if isinstance(leaf, np.ndarray):
                    if not leaf.flags.writeable:
                        # copy-on-demand: pooled-load leaves are
                        # zero-copy views over a buffer the pool will
                        # reuse after drop(); jnp.asarray may ALIAS an
                        # aligned host array instead of copying, so
                        # detach here, exactly once, at materialization
                        leaf = leaf.copy()
                    if to_device:
                        leaf = jax.numpy.asarray(leaf)
                leaves[i] = leaf
        return jax.tree.unflatten(rec["treedef"], leaves)

    def drop(self, key) -> None:
        """Consume a record after backward: free memory + delete the
        blob from the backend."""
        with self._lock:
            rec = self._records.pop(key, None)
        if rec is None:
            return
        lj = rec.get("load_job")
        if lj is not None and lj.prefetched and not rec.get("load_used"):
            # ghost: prefetched from the backend but dropped unread —
            # wasted read bandwidth the planner should know about
            obs.count("prefetch.ghost")
        for bkey in rec["acquired"]:
            self.registry.release_key(bkey)
        job = rec["job"]
        if job is not None:
            # spooled-leaf keys the store worker did not release (the
            # store was cancelled, failed, or is still holding arrays
            # for forwarding) die with the record
            with job.cond:
                keys, job.reg_keys = job.reg_keys, ()
            for bkey in keys:
                self.registry.release_key(bkey)
        self.tracker.free((key, "s"), tag=f"consumed:{key}")
        self.tracker.free((key, "k"), tag=f"consumed:{key}")
        lease = rec.get("load_lease")
        if lease is not None:
            # the record's loaded views die with the record; hand the
            # pooled buffer to the next load
            rec["loaded"] = None
            rec["load_lease"] = None
            lease.release()
        if not rec["spool_idx"]:
            return
        job = rec["job"]
        if job is not None:
            with job.cond:
                if job.state == QUEUED:
                    # never written; cancel so the worker skips the
                    # (now pointless) write entirely
                    job.state = CANCELED
                    self.stats.stores_canceled += 1
                    return
                if job.state == RUNNING:
                    # the write will land *after* this delete — flag the
                    # job so the worker deletes on completion, or the
                    # blob leaks forever (on a RAM backend that is a
                    # real memory leak, not a stray file)
                    job.orphaned = True
                    return
        self.backend.delete(str(key))

    def wait_io(self) -> None:
        """Barrier: wait for all queued stores (paper Algorithm 1 line 15)."""
        self._store_q.join()
        self._load_q.join()

    def calibrate_backend(self, nbytes: int, repeats: int = 2) -> None:
        """Re-measure the whole store path with a synthetic uncontended
        burst.

        The profiling step's writes race jit compilation for CPU, so the
        busy-clock bandwidth they leave behind can understate the device
        severalfold and make the planner underoffload. Call after
        wait_io(). Two measurements:

        * codec+container throughput and size ratio on an incompressible
          payload (the worker encodes before it writes, so a slow codec
          bounds the store path no matter how fast the device is);
        * per-tier device bandwidth via backend.calibrate, which
          exercises every tier of a composite backend.
        """
        if nbytes <= 0:
            return
        import os as _os
        payload = _os.urandom(nbytes)
        t0 = time.perf_counter()
        for _ in range(repeats):
            data = pack_parts([payload], self.codec)
        t_codec = (time.perf_counter() - t0) / repeats
        self._codec_bw = nbytes / t_codec if t_codec > 0 else float("inf")
        # Size ratio from *real* spooled residuals when available: the
        # urandom probe is right for throughput (worst case) but wrong
        # for ratio — activations compress, random bytes don't.
        if self.stats.bytes_offloaded_logical > 0:
            self._codec_ratio = (self.stats.bytes_offloaded
                                 / self.stats.bytes_offloaded_logical)
        else:
            self._codec_ratio = len(data) / nbytes
        self.backend.calibrate(data, repeats)

    def planner_bandwidth(self) -> Union[float, List[TierBandwidth]]:
        """What the adaptive planner should plan against.

        Per-tier *store-path* bandwidths: the measured device rate of
        each tier composed (harmonically — the worker encodes, then
        writes) with the measured codec throughput, in logical residual
        bytes. Tier capacities are converted to logical bytes via the
        codec's size ratio. Falls back to the spool's own end-to-end
        scalar while any tier is still unmeasured."""
        tiers = self.backend.tier_bandwidths()
        if not tiers or any(t.write_bw <= 0 or t.write_bw == float("inf")
                            for t in tiers):
            return self.stats.write_bandwidth
        ratio = getattr(self, "_codec_ratio", 1.0)
        codec_bw = getattr(self, "_codec_bw", float("inf"))
        out = []
        for t in tiers:
            per_byte = ratio / t.write_bw + (1.0 / codec_bw
                                             if codec_bw > 0 else 0.0)
            bw = 1.0 / per_byte
            if self._bw:
                # the simulated-tier throttle (encoded bytes/s) caps
                # every store job regardless of device speed; express
                # it in logical bytes like the rest of the tier
                bw = min(bw, self._bw / max(ratio, 1e-9))
            cap = (None if t.capacity_bytes is None
                   else int(t.capacity_bytes / max(ratio, 1e-9)))
            out.append(TierBandwidth(t.name, bw, cap))
        return out

    def close(self) -> None:
        """Drain queued I/O, stop and JOIN the worker threads, close the
        backend. Idempotent — a second close is a no-op, and returning
        guarantees no worker is still mid-write."""
        if self._closed:
            return
        self._closed = True
        self.wait_io()
        self._stop = True
        for _ in range(self._store_threads):
            self._store_q.put(None)
        for _ in range(self._load_threads):
            self._load_q.put(None)
        for t in self._threads:
            t.join()
        self._threads = []
        self.backend.close()
        if self._owns_pool:
            self.pool.close()

    def data_plane_stats(self) -> Dict[str, Any]:
        """One dict for the whole byte path: backend I/O (incl. host
        copies-per-byte) + aligned-pool reuse. This is where the
        'zero per-job large allocations' claim becomes a number."""
        return {
            "backend": self.backend.stats.as_dict(),
            "pool": self.pool.stats(),
        }

    # --------------------------------------------------------- workers

    def _with_retry(self, op: str, key, fn):
        """Run one backend call with bounded retry/backoff on transient
        failures; every outcome feeds the health monitor."""
        policy = self.retry
        attempt = 1
        while True:
            t0 = time.perf_counter()
            try:
                out = fn()
            except BaseException as e:
                self.health.record_failure(op, e,
                                           time.perf_counter() - t0)
                if (classify_io_error(e) != "transient"
                        or attempt >= policy.max_attempts):
                    raise
                if op == "write":
                    self.stats.store_retries += 1
                else:
                    self.stats.load_retries += 1
                if obs.is_enabled():
                    obs.instant("resilience.retry", cat="resilience",
                                op=op, key=str(key), attempt=attempt,
                                error=repr(e))
                time.sleep(policy.delay(attempt))
                attempt += 1
            else:
                self.health.record_success(op,
                                           time.perf_counter() - t0)
                return out

    def _worker(self, q: "queue.Queue[Optional[_Job]]"):
        while True:
            job = q.get()
            if job is None:
                q.task_done()
                return
            try:
                self._run_job(job)
            except BaseException as e:
                # keep the worker alive and surface the failure at
                # fetch() instead of deadlocking a waiter forever
                job.error = e
                with job.cond:
                    job.state = DONE
                    job.cond.notify_all()
            finally:
                q.task_done()

    def _run_job(self, job: _Job):
        with job.cond:
            if job.state == CANCELED:
                job.cond.notify_all()
                return
            job.state = RUNNING
        t0 = time.perf_counter()
        queued_ms = (t0 - job.t_enq) * 1e3
        if job.kind == "store":
            with obs.span("spool.store", cat="spool", key=str(job.key),
                          cause=job.cause,
                          queued_ms=queued_ms) as store_sp:
                arrays = [np.asarray(a) for a in job.arrays]
                # vectored store: the serde part list flows through the
                # codec container straight to backend.write_parts — with
                # the raw codec on a vectored backend the payload is
                # never joined or copied on the host at all
                with obs.span("codec.encode", cat="codec",
                              key=str(job.key)):
                    parts = encode_parts(serialize_parts(arrays),
                                         self.codec)
                nbytes = sum(len(p) if not isinstance(p, memoryview)
                             else p.nbytes for p in parts)
                # memoryview parts are re-readable, so a retry re-issues
                # the same vectored write without re-encoding
                write_s = 0.0

                def write():
                    nonlocal write_s
                    tw = time.perf_counter()
                    try:
                        self.backend.write_parts(str(job.key), parts)
                    finally:
                        write_s += time.perf_counter() - tw

                self._with_retry("write", job.key, write)
                dt = time.perf_counter() - t0
                if self._bw:
                    min_t = nbytes / self._bw
                    if dt < min_t:
                        time.sleep(min_t - dt)
                        dt = min_t
                store_sp.set(bytes=nbytes)
            self.stats.bytes_offloaded += nbytes
            self.stats.bytes_offloaded_logical += \
                sum(a.nbytes for a in arrays)
            self.stats.store_time += dt
            self.stats.write_time += write_s
            self.stats.num_stores += 1
            # registry entries must not outlive the buffers they track:
            # release BEFORE freeing, so a recycled address can never
            # hit a stale entry (and a still-live alias keeps its own
            # refcount on the entry)
            with job.cond:
                keys, job.reg_keys = job.reg_keys, ()
            for bkey in keys:
                self.registry.release_key(bkey)
            with job.cond:
                job.arrays = None          # drop the reference -> memory free
                job.state = DONE
                orphaned = job.orphaned
                job.cond.notify_all()
            self.tracker.free((job.key, "s"), tag=f"offloaded:{job.key}")
            if orphaned:
                # Dropped while we were writing. Spool keys are reused
                # across steps, so a NEW lease of this key may already
                # exist — deleting then would destroy its blob (a new
                # lease's write can only happen after its record is
                # inserted under _lock, so checking and deleting under
                # the same lock closes the race).
                with self._lock:
                    if job.key not in self._records:
                        self.backend.delete(str(job.key))
        else:
            key = str(job.key)
            # pooled load: size the blob, readinto a leased aligned
            # buffer, and deserialize zero-copy views over it. The
            # lease lives until the record is dropped (fetch copies on
            # demand when it materializes device arrays).
            lease = None
            with obs.span("spool.load", cat="spool", key=key,
                          cause=job.cause,
                          queued_ms=queued_ms) as load_sp:
                # RAM-backed stores hand the blob back by reference — a
                # pooled staging copy would only ADD a memcpy there
                nbytes = None if self.backend.zero_copy_read \
                    else self._with_retry(
                        "read", key, lambda: self.backend.size(key))
                if nbytes is not None and nbytes > 0:
                    lease = self.pool.acquire(nbytes)
                    try:
                        # the leased buffer is reused across attempts: a
                        # retried readinto just overwrites it
                        blob = self._with_retry(
                            "read", key,
                            lambda: self.backend.readinto(key, lease.mv))
                    except BaseException:
                        lease.release()
                        raise
                    nread = len(blob)
                else:
                    blob = self._with_retry(
                        "read", key, lambda: self.backend.read(key))
                    nread = len(blob)
                try:
                    with obs.span("codec.decode", cat="codec", key=key):
                        payload, aliases = unpack_aliased(blob)
                        # non-aliasing payloads (codec decodes) own
                        # fresh memory: leave the views writable so
                        # fetch's copy-on-demand doesn't pay a
                        # redundant memcpy
                        arrays = deserialize_leaves(payload, copy=False,
                                                    pinned=aliases)
                except BaseException:
                    if lease is not None:
                        lease.release()
                    raise
                if lease is not None and not aliases:
                    # decoding codecs hand back fresh memory: nothing
                    # borrows the pooled buffer, recycle it immediately
                    # instead of pinning it until drop()
                    lease.release()
                    lease = None
                dt = time.perf_counter() - t0
                if self._bw:
                    min_t = nread / self._bw
                    if dt < min_t:
                        time.sleep(min_t - dt)
                        dt = min_t
                load_sp.set(bytes=nread)
            self.stats.bytes_loaded += nread
            self.stats.load_time += dt
            self.stats.num_loads += 1
            with self._lock:
                rec = self._records.get(job.key)
                if rec is not None:
                    rec["loaded"] = arrays
                    rec["load_lease"] = lease
                elif lease is not None:
                    # record dropped while we were loading: nobody will
                    # ever release this lease through drop()
                    lease.release()
            with job.cond:
                job.state = DONE
                job.cond.notify_all()
