"""Per-layer activation offloading hooks for the jit engine (paper §3.2).

The staged engine hands each module's autograd residuals to the
`ActivationSpool` from ordinary Python between per-stage jit calls. The
jit engine runs the whole training step as ONE XLA program, so the same
pack/unpack-hook dataflow has to cross the program boundary from inside
the trace. This module is that bridge:

  * `spooled_scan_body(fn, bridge)` wraps a segment's scan body in a
    `jax.custom_vjp`. The forward computes the segment's actual autograd
    residuals (the leaves of the `jax.vjp` closure, exactly like
    `core.staged._Stage`), keeps the parameter leaves as ordinary XLA
    residuals, and hands everything else to the spool through a
    `jax.experimental.io_callback` — after which XLA frees the device
    buffers (pack-hook semantics). The backward's io_callback fetches
    them back (blocking, with the spool's tensor forwarding if the store
    is still in flight) and applies the saved vjp.
  * `HookBridge` is the host side: a thread-safe shim that keys spool
    step-leases on the *traced* step counter the callbacks receive, so
    re-entrant offload/fetch calls from XLA host-callback threads land
    in the right transaction. A backward fetch prefetches the previous
    stage first (§3.3.2, one module ahead).

SPMD (multi-device meshes): an io_callback cannot be partitioned by
GSPMD, so on a mesh the hooks wrap the callbacks in a `shard_map` over
the whole mesh — every device invokes its own host callback with only
its LOCAL residual shard (`ShardPlan` picks per-leaf PartitionSpecs:
leading dim over the dp axes, the innermost divisible dim over tp).
Leases become shard-qualified (``jit{step}/s{shard}`` next to the
existing ``_s{stage}`` keys). Mesh axes that shard no leaf of a segment
only replicate data; those replica devices do not store a second copy —
the primary replica records the stage with ``consumers=n_replicas`` and
the bridge counts backward fetches down by that expected shard count
(`HookBridge(dedupe_replicas=False)` restores one store per device).
Callbacks then arrive on N XLA host-callback threads per step instead
of one; the bridge's fetch additionally *waits* for its forward store
callback (bounded by `fetch_timeout`), so no assumption about XLA's
cross-device schedule is baked in. The callbacks go through
`repro.core.hostcb.raw_io_callback` — `io_callback` minus its arg
`device_put`, whose async copy of a large operand can starve against
the mesh's collectives and deadlock the step (see hostcb) — so a host
callback never re-enters the jax runtime: the bridge copies operands
with plain owned memcpys and fetches with `to_device=False`.

Ordering note: the forward callback returns a tiny token that is
threaded through the custom_vjp residuals into the backward callback's
operands. The pairing is therefore enforced by DATA dependence, not by
`ordered=True` effects — scan linearization drops unordered-result-free
effectful calls from the forward pass, and tokens also keep XLA from
reordering a fetch before its store was enqueued.

Grad taps (eager optimizer overlap): with an `opt_sink`, the backward
rule additionally streams each layer's parameter cotangents to
`opt_sink.on_grads(step, stage, leaves)` the moment the layer's vjp has
run — while XLA continues into the next-lower layer's backward. The tap
is fire-and-forget (the sink must never block the callback thread); its
liveness token is folded back into dp by multiplying leaf 0 with a
runtime ``token*0.0 + 1.0`` float gate — bitwise-exact (×1.0) yet not
constant-foldable, so the tap survives DCE. An integer ``token*0`` fold
would be simplified away, and ``+0.0`` would flip ``-0.0`` bits.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.cache.horizon import reuse_horizon
from repro.core.hostcb import raw_io_callback as io_callback
from repro.core.spool import ActivationSpool, SpoolStepTransaction
from repro.parallel.shmap import (axes_size, canonical_axis_entry,
                                  linear_axis_index, local_shape,
                                  mesh_size, spec_axes)

#: stage-index offset for encoder-stream layers, so one step lease can
#: hold both streams without key collisions (decoder layers are 0-based)
ENC_STAGE_BASE = 1 << 20

#: how long a backward fetch waits for its matching forward offload
#: callback before giving up — on a mesh the callbacks arrive on
#: independent XLA host-callback threads, and a replica's backward can
#: in principle be scheduled before the primary's forward callback ran
DEFAULT_FETCH_TIMEOUT_S = 120.0


# ====================================================================
# Shard planning (how residual leaves map onto mesh devices)
# ====================================================================

@dataclass(frozen=True)
class ShardPlan:
    """How one hooked segment's residual leaves split across a mesh.

    `specs[i]` is leaf i's PartitionSpec; `writer_axes` are the mesh
    axes that shard at least one leaf (devices differing only along the
    remaining `replica_axes` hold byte-identical residuals). The shard
    id in spool keys is the linearized index over `writer_axes`; the
    replica id over `replica_axes` selects which duplicate stores."""

    mesh: Any
    specs: Tuple[Any, ...]
    writer_axes: Tuple[str, ...]
    replica_axes: Tuple[str, ...]

    @property
    def n_shards(self) -> int:
        return axes_size(self.mesh, self.writer_axes)

    @property
    def n_replicas(self) -> int:
        return axes_size(self.mesh, self.replica_axes)

    def local_sds(self, global_sds) -> Tuple[jax.ShapeDtypeStruct, ...]:
        return tuple(
            jax.ShapeDtypeStruct(local_shape(s.shape, spec, self.mesh),
                                 s.dtype)
            for s, spec in zip(global_sds, self.specs))


def plan_shards(mesh, dp_axes, tp_axis, leaf_sds) -> ShardPlan:
    """Pick a PartitionSpec per residual leaf: leading dim over the dp
    axes (batch-major residuals dominate), the innermost other divisible
    dim over tp. Indivisible leaves replicate — their bytes are stored
    once per *writer* group, not once per device."""
    dp_axes = tuple(a for a in (dp_axes or ())
                    if a in mesh.shape and mesh.shape[a] > 1)
    if tp_axis is not None and (tp_axis not in mesh.shape
                                or mesh.shape[tp_axis] <= 1):
        tp_axis = None
    dp_size = axes_size(mesh, dp_axes)
    specs = []
    for s in leaf_sds:
        parts: List[Any] = [None] * len(s.shape)
        if dp_axes and s.shape and s.shape[0] > 0 \
                and s.shape[0] % dp_size == 0:
            parts[0] = canonical_axis_entry(dp_axes)
        if tp_axis is not None:
            tp = mesh.shape[tp_axis]
            for d in range(len(s.shape) - 1, -1, -1):
                if parts[d] is None and s.shape[d] > 0 \
                        and s.shape[d] % tp == 0:
                    parts[d] = tp_axis
                    break
        specs.append(P(*parts))
    used = set()
    for spec in specs:
        used.update(spec_axes(spec))
    writer = tuple(a for a in mesh.axis_names if a in used)
    replica = tuple(a for a in mesh.axis_names if a not in used)
    return ShardPlan(mesh=mesh, specs=tuple(specs),
                     writer_axes=writer, replica_axes=replica)


class HookBridge:
    """Host-side endpoint of the jit engine's activation-offload hooks.

    One bridge per training session. Callbacks arrive on XLA's
    host-callback threads with (step, stage[, shard]) scalars; the
    bridge opens one transactional spool lease per step and shard
    (key ``jit{step}`` on one device, ``jit{step}/s{shard}`` per mesh
    shard — mirroring the staged engine's ``mb{mb}``) and closes each
    lease when the backward pass has consumed every stage it recorded.

    Shard accounting: when residuals are replicated across part of the
    mesh and `dedupe_replicas` is on, only the primary replica stores a
    stage — recorded with ``consumers=n_replicas`` — and every
    replica's backward fetch counts the stage down; the LAST fetch
    drops it. `stats_by_shard()` exposes per-shard offload/fetch/byte
    counters whose totals sum exactly to the bridge-wide traffic.
    """

    def __init__(self, spool: ActivationSpool, *, key_prefix: str = "jit",
                 dedupe_replicas: bool = True,
                 fetch_timeout: float = DEFAULT_FETCH_TIMEOUT_S,
                 fetch_fallback: bool = False):
        self.spool = spool
        self.dedupe_replicas = dedupe_replicas
        self.fetch_timeout = fetch_timeout
        self.fetch_fallback = fetch_fallback
        self._prefix = key_prefix
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._txs: Dict[str, SpoolStepTransaction] = {}
        self._shard_stats: Dict[Any, Dict[str, int]] = {}

    @property
    def stats(self):
        return self.spool.stats

    def stats_by_shard(self) -> Dict[Any, Dict[str, int]]:
        """Per-shard callback traffic: offloads / fetches /
        replica_skips counts and logical bytes in each direction. The
        key is the shard id (None on a single device)."""
        with self._lock:
            return {k: dict(v) for k, v in self._shard_stats.items()}

    def _note(self, shard, field: str, n: int = 1) -> None:
        with self._lock:
            rec = self._shard_stats.setdefault(shard, {
                "offloads": 0, "fetches": 0, "replica_skips": 0,
                "degraded_fetches": 0, "bytes_in": 0, "bytes_out": 0})
            rec[field] += n

    def _step_id(self, step: int, shard) -> str:
        base = f"{self._prefix}{step}"
        return base if shard is None else f"{base}/s{shard}"

    def _tx(self, step_id: str) -> SpoolStepTransaction:
        with self._lock:
            tx = self._txs.get(step_id)
            if tx is None:
                tx = self.spool.step(step_id)
                self._txs[step_id] = tx
            return tx

    # ---------------------------------------------------- callback API

    def offload(self, step: int, stage: int, arrays: List[Any], *,
                shard=None, consumers: int = 1) -> None:
        """Forward hook: async-store one segment's residual leaves
        under the (step, shard) lease. `consumers` is how many backward
        fetches this stage expects (one per replica shard).

        The leaves are COPIED here: raw_io_callback hands the hooks
        numpy views of XLA's operand buffers that die when the callback
        returns, and the spool's store worker runs after that. A plain
        owned memcpy also never touches the jax runtime — a device
        thread must not block on jax's async machinery mid-step."""
        with obs.span("hook.offload", cat="hook", step=step, stage=stage,
                      shard=shard) as sp:
            arrays = [np.array(a, copy=True) for a in arrays]
            tx = self._tx(self._step_id(step, shard))
            tx.offload(stage, arrays, consumers=consumers)
            nbytes = int(sum(a.nbytes for a in arrays))
            sp.set(bytes=nbytes)
        self._note(shard, "offloads")
        self._note(shard, "bytes_in", nbytes)
        with self._cv:
            self._cv.notify_all()

    def sharded_offload(self, step: int, stage: int, arrays: List[Any],
                        *, shard: int, replica: int,
                        n_replicas: int) -> None:
        """Mesh entry point: with replica dedupe the primary replica
        stores once for its whole replica group; without it every
        device stores its own copy under a replica-qualified shard."""
        if self.dedupe_replicas and n_replicas > 1:
            if replica == 0:
                self.offload(step, stage, arrays, shard=shard,
                             consumers=n_replicas)
            else:
                self._note(shard, "replica_skips")
                obs.instant("hook.replica_skip", cat="hook", step=step,
                            stage=stage, shard=shard, replica=replica)
        else:
            self.offload(step, stage, arrays,
                         shard=shard * n_replicas + replica)

    def fetch(self, step: int, stage: int, *,
              shard=None) -> List[np.ndarray]:
        """Backward hook: blocking fetch of one segment's residuals,
        prefetching the previous stage first (one module ahead). Counts
        the stage's consumers down; the last fetch drops it, and the
        (step, shard) lease closes when its last live stage is
        consumed. Waits (bounded) for the forward offload callback —
        on a mesh the store and fetch arrive on different host-callback
        threads and their cross-device order is not guaranteed."""
        step_id = self._step_id(step, shard)
        # only a sharded fetch may legitimately beat its store callback
        # (they run on different device threads); on one device the
        # token data-dependence already ordered them, so a missing
        # lease there is a bug — fail fast instead of timing out
        wait = self.fetch_timeout if shard is not None else 0.0
        deadline = time.monotonic() + wait
        with obs.span("hook.fetch", cat="hook", step=step, stage=stage,
                      shard=shard) as fsp:
            with obs.span("hook.wait_store", cat="hook", step=step,
                          stage=stage, shard=shard):
                with self._cv:
                    while True:
                        tx = self._txs.get(step_id)
                        if tx is not None and tx.has_stage(stage):
                            break
                        left = deadline - time.monotonic()
                        if left <= 0:
                            raise KeyError(
                                f"no live spool record for step "
                                f"{step_id!r} stage {stage} after "
                                f"{wait:.0f}s — was the forward offload "
                                f"callback dropped?")
                        self._cv.wait(timeout=min(left, 1.0))
            # one module ahead (§3.3.2): the reuse horizon over the
            # remaining backward stages
            for s in reuse_horizon(range(stage - 1, -1, -1)):
                tx.prefetch(s)
            # to_device=False: the callback returns host arrays straight
            # to XLA — converting through jnp would device_put on the
            # callback thread, the exact jax-runtime dependence
            # raw_io_callback exists to avoid
            out = tx.consume(stage, to_device=False)
            arrays = [np.asarray(a) for a in out]
            nbytes = int(sum(a.nbytes for a in arrays))
            fsp.set(bytes=nbytes)
        self._note(shard, "fetches")
        self._note(shard, "bytes_out", nbytes)
        with self._lock:
            if not tx.live_stages and self._txs.get(step_id) is tx:
                del self._txs[step_id]
                tx.close()
        return arrays

    def sharded_fetch(self, step: int, stage: int, *, shard: int,
                      replica: int, n_replicas: int) -> List[np.ndarray]:
        if self.dedupe_replicas and n_replicas > 1:
            return self.fetch(step, stage, shard=shard)
        return self.fetch(step, stage,
                          shard=shard * n_replicas + replica)

    def fetch_or_fallback(self, step: int, stage: int, shapes,
                          *, shard=None) -> Tuple[np.ndarray, ...]:
        """Degraded-mode fetch: like `fetch` but a load failure returns
        ``(0, *zeros)`` instead of raising, so the XLA program can branch
        to recompute (`spooled_scan_body`'s lax.cond). On success returns
        ``(1, *arrays)``. The branch decision is runtime data — the hook
        trace always contains BOTH the fetch and the recompute path, and
        this flag picks one per (step, stage) at execution time."""
        try:
            arrays = self.fetch(step, stage, shard=shard)
            return (np.int32(1), *arrays)
        except (RuntimeError, OSError, KeyError) as e:
            self.spool.stats.fetch_fallbacks += 1
            self._note(shard, "degraded_fetches")
            obs.count("resilience.fetch_fallback")
            obs.instant("resilience.fetch_fallback", cat="resilience",
                        step=step, stage=stage, shard=shard,
                        error=repr(e))
            self._abort_stage(step, stage, shard)
            zeros = tuple(np.zeros(s.shape, s.dtype) for s in shapes)
            return (np.int32(0), *zeros)

    def _abort_stage(self, step: int, stage: int, shard=None) -> None:
        """Drop a stage whose fetch failed so the (step, shard) lease can
        still close — the blob may be gone, `drop` tolerates that."""
        step_id = self._step_id(step, shard)
        with self._lock:
            tx = self._txs.get(step_id)
            if tx is None:
                return
            try:
                tx.drop(stage)
            except Exception:
                pass
            if not tx.live_stages and self._txs.get(step_id) is tx:
                del self._txs[step_id]
                tx.close()

    def close(self) -> None:
        """Drop any leftover leases (a step aborted mid-backward)."""
        with self._lock:
            txs, self._txs = list(self._txs.values()), {}
        for tx in txs:
            tx.close()


def _tap_grads(dp, step, stage, sink, mesh=None):
    """Stream one layer's parameter cotangents to ``sink.on_grads``
    from inside the backward trace without changing dp's value.

    Single device: one raw_io_callback with the dp leaves as operands
    (the sink copies what it keeps). On a mesh the tap runs under a
    shard_map with replicated in_specs — GSPMD materializes the
    logically-correct (post-reduction) gradients before the body — and
    only the device with linear index 0 hands them to the sink; the
    token is psum'd so every device's schedule orders the tap
    (offload_body precedent). The returned dp folds the token in via
    the ×1.0 gate described in the module docstring."""
    leaves, treedef = jax.tree.flatten(dp)
    if not leaves:
        return dp
    if mesh is None or mesh_size(mesh) <= 1:
        def grad_tap_cb(step_, stage_, *arrays):
            sink.on_grads(int(step_), int(stage_),
                          [np.array(a, copy=True) for a in arrays])
            return np.int32(0)

        tok = io_callback(grad_tap_cb,
                          jax.ShapeDtypeStruct((), jnp.int32),
                          step, stage, *leaves)
        gate = tok.astype(jnp.float32) * 0.0 + 1.0
    else:
        axis_names = tuple(mesh.axis_names)

        def grad_tap_cb(step_, stage_, dev_, *arrays):
            if int(np.asarray(dev_).reshape(())) == 0:
                sink.on_grads(int(step_), int(stage_),
                              [np.array(a, copy=True) for a in arrays])
            return np.zeros((1,), np.int32)

        def tap_body(step_, stage_, *leaves_):
            dev_ = linear_axis_index(mesh, axis_names)
            tok = io_callback(grad_tap_cb,
                              jax.ShapeDtypeStruct((1,), jnp.int32),
                              step_, stage_, dev_, *leaves_)
            return jax.lax.psum(tok, axis_names)

        token_spec = P(canonical_axis_entry(axis_names))
        tok = shard_map(tap_body, mesh=mesh,
                        in_specs=(P(), P(), *([P()] * len(leaves))),
                        out_specs=token_spec,
                        check_vma=False)(step, stage, *leaves)
        gate = jnp.sum(tok.astype(jnp.float32)) * 0.0 + 1.0
    leaves = [leaves[0] * gate.astype(leaves[0].dtype)] + leaves[1:]
    return jax.tree.unflatten(treedef, leaves)


def tapped_scan_body(fn: Callable, opt_sink, *, mesh=None) -> Callable:
    """Tap-only wrapper for segments whose residuals stay in device
    memory (``host_offload="opt_state"`` with opt overlap): the forward
    saves the ordinary vjp residuals as XLA residuals — no spool I/O —
    and the backward streams each layer's parameter grads to
    `opt_sink` the moment its vjp has run. Same
    ``wrapped(p, x, step, stage)`` signature as `spooled_scan_body`."""
    cell: Dict[str, Any] = {}

    @jax.custom_vjp
    def wrapped(p, x, step, stage):
        return fn(p, x)

    def fwd(p, x, step, stage):
        out, vjp = jax.vjp(fn, p, x)
        leaves, treedef = jax.tree.flatten(vjp)
        cell["treedef"] = treedef
        return out, (tuple(leaves), step, stage)

    def bwd(res, g):
        leaves, step, stage = res
        vjp = jax.tree.unflatten(cell["treedef"], list(leaves))
        dp, dx = vjp(g)
        dp = _tap_grads(dp, step, stage, opt_sink, mesh)
        return dp, dx, jnp.zeros_like(step), jnp.zeros_like(stage)

    wrapped.defvjp(fwd, bwd)
    return wrapped


def spooled_scan_body(fn: Callable, bridge: HookBridge, *,
                      mesh=None, dp_axes=(), tp_axis=None,
                      opt_sink=None) -> Callable:
    """Wrap ``fn(p_layer, x) -> out`` (a segment's per-layer body) so its
    residuals stream through the bridge's spool.

    Returns ``wrapped(p_layer, x, step, stage) -> out`` where `step` and
    `stage` are traced float32 scalars (float so the custom_vjp
    cotangents are ordinary zeros; values are exact integers). The
    undifferentiated primal path calls `fn` directly — serving and eval
    never touch the spool.

    With a multi-device `mesh`, the callbacks run under a shard_map so
    each device hands the bridge only its local residual shard (see the
    module docstring); `dp_axes`/`tp_axis` seed the per-leaf sharding
    choice exactly like `RunSettings`. With an `opt_sink`, the backward
    additionally taps the layer's parameter grads (see `_tap_grads`).
    """
    # populated at trace time by fwd, read by bwd (same trace); the
    # pattern and the param-leaf identity test match core.staged._Stage
    cell: Dict[str, Any] = {}
    sharded = mesh is not None and mesh_size(mesh) > 1
    # Degraded mode (single device only): the bwd callback returns an
    # ok-flag and the trace carries BOTH the fetch and a recompute path
    # through a lax.cond, with (p, x) saved as extra residuals. Under a
    # mesh the recompute branch would put collectives inside cond
    # branches — not supported, so sharded runs keep fetch-or-raise.
    fallback = bridge.fetch_fallback and not sharded

    @jax.custom_vjp
    def wrapped(p, x, step, stage):
        return fn(p, x)

    def fwd(p, x, step, stage):
        out, vjp = jax.vjp(fn, p, x)
        leaves, treedef = jax.tree.flatten(vjp)
        pids = {id(t) for t in jax.tree.leaves(p)}
        param_idx = tuple(i for i, l in enumerate(leaves) if id(l) in pids)
        resid_idx = tuple(i for i in range(len(leaves))
                          if i not in param_idx)
        cell["treedef"] = treedef
        cell["param_idx"] = param_idx
        cell["resid_idx"] = resid_idx
        cell["n_leaves"] = len(leaves)
        cell["resid_shapes"] = tuple(
            jax.ShapeDtypeStruct(leaves[i].shape, leaves[i].dtype)
            for i in resid_idx)
        kept = tuple(leaves[i] for i in param_idx)
        if not resid_idx:            # segment saved only parameter leaves
            return out, (kept, step, stage, jnp.zeros((), jnp.int32))

        resid = tuple(leaves[i] for i in resid_idx)
        if not sharded:
            def offload_cb(step_, stage_, *arrays):
                bridge.offload(int(step_), int(stage_), list(arrays))
                return np.int32(0)

            token = io_callback(offload_cb,
                                jax.ShapeDtypeStruct((), jnp.int32),
                                step, stage, *resid)
            if fallback:
                # The recompute branch re-differentiates the segment in
                # bwd, where fn's closed-over tracers (positions, masks)
                # would leak into the staged-out jaxpr as invalid
                # consts. Hoist them into explicit residuals and save
                # the closure-free converted function instead.
                # jax.closure_convert is not enough: it only hoists
                # perturbable (float) consts, and e.g. int32 positions
                # still leak.
                conv_fn, hoisted = _hoist_all_consts(fn, p, x)
                cell["conv_fn"] = conv_fn
                return out, (kept, step, stage, token,
                             (p, x, hoisted))
            return out, (kept, step, stage, token)

        plan = plan_shards(mesh, dp_axes, tp_axis, cell["resid_shapes"])
        cell["plan"] = plan
        n_replicas = plan.n_replicas

        def offload_cb(step_, stage_, shard_, replica_, *arrays):
            bridge.sharded_offload(int(step_), int(stage_), list(arrays),
                                   shard=int(shard_),
                                   replica=int(replica_),
                                   n_replicas=n_replicas)
            return np.zeros((1,), np.int32)

        dedupe = bridge.dedupe_replicas and n_replicas > 1

        def offload_body(step_, stage_, *local_leaves):
            shard_ = linear_axis_index(mesh, plan.writer_axes)
            replica_ = linear_axis_index(mesh, plan.replica_axes)
            tok = io_callback(offload_cb,
                              jax.ShapeDtypeStruct((1,), jnp.int32),
                              step_, stage_, shard_, replica_,
                              *local_leaves)
            if dedupe:
                # With replica dedupe only the primary replica's
                # callback stores; a replica's backward fetch then
                # BLOCKS (host side) on the primary's store having run.
                # XLA's scheduler cannot see that cross-device callback
                # dependence and may legally park the primary at a
                # later collective first — a deadlock. The psum makes
                # the dependence explicit: every device's token now
                # data-depends on every replica's (so in particular the
                # primary's) store callback having executed.
                tok = jax.lax.psum(tok, plan.replica_axes)
            return tok

        # one (1,)-token per device, reassembled over the whole mesh so
        # the backward shard_map can hand each device its own token back
        token_spec = P(canonical_axis_entry(mesh.axis_names))
        token = shard_map(offload_body, mesh=mesh,
                          in_specs=(P(), P(), *plan.specs),
                          out_specs=token_spec,
                          check_vma=False)(step, stage, *resid)
        return out, (kept, step, stage, token)

    def bwd(res, g):
        saved_in = None
        if fallback and len(res) == 5:
            kept, step, stage, token, saved_in = res
        else:
            kept, step, stage, token = res
        leaves: List[Any] = [None] * cell["n_leaves"]
        for i, l in zip(cell["param_idx"], kept):
            leaves[i] = l
        ok = None
        if cell["resid_idx"]:
            if not sharded:
                if fallback:
                    def fetch_cb(step_, stage_, _token):
                        return bridge.fetch_or_fallback(
                            int(step_), int(stage_),
                            cell["resid_shapes"])

                    got = io_callback(
                        fetch_cb,
                        (jax.ShapeDtypeStruct((), jnp.int32),
                         *cell["resid_shapes"]),
                        step, stage, token)
                    ok, fetched = got[0], got[1:]
                else:
                    def fetch_cb(step_, stage_, _token):
                        return tuple(bridge.fetch(int(step_),
                                                  int(stage_)))

                    fetched = io_callback(fetch_cb, cell["resid_shapes"],
                                          step, stage, token)
            else:
                plan = cell["plan"]
                local_sds = plan.local_sds(cell["resid_shapes"])
                n_replicas = plan.n_replicas

                def fetch_cb(step_, stage_, shard_, replica_, _token):
                    return tuple(bridge.sharded_fetch(
                        int(step_), int(stage_), shard=int(shard_),
                        replica=int(replica_), n_replicas=n_replicas))

                def fetch_body(step_, stage_, token_):
                    shard_ = linear_axis_index(mesh, plan.writer_axes)
                    replica_ = linear_axis_index(mesh, plan.replica_axes)
                    return io_callback(fetch_cb, local_sds, step_, stage_,
                                       shard_, replica_, token_)

                token_spec = P(canonical_axis_entry(mesh.axis_names))
                fetched = shard_map(fetch_body, mesh=mesh,
                                    in_specs=(P(), P(), token_spec),
                                    out_specs=plan.specs,
                                    check_vma=False)(step, stage, token)
            for i, l in zip(cell["resid_idx"], fetched):
                leaves[i] = l
        if ok is not None and saved_in is not None:
            p_saved, x_saved, hoisted = saved_in

            def use_fetched(g_):
                vjp = jax.tree.unflatten(cell["treedef"], leaves)
                return vjp(g_)

            def use_recompute(g_):
                # re-runs the segment forward from the saved inputs and
                # differentiates it — the zeros the failed fetch
                # returned are never read on this branch
                outs = jax.vjp(cell["conv_fn"], p_saved, x_saved,
                               *hoisted)[1](g_)
                return outs[0], outs[1]

            dp, dx = jax.lax.cond(ok > 0, use_fetched, use_recompute, g)
        else:
            vjp = jax.tree.unflatten(cell["treedef"], leaves)
            dp, dx = vjp(g)
        if opt_sink is not None:
            dp = _tap_grads(dp, step, stage, opt_sink,
                            mesh if sharded else None)
        return dp, dx, jnp.zeros_like(step), jnp.zeros_like(stage)

    wrapped.defvjp(fwd, bwd)
    return wrapped


def _hoist_all_consts(fn: Callable, *example_args):
    """Closure-convert `fn`, hoisting EVERY tracer const — unlike
    jax.closure_convert, which only hoists perturbable (float) ones.

    Returns ``(conv_fn, hoisted)`` where ``conv_fn(*example_args,
    *hoisted)`` equals ``fn(*example_args)`` but closes over no tracers,
    so it can be re-traced inside a custom_vjp bwd rule (the degraded
    recompute branch) without leaking the enclosing trace."""
    flat_in, in_tree = jax.tree.flatten(example_args)
    store: Dict[str, Any] = {}

    def flat_fn(*fl):
        out = fn(*jax.tree.unflatten(in_tree, fl))
        out_flat, store["out_tree"] = jax.tree.flatten(out)
        return out_flat

    closed = jax.make_jaxpr(flat_fn)(*flat_in)
    consts = list(closed.consts)
    tracer_idx = tuple(i for i, c in enumerate(consts)
                       if isinstance(c, jax.core.Tracer))
    hoisted = tuple(consts[i] for i in tracer_idx)
    n_args = len(example_args)

    def conv_fn(*args):
        trees, hs = args[:n_args], args[n_args:]
        cs = list(consts)
        for i, h in zip(tracer_idx, hs):
            cs[i] = h
        fl = jax.tree.flatten(trees)[0]
        out_flat = jax.core.eval_jaxpr(closed.jaxpr, cs, *fl)
        return jax.tree.unflatten(store["out_tree"], out_flat)

    return conv_fn, hoisted


def run_splits(mask: List[bool]) -> List[tuple]:
    """Split a per-layer offload mask into contiguous (start, end,
    offload) runs — a scanned super-layer can only be hooked whole, so
    mixed plans split the stack into a few shorter scans."""
    runs = []
    start = 0
    for i in range(1, len(mask) + 1):
        if i == len(mask) or mask[i] != mask[start]:
            runs.append((start, i, mask[start]))
            start = i
    return runs
