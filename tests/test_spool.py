"""Unit tests for the ActivationSpool: async store/load roundtrip, tensor
forwarding, dedup, store cancellation, the wait_io barrier, and the
simulated-bandwidth mode used by the ROK sweeps."""
import os
import tempfile
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spool import ActivationSpool


def _spool(**kw):
    d = tempfile.mkdtemp(prefix="spool_test_")
    kw.setdefault("min_offload_elements", 16)
    return ActivationSpool(d, **kw), d


def _tree(seed=0, n=3, shape=(64, 64)):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=shape), jnp.float32)
            for _ in range(n)]


def test_roundtrip_exact():
    spool, d = _spool()
    tree = _tree()
    spool.offload("k0", tree)
    spool.wait_io()
    out = spool.fetch("k0")
    for a, b in zip(tree, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    spool.drop("k0")
    assert not os.path.exists(os.path.join(d, "k0.act"))
    spool.close()


def test_bf16_roundtrip():
    spool, _ = _spool()
    tree = [jnp.ones((32, 32), jnp.bfloat16) * 1.5]
    spool.offload("k", tree)
    spool.wait_io()
    out = spool.fetch("k")
    assert out[0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out[0], np.float32),
                                  np.asarray(tree[0], np.float32))
    spool.close()


def test_forwarding_when_store_in_flight():
    """fetch() during a slow store must forward the in-memory reference
    (paper §3.3.2) and cancel queued writes (§3.3.3 feature 1)."""
    spool, _ = _spool(bandwidth_limit=1e6, store_threads=1)  # ~1 MB/s
    t1 = _tree(1)
    t2 = _tree(2)
    spool.offload("a", t1)          # occupies the single store thread
    spool.offload("b", t2)          # waits in queue
    out = spool.fetch("b")          # must forward, not wait for disk
    for a, b in zip(t2, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert spool.stats.bytes_forwarded > 0
    assert spool.stats.stores_canceled >= 1
    spool.wait_io()
    spool.close()


def test_dedup_same_buffer_written_once():
    spool, _ = _spool()
    x = jnp.ones((128, 128), jnp.float32)
    spool.offload("k1", [x, x])     # same buffer twice
    spool.wait_io()
    assert spool.stats.bytes_deduped >= x.size * 4
    out = spool.fetch("k1")
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))
    spool.close()


def test_parameters_never_offloaded():
    spool, _ = _spool()
    p = jnp.ones((64, 64), jnp.float32)
    spool.register_parameters({"w": p})
    spool.offload("k", [p, jnp.zeros((64, 64), jnp.float32)])
    spool.wait_io()
    out = spool.fetch("k")
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(p))
    spool.close()


def test_small_tensors_stay_in_memory():
    spool, _ = _spool(min_offload_elements=10**6)
    t = _tree(shape=(8, 8))
    spool.offload("k", t)
    spool.wait_io()
    assert spool.stats.bytes_offloaded == 0   # all below the threshold
    out = spool.fetch("k")
    for a, b in zip(t, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    spool.close()


def test_keep_then_fetch():
    spool, _ = _spool()
    t = _tree()
    spool.keep("k", t)
    out = spool.fetch("k")
    for a, b in zip(t, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    spool.drop("k")
    assert spool.tracker.current == 0
    spool.close()


def test_prefetch_then_fetch():
    spool, _ = _spool()
    t = _tree()
    spool.offload("k", t)
    spool.wait_io()
    spool.prefetch("k")
    spool.wait_io()
    out = spool.fetch("k")
    for a, b in zip(t, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    spool.close()


def test_tracker_reflects_offload_lifecycle():
    spool, _ = _spool()
    t = _tree(shape=(256, 256))
    nbytes = sum(x.size * 4 for x in t)
    spool.offload("k", t)
    spool.wait_io()                 # store done -> device bytes released
    assert spool.tracker.current == 0
    spool.fetch("k")                # reloaded -> resident again
    assert spool.tracker.current == nbytes
    spool.drop("k")
    assert spool.tracker.current == 0
    spool.close()


def test_step_lease_roundtrip_and_keys():
    """The transaction derives the seed's exact key shape and owns drop
    bookkeeping."""
    spool, d = _spool()
    t = _tree()
    with spool.step("mb0") as tx:
        assert tx.key(3) == "mb0_s3"
        tx.offload(3, t)
        spool.wait_io()
        assert os.path.exists(os.path.join(d, "mb0_s3.act"))
        out = tx.fetch(3)
        for a, b in zip(t, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        tx.drop(3)
    assert not os.path.exists(os.path.join(d, "mb0_s3.act"))
    assert spool.tracker.current == 0
    spool.close()


def test_step_lease_drops_leftovers_on_exception():
    """An exception mid-step must not leak records, memory accounting,
    or backend blobs (the seed's hand-rolled protocol leaked all
    three)."""
    spool, d = _spool()
    with pytest.raises(RuntimeError, match="boom"):
        with spool.step("mb0") as tx:
            tx.offload(0, _tree(0))
            tx.keep(1, _tree(1))
            spool.wait_io()
            raise RuntimeError("boom")
    assert spool.tracker.current == 0
    assert not spool._records
    assert not os.path.exists(os.path.join(d, "mb0_s0.act"))
    # the lease is released: the same step id can be leased again
    with spool.step("mb0") as tx:
        tx.keep(0, _tree())
        tx.fetch(0)
    spool.close()


def test_step_lease_collision_and_unknown_stage():
    spool, _ = _spool()
    tx = spool.step("s")
    with pytest.raises(RuntimeError):
        spool.step("s")             # double lease of a live step id
    with pytest.raises(KeyError):
        tx.fetch(0)                 # never recorded
    tx.prefetch(0)                  # unknown stage: silently ignored
    tx.close()
    tx.close()                      # idempotent
    spool.step("s").close()         # released after close
    spool.close()


def test_peek_does_not_cancel_pending_store():
    """A non-consuming fetch (checkpoint materialization) must leave a
    queued store alive so the blob still lands; and a consuming fetch
    after a cancel must forward the still-resident arrays instead of
    chasing a blob that was never written."""
    spool, d = _spool(bandwidth_limit=1e6, store_threads=1)  # ~1 MB/s
    t1, t2 = _tree(1), _tree(2)
    with spool.step("opt") as tx:
        spool.offload("blocker", t1)    # occupies the single store thread
        tx.offload(0, t2)               # waits in queue
        out = tx.peek(0)                # forwarded, NOT cancelled
        for a, b in zip(t2, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert spool.stats.stores_canceled == 0
        spool.wait_io()                 # the store still landed
        assert os.path.exists(os.path.join(d, "opt_s0.act"))
        out2 = tx.fetch(0)              # consuming fetch finds the blob
        for a, b in zip(t2, out2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    spool.drop("blocker")
    spool.close()


def test_prefetch_after_cancelled_store_skips_ghost_load():
    """Regression: prefetch on a record whose store was cancelled (its
    arrays still resident) used to enqueue a load for a blob that was
    never written — a ghost read that buried the backend error on the
    load job. CANCELED-with-arrays is in-memory: no load."""
    spool, _ = _spool(bandwidth_limit=1e6, store_threads=1)
    spool.offload("a", _tree(1))    # occupies the single store thread
    t = _tree(2)
    spool.offload("b", t)           # queued
    spool.fetch("b")                # forwards + cancels the write
    assert spool.stats.stores_canceled == 1
    spool.prefetch("b")             # must NOT enqueue a load
    assert spool._records["b"]["load_job"] is None
    out = spool.fetch("b")          # forwards the resident arrays
    for a, b in zip(t, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    spool.wait_io()
    assert spool.stats.num_loads == 0
    spool.close()


def test_refetch_after_cancel_forwards_resident_arrays():
    spool, _ = _spool(bandwidth_limit=1e6, store_threads=1)
    spool.offload("a", _tree(1))        # occupies the single store thread
    t = _tree(2)
    spool.offload("b", t)               # queued
    spool.fetch("b")                    # forwards + cancels the write
    assert spool.stats.stores_canceled == 1
    out = spool.fetch("b")              # must forward again, not raise
    for a, b in zip(t, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    spool.wait_io()
    spool.close()


def test_close_joins_workers_and_is_idempotent():
    spool, _ = _spool()
    spool.offload("k", _tree())
    threads = list(spool._threads)
    assert threads
    spool.close()
    assert all(not t.is_alive() for t in threads)
    spool.close()                   # second close: no-op
    with pytest.raises(RuntimeError):
        spool.step("late")          # no leases on a closed spool


def test_bandwidth_limit_enforced():
    spool, _ = _spool(bandwidth_limit=2e6)
    t = [jnp.ones((512, 512), jnp.float32)]   # 1 MB
    t0 = time.perf_counter()
    spool.offload("k", t)
    spool.wait_io()
    dt = time.perf_counter() - t0
    assert dt >= 0.4, dt            # >= nbytes / bw
    spool.close()


def test_write_time_leaves_the_bandwidth_cap_out():
    """`write_time` is the time inside the backend's write: the cap's
    sleep counts in `store_time` only, so the write rate stays the
    backend's."""
    spool, _ = _spool(bandwidth_limit=2e6, store_threads=1)
    spool.offload("k", [jnp.ones((512, 512), jnp.float32)])   # 1 MB
    spool.wait_io()
    st = spool.stats
    assert st.store_time >= st.bytes_offloaded / 2e6 - 1e-3
    assert 0.0 < st.write_time < 0.5 * st.store_time
    assert st.snapshot().sub(st).write_time == 0.0
    spool.close()


# ------------------------------------------- data-plane stat regressions


def test_write_bandwidth_zero_before_first_store():
    """Regression: SpoolStats.write_bandwidth returned inf before any
    store completed, and dryrun/roofline reports printed infinite
    bandwidth."""
    spool, _ = _spool()
    assert spool.stats.write_bandwidth == 0.0
    spool.offload("k", _tree())
    spool.wait_io()
    assert 0.0 < spool.stats.write_bandwidth < float("inf")
    spool.close()


class _FailingWriteBackend:
    """Minimal backend whose writes always fail (ENOSPC-style)."""

    def __init__(self):
        from repro.io import HostMemoryBackend
        self._inner = HostMemoryBackend()
        self.stats = self._inner.stats
        self.kind = "failing"

    def write_parts(self, key, parts):
        raise OSError(28, "No space left on device")

    write = write_parts

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_failed_store_forwarding_counted_once():
    """Regression: the failed-store forwarding branch ignored the
    fwd_counted flag, so a peek-then-fetch of a failed store inflated
    bytes_forwarded."""
    spool = ActivationSpool(_FailingWriteBackend(),
                            min_offload_elements=16)
    tree = _tree()
    nbytes = sum(np.asarray(x).nbytes for x in tree)
    spool.offload("k", tree)
    spool.wait_io()                      # store fails, arrays retained
    out1 = spool.fetch("k", cancel_pending=False)     # peek
    out2 = spool.fetch("k")                           # fetch
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert spool.stats.bytes_forwarded == nbytes, \
        "peek-then-fetch of a failed store must count ONE forwarding"
    assert spool.stats.num_forwarded == 1
    spool.drop("k")
    spool.close()


def test_pooled_load_lease_reused_across_steps():
    """Steady state of the pooled load path: the same aligned buffer
    serves successive loads (hit rate climbs), and dropped records
    release their leases back to the pool."""
    spool, _ = _spool()
    for step in range(4):
        spool.offload(f"s{step}", _tree(seed=step))
        spool.wait_io()
        out = spool.fetch(f"s{step}")
        assert len(out) == 3
        spool.drop(f"s{step}")
    stats = spool.pool.stats()
    assert stats["hits"] >= 2, stats     # buffers really got reused
    assert spool.pool.free_bytes > 0     # leases returned after drop
    spool.close()


def test_data_plane_stats_shape():
    spool, _ = _spool()
    spool.offload("k", _tree())
    spool.wait_io()
    spool.fetch("k")
    spool.drop("k")
    dp = spool.data_plane_stats()
    assert set(dp) == {"backend", "pool"}
    assert dp["backend"]["copies_per_byte"] == 0.0   # vectored fs path
    assert 0.0 <= dp["pool"]["hit_rate"] <= 1.0
    spool.close()


def test_decoding_codec_releases_lease_before_drop():
    """zlib/byteplane decodes own fresh memory, so the pooled read
    buffer must go back to the pool at load time, not sit pinned on the
    record until drop()."""
    spool, _ = _spool(codec="zlib")
    spool.offload("k", _tree())
    spool.wait_io()
    spool.prefetch("k")
    spool.wait_io()                       # load done, record not dropped
    assert spool.pool.free_bytes > 0, \
        "lease should be recycled as soon as the decode detaches"
    out = spool.fetch("k")
    assert len(out) == 3
    spool.drop("k")
    spool.close()
