"""Fault-injection tests for the spool stack (`repro.io.faults`).

A `FaultInjectingBackend` wraps any registered backend and injects
write failures, short reads and delayed completion, driving the
recovery paths that healthy hardware only exercises by accident:
failed-store-then-fetch tensor forwarding, lease cleanup on exception,
truncated-blob surfacing with pool-lease release, cancellation /
forwarding under slow stores, and the aio backend's wait-for-sibling-
segments contract on a failed submission.
"""
import os
import threading
import time

import numpy as np
import pytest

from repro.core.spool import ActivationSpool
from repro.io import (BACKENDS, AioBackend, FaultInjectingBackend,
                      FilesystemBackend, HostMemoryBackend,
                      backend_from_spec)

MIN_OFF = 4


def _tree(rng, n=4096):
    return {"a": rng.normal(size=(n,)).astype(np.float32),
            "b": rng.normal(size=(n, 2)).astype(np.float32)}


def _tree_bytes(tree):
    return sum(a.nbytes for a in tree.values())


def _assert_tree_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def _spool(backend, **kw):
    kw.setdefault("min_offload_elements", MIN_OFF)
    kw.setdefault("store_threads", 1)
    kw.setdefault("load_threads", 1)
    return ActivationSpool(backend, **kw)


# ------------------------------------------------------------ factory

def test_fault_backend_registered_and_spec_constructible():
    assert "fault" in BACKENDS
    bk = backend_from_spec("fault@2:mem")
    assert isinstance(bk, FaultInjectingBackend)
    assert isinstance(bk.inner, HostMemoryBackend)
    assert bk.zero_copy_read            # mirrors the inner backend
    with pytest.raises(OSError):
        bk.write("k", b"x" * 64)
    with pytest.raises(OSError):
        bk.write("k", b"x" * 64)
    bk.write("k", b"x" * 64)            # third write succeeds
    assert bk.injected["write_failures"] == 2
    assert bk.read("k") == b"x" * 64
    bk.close()


def test_fault_spec_wraps_fs_and_owns_tmpdir(tmp_path):
    bk = backend_from_spec(f"fault:fs:{tmp_path}/inner")
    assert isinstance(bk.inner, FilesystemBackend)
    assert bk.directory == f"{tmp_path}/inner"
    bk.write("k", b"payload")
    assert bk.read("k") == b"payload"
    bk.close()


# ----------------------------------------- failed-store recovery paths

def test_failed_store_then_fetch_forwards_in_memory():
    """A store that dies on the device (ENOSPC-style) must not lose the
    step: fetch forwards the still-referenced arrays instead of chasing
    a blob that never landed."""
    bk = FaultInjectingBackend(HostMemoryBackend(), fail_writes=1,
                               write_exc=OSError(28, "No space left"))
    spool = _spool(bk)
    rng = np.random.default_rng(0)
    tree = _tree(rng)
    with spool.step("mb0") as tx:
        tx.offload(0, tree)
        spool.wait_io()                  # store failed; arrays resident
        out = tx.fetch(0)
        _assert_tree_equal(tree, out)
        tx.drop(0)                       # delete of unwritten key: no-op
    assert spool.stats.bytes_forwarded == _tree_bytes(tree)
    assert bk.injected["write_failures"] == 1
    assert bk.inner.stats.num_writes == 0
    assert not spool._records
    spool.close()


def test_failed_store_peek_then_fetch_counts_one_forwarding():
    """Peek-then-fetch of one failed store is ONE forwarding event (the
    fwd_counted regression), even through the injector. Three armed
    failures defeat the default 3-attempt retry, so the store really
    fails (a single transient failure is ridden out since resilience)."""
    bk = FaultInjectingBackend(HostMemoryBackend(), fail_writes=3)
    spool = _spool(bk)
    rng = np.random.default_rng(1)
    tree = _tree(rng)
    with spool.step("mb0") as tx:
        tx.offload(0, tree)
        spool.wait_io()
        _assert_tree_equal(tree, tx.peek(0))
        _assert_tree_equal(tree, tx.fetch(0))
        tx.drop(0)
    assert spool.stats.bytes_forwarded == _tree_bytes(tree)
    assert spool.stats.num_forwarded == 1
    assert spool.stats.store_retries == 2    # attempts 2 and 3
    spool.close()


def test_lease_dropped_on_exception_mid_step():
    """An exception between offload and fetch must not strand records:
    the transaction's close() drops everything, including blobs whose
    (delayed) store is still in flight when the step aborts."""
    bk = FaultInjectingBackend(HostMemoryBackend(), write_delay=0.2)
    spool = _spool(bk)
    rng = np.random.default_rng(2)
    with pytest.raises(RuntimeError, match="step exploded"):
        with spool.step("mb0") as tx:
            tx.offload(0, _tree(rng))
            tx.offload(1, _tree(rng))
            raise RuntimeError("step exploded")
    spool.wait_io()
    assert not spool._records            # every record dropped
    # an orphaned in-flight write is deleted when it lands; nothing may
    # survive on the backend
    assert len(bk.inner._blobs) == 0
    spool.close()
    # the lease itself was released: the step id is reusable
    spool2 = _spool(FaultInjectingBackend(HostMemoryBackend()))
    with spool2.step("mb0"):
        pass
    spool2.close()


def test_short_read_surfaces_error_and_releases_pool(tmp_path):
    """A truncated blob (torn write / bad device) must surface as a
    load error at fetch — not a hang, not a corrupt tree — and the
    pooled load buffer must go back to the pool."""
    bk = FaultInjectingBackend(FilesystemBackend(str(tmp_path)),
                               short_reads=1, short_by=8)
    spool = _spool(bk)
    rng = np.random.default_rng(3)
    tree = _tree(rng)
    with spool.step("mb0") as tx:
        tx.offload(0, tree)
        spool.wait_io()                  # store landed; memory released
        with pytest.raises(RuntimeError, match="spool load failed"):
            tx.fetch(0)
        tx.drop(0)
    assert bk.injected["short_reads"] == 1
    # the failed load's pool lease was released, not leaked
    pstats = spool.pool.stats()
    assert pstats["free_bytes"] == pstats["bytes_allocated"]
    # the spool stays usable: a healthy record round-trips after the
    # failure (the worker survived the poisoned job)
    with spool.step("mb1") as tx:
        tx.offload(0, tree)
        spool.wait_io()
        _assert_tree_equal(tree, tx.fetch(0))
        tx.drop(0)
    spool.close()


def test_delayed_store_completion_forwarding_and_cancel():
    """Slow stores widen the forwarding windows: a fetch racing a
    QUEUED store cancels it, one racing a RUNNING store forwards and
    lets the write land — counters must account for both exactly."""
    bk = FaultInjectingBackend(HostMemoryBackend(), write_delay=0.3)
    spool = _spool(bk)                   # store_threads=1: 2nd job queues
    rng = np.random.default_rng(4)
    t0, t1 = _tree(rng), _tree(rng)
    with spool.step("mb0") as tx:
        tx.offload(0, t0)                # worker picks up, sleeps
        time.sleep(0.05)                 # let the worker reach RUNNING
        tx.offload(1, t1)                # still QUEUED behind it
        _assert_tree_equal(t1, tx.fetch(1))   # queued -> cancel+forward
        _assert_tree_equal(t0, tx.fetch(0))   # running -> forward
        tx.drop(0)
        tx.drop(1)
    spool.wait_io()
    assert spool.stats.bytes_forwarded == _tree_bytes(t0) + _tree_bytes(t1)
    assert spool.stats.num_forwarded == 2
    assert spool.stats.stores_canceled >= 1
    assert spool.stats.num_stores + spool.stats.stores_canceled == 2
    spool.close()


# ------------------------------------------------- aio sibling waits

@pytest.mark.skipif(not hasattr(os, "pwritev"), reason="needs pwritev")
def test_aio_failed_segment_waits_for_sibling_writes(tmp_path,
                                                     monkeypatch):
    """When one of a blob's concurrent segments fails, the aio backend
    must wait for every sibling pwritev to finish before closing the
    fd — closing early would let the OS recycle the descriptor under a
    still-running write (cross-blob corruption)."""
    backend = AioBackend(str(tmp_path), queue_depth=4, direct=False)
    events = []
    fds = set()
    lock = threading.Lock()
    real_pwritev, real_close = os.pwritev, os.close

    def slow_pwritev(fd, bufs, offset):
        with lock:
            fds.add(fd)
        if offset == 0:
            raise OSError(5, "injected segment failure")
        time.sleep(0.25)
        n = real_pwritev(fd, bufs, offset)
        with lock:
            events.append(("pwritev_done", fd, time.monotonic()))
        return n

    def traced_close(fd):
        with lock:
            if fd in fds:
                events.append(("close", fd, time.monotonic()))
        return real_close(fd)

    monkeypatch.setattr(os, "pwritev", slow_pwritev)
    monkeypatch.setattr(os, "close", traced_close)
    payload = os.urandom(1 << 20)        # 4 x 256 KiB segments
    with pytest.raises(OSError):
        backend.write("blob", payload)
    monkeypatch.undo()
    closes = {fd: t for ev, fd, t in events if ev == "close"}
    done = [(fd, t) for ev, fd, t in events if ev == "pwritev_done"]
    assert done, "sibling segments never ran"
    for fd, t in done:
        assert fd in closes, "fd never closed"
        assert t <= closes[fd], \
            "fd closed while a sibling pwritev was still running"
    backend.close()


def test_fault_injection_through_spool_store_path_keeps_worker_alive():
    """Armed at runtime: a burst of failures mid-training must not kill
    the store workers — later steps keep spooling normally."""
    bk = FaultInjectingBackend(HostMemoryBackend())
    spool = _spool(bk)
    rng = np.random.default_rng(5)
    ok = _tree(rng)
    with spool.step("s0") as tx:
        tx.offload(0, ok)
        spool.wait_io()
        _assert_tree_equal(ok, tx.fetch(0))
        tx.drop(0)
    bk.arm_write_failures(3, key_substr="s1")  # defeats 3-try retry
    bad = _tree(rng)
    with spool.step("s1") as tx:
        tx.offload(0, bad)
        spool.wait_io()
        _assert_tree_equal(bad, tx.fetch(0))   # forwarded
        tx.drop(0)
    with spool.step("s2") as tx:               # healthy again
        tx.offload(0, ok)
        spool.wait_io()
        _assert_tree_equal(ok, tx.fetch(0))
        tx.drop(0)
    assert bk.injected["write_failures"] == 3
    assert spool.stats.store_retries == 2
    assert spool.stats.num_stores == 2
    spool.close()
