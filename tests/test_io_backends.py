"""Tests for the repro.io storage-backend subsystem: spool round-trip /
forwarding / cancellation over every backend, the vectored zero-copy
data plane (write_parts / readinto / size, aligned buffer pool, aio
direct I/O), stripe balance + per-device endurance projection, tiered
eviction under the RAM budget, codec round-trips incl. byteplane, serde
edge cases, and the tiered adaptive-planner bandwidth model."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adaptive import (ModuleProfile, TierBandwidth,
                                 effective_write_bandwidth, plan_offload)
from repro.core.endurance import project_device_lifespans
from repro.core.spool import ActivationSpool
from repro.io import (CODECS, AioBackend, AlignedBufferPool,
                      FilesystemBackend, HostMemoryBackend,
                      StripedBackend, TieredBackend, backend_from_spec,
                      build_backend, deserialize_leaves, encode_parts,
                      pack, parse_bytes, serialize_leaves,
                      serialize_parts, unpack)

BACKEND_KINDS = ["fs", "striped", "mem", "tiered", "aio"]
CODEC_NAMES = ["raw", "zlib", "byteplane"]


def make_backend(kind: str, tmp_path, **kw):
    if kind == "fs":
        return FilesystemBackend(str(tmp_path / "fs"))
    if kind == "striped":
        return StripedBackend([str(tmp_path / f"s{i}") for i in range(3)],
                              chunk_bytes=kw.get("chunk_bytes", 1 << 12))
    if kind == "mem":
        return HostMemoryBackend()
    if kind == "tiered":
        return TieredBackend(FilesystemBackend(str(tmp_path / "lower")),
                             capacity_bytes=kw.get("capacity_bytes",
                                                   32 << 10))
    if kind == "aio":
        return AioBackend(str(tmp_path / "aio"),
                          queue_depth=kw.get("queue_depth", 4))
    raise AssertionError(kind)


def _tree(seed=0, n=3, shape=(64, 64)):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=shape), jnp.float32)
            for _ in range(n)]


# ------------------------------------------------------- raw backend API


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_backend_blob_roundtrip(kind, tmp_path):
    b = make_backend(kind, tmp_path)
    data = os.urandom(10_000)
    b.write("k", data)
    assert b.read("k") == data
    assert b.stats.bytes_written == len(data)
    assert b.stats.bytes_read == len(data)
    b.delete("k")
    with pytest.raises((FileNotFoundError, OSError)):
        b.read("k")
    b.delete("missing")          # missing-tolerant, like spool.drop
    b.close()


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_backend_shard_key_roundtrip(kind, tmp_path):
    """Per-shard spool keys carry a "/" (`jit0/s1_s3`); a file-backed
    backend must store them as one flat file, not a missing subdir."""
    b = make_backend(kind, tmp_path)
    data = os.urandom(10_000)
    for key in ("jit0/s1_s3", "jit0%2Fs1_s3"):
        b.write(key, data + key.encode())
    for key in ("jit0/s1_s3", "jit0%2Fs1_s3"):
        assert b.read(key) == data + key.encode()
        b.delete(key)
    b.close()


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_backend_reports_tier_bandwidths(kind, tmp_path):
    b = make_backend(kind, tmp_path)
    b.write("k", b"x" * 4096)
    tiers = b.tier_bandwidths()
    assert len(tiers) >= 1
    assert all(t.write_bw > 0 for t in tiers)
    if kind == "tiered":
        assert tiers[0].capacity_bytes == b.capacity_bytes
        assert tiers[-1].capacity_bytes is None


# ------------------------------------------------- spool over backends


@pytest.mark.parametrize("kind", BACKEND_KINDS)
@pytest.mark.parametrize("codec", CODEC_NAMES)
def test_spool_roundtrip_over_backend(kind, codec, tmp_path):
    spool = ActivationSpool(make_backend(kind, tmp_path), codec=codec,
                            min_offload_elements=16)
    trees = {f"k{i}": _tree(seed=i) for i in range(4)}
    for k, t in trees.items():
        spool.offload(k, t)
    spool.wait_io()
    assert spool.backend.stats.num_writes > 0
    for k in reversed(list(trees)):       # backward-order consumption
        out = spool.fetch(k)
        for a, b in zip(trees[k], out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        spool.drop(k)
    spool.close()


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_spool_forwarding_and_cancellation(kind, tmp_path):
    """fetch() during a slow store must forward the in-memory reference
    (§3.3.2) and cancel queued writes (§3.3.3 feature 1) on every
    backend."""
    spool = ActivationSpool(make_backend(kind, tmp_path),
                            bandwidth_limit=1e6, store_threads=1,
                            min_offload_elements=16)
    t1, t2 = _tree(1), _tree(2)
    spool.offload("a", t1)          # occupies the single store thread
    spool.offload("b", t2)          # waits in queue
    out = spool.fetch("b")          # must forward, not wait for storage
    for a, b in zip(t2, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert spool.stats.bytes_forwarded > 0
    assert spool.stats.stores_canceled >= 1
    spool.wait_io()
    spool.close()


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_spool_dedup_preserved_over_backend(kind, tmp_path):
    spool = ActivationSpool(make_backend(kind, tmp_path),
                            min_offload_elements=16)
    x = jnp.ones((128, 128), jnp.float32)
    spool.offload("k1", [x, x])     # same buffer twice
    spool.wait_io()
    assert spool.stats.bytes_deduped >= x.size * 4
    out = spool.fetch("k1")
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))
    spool.close()


def test_spool_drop_during_inflight_store_leaks_nothing(tmp_path):
    """drop() racing an in-flight (forwarded) store must not orphan the
    blob: on a RAM backend that would be a permanent memory leak."""
    backend = HostMemoryBackend()
    spool = ActivationSpool(backend, bandwidth_limit=2e6,
                            store_threads=1, min_offload_elements=16)
    spool.offload("a", _tree(1))    # slow store occupies the thread
    spool.offload("b", _tree(2))    # queued behind it
    spool.fetch("b")                # forwarded
    spool.fetch("a")                # forwarded from the RUNNING store
    spool.drop("a")                 # store still in flight
    spool.drop("b")                 # store still queued -> canceled
    spool.wait_io()
    assert backend.resident_bytes == 0, "orphaned blob left in RAM"
    spool.close()


# ----------------------------------------------------------- striping


def test_striped_balance_across_devices(tmp_path):
    dirs = [str(tmp_path / f"ssd{i}") for i in range(4)]
    b = StripedBackend(dirs, chunk_bytes=1 << 10)
    b.write("k", os.urandom(64 << 10))          # 64 chunks over 4 dirs
    per_dev = b.per_device_write_bytes()
    assert len([n for n in per_dev if n > 0]) >= 2
    assert max(per_dev) - min(per_dev) <= b.chunk_bytes
    for d in dirs:                              # files really spread out
        assert any(f.startswith("k.c") for f in os.listdir(d))
    assert b.read("k") == b.read("k")
    b.delete("k")
    assert all(not os.listdir(d) for d in dirs)


def test_striped_rewrite_with_fewer_chunks_prunes_tail(tmp_path):
    """Re-writing a key with a smaller blob must remove the old trailing
    chunks, or probe-based readers reassemble fresh+stale garbage."""
    dirs = [str(tmp_path / f"ssd{i}") for i in range(2)]
    b = StripedBackend(dirs, chunk_bytes=1 << 10)
    b.write("k", os.urandom(5 << 10))      # 5 chunks
    small = os.urandom(2 << 10)            # 2 chunks
    b.write("k", small)
    fresh = StripedBackend(dirs, chunk_bytes=1 << 10)
    assert fresh.read("k") == small
    b.delete("k")
    assert all(not os.listdir(d) for d in dirs)


def test_tiered_small_rewrite_clears_stale_lower_copy(tmp_path):
    """small -> oversize -> small leases of one key must never leave a
    stale lower-tier blob behind."""
    lower = HostMemoryBackend()
    b = TieredBackend(lower, capacity_bytes=1 << 10)
    b.write("k", os.urandom(1 << 20))      # oversize -> lower
    b.write("k", b"fresh-small")           # small -> upper
    assert b.read("k") == b"fresh-small"
    assert lower.resident_bytes == 0       # stale oversize copy purged
    b.delete("k")
    assert b.resident_bytes == 0 and lower.resident_bytes == 0


def test_striped_read_without_manifest(tmp_path):
    """A second backend over the same directories (fresh process view)
    must reassemble blobs by probing chunk files."""
    dirs = [str(tmp_path / f"ssd{i}") for i in range(2)]
    data = os.urandom(10_000)
    StripedBackend(dirs, chunk_bytes=1 << 10).write("k", data)
    fresh = StripedBackend(dirs, chunk_bytes=1 << 10)
    assert fresh.read("k") == data


def test_striped_endurance_projection(tmp_path):
    """Per-device write accounting feeds the Fig.9-style lifespan model:
    balanced stripes -> near-equal shares and finite per-drive lives."""
    b = StripedBackend([str(tmp_path / f"ssd{i}") for i in range(4)],
                       chunk_bytes=1 << 10)
    for i in range(8):
        b.write(f"k{i}", os.urandom(16 << 10))
    wear = project_device_lifespans(b.per_device_write_bytes(),
                                    elapsed_s=10.0)
    assert len(wear) == 4
    assert abs(sum(w.share for w in wear) - 1.0) < 1e-9
    assert max(w.share for w in wear) < 0.30    # balanced round-robin
    assert all(0 < w.lifespan_years < float("inf") for w in wear)
    # a skewed array ages its hot drive faster than a balanced one
    skewed = project_device_lifespans([3 << 20, 1 << 20], elapsed_s=10.0)
    assert skewed[0].lifespan_years < skewed[1].lifespan_years


# ------------------------------------------------------------- tiering


def test_tiered_eviction_respects_budget(tmp_path):
    lower = HostMemoryBackend()
    budget = 64 << 10
    b = TieredBackend(lower, capacity_bytes=budget)
    blobs = {f"k{i}": os.urandom(16 << 10) for i in range(10)}
    for k, v in blobs.items():
        b.write(k, v)
        assert b.resident_bytes <= budget
    assert b.evictions > 0
    # backward-access order: the *latest* stores (needed first by the
    # backward pass) are still in RAM; the earliest spilled to lower.
    assert "k9" in b.upper and "k0" not in b.upper
    assert lower.read("k0") == blobs["k0"]
    for k, v in blobs.items():                  # reads hit either tier
        assert b.read(k) == v
    b.delete("k9")
    b.delete("k0")
    assert "k9" not in b.upper


def test_tiered_oversize_blob_bypasses_ram(tmp_path):
    lower = HostMemoryBackend()
    b = TieredBackend(lower, capacity_bytes=1 << 10)
    big = os.urandom(1 << 20)
    b.write("big", big)
    assert b.resident_bytes == 0
    assert b.read("big") == big


def test_tiered_oversize_rewrite_replaces_resident_copy(tmp_path):
    """Rewriting a resident key with an over-budget blob must not leave
    the stale small copy shadowing it in RAM."""
    lower = HostMemoryBackend()
    b = TieredBackend(lower, capacity_bytes=1 << 10)
    b.write("k", b"small")
    big = os.urandom(1 << 20)
    b.write("k", big)
    assert b.read("k") == big
    assert b.resident_bytes == 0
    b.delete("k")
    assert lower.resident_bytes == 0


def test_spool_key_reuse_after_orphaned_store(tmp_path):
    """Re-offloading a key whose previous (dropped) store is still in
    flight must keep the new blob: the stale orphan cleanup must not
    delete the next lease's data."""
    backend = HostMemoryBackend()
    spool = ActivationSpool(backend, bandwidth_limit=2e6,
                            store_threads=1, min_offload_elements=16)
    t_old, t_new = _tree(1), _tree(5)
    spool.offload("k", t_old)       # slow store starts RUNNING
    spool.fetch("k")                # forwarded from the running store
    spool.drop("k")                 # orphans the in-flight write
    spool.offload("k", t_new)       # same key, new lease
    spool.wait_io()
    out = spool.fetch("k")
    for a, want in zip(out, t_new):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(want))
    spool.drop("k")
    spool.wait_io()
    assert backend.resident_bytes == 0
    spool.close()


# --------------------------------------- vectored data-plane contract


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_write_parts_matches_joined_write(kind, tmp_path):
    """The vectored path must store byte-identical blobs to the joined
    path, and `size` must report the true stored length."""
    b = make_backend(kind, tmp_path)
    parts = [b"head", os.urandom(10_000), b"", os.urandom(3)]
    joined = b"".join(parts)
    b.write_parts("vec", [memoryview(p) for p in parts])
    b.write("join", joined)
    assert b.read("vec") == joined == b.read("join")
    assert b.size("vec") == len(joined)
    assert b.stats.bytes_written == 2 * len(joined)
    b.close()


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_readinto_fills_caller_buffer(kind, tmp_path):
    b = make_backend(kind, tmp_path)
    data = os.urandom(20_000)
    b.write_parts("k", [memoryview(data)])
    pool = AlignedBufferPool()
    with pool.acquire(len(data)) as lease:
        mv = b.readinto("k", lease.mv)
        assert len(mv) == len(data)
        assert bytes(mv) == data
    # too-small buffer must be rejected, not silently truncated
    with pytest.raises((ValueError, FileNotFoundError)):
        b.readinto("k", memoryview(bytearray(100)))
    with pytest.raises((FileNotFoundError, OSError)):
        b.readinto("missing", memoryview(bytearray(1 << 15)))
    pool.close()
    b.close()


@pytest.mark.parametrize("kind", ["fs", "striped", "tiered"])
def test_vectored_fs_paths_copy_nothing(kind, tmp_path):
    """The zero-copy claim, as a number: fs-family vectored writes and
    pooled reads must not perform a single host-side payload copy."""
    b = make_backend(kind, tmp_path, capacity_bytes=0)  # tiered: all low
    parts = serialize_parts([np.arange(4096, dtype=np.float32)])
    b.write_parts("k", parts)
    pool = AlignedBufferPool()
    with pool.acquire(b.size("k")) as lease:
        b.readinto("k", lease.mv)
    assert b.stats.bytes_copied == 0
    if kind == "tiered":
        assert b.lower.stats.bytes_copied == 0
    pool.close()
    b.close()


def test_bufpool_alignment_and_reuse():
    pool = AlignedBufferPool(alignment=4096, max_bytes=1 << 20)
    a = pool.acquire(10_000)
    assert a.capacity % 4096 == 0 and a.capacity >= 10_000
    assert np.frombuffer(a.mv, np.uint8).ctypes.data % 4096 == 0
    a.mv[:5] = b"hello"
    a.release()
    a.release()                       # idempotent
    b = pool.acquire(9_000)           # same size class -> reuse
    assert pool.hits == 1 and pool.misses == 1
    b.release()
    assert pool.free_bytes == b.capacity
    pool.close()
    assert pool.free_bytes == 0


def test_bufpool_trims_beyond_cap():
    pool = AlignedBufferPool(alignment=4096, max_bytes=8192)
    leases = [pool.acquire(8192) for _ in range(3)]
    for lease in leases:
        lease.release()
    assert pool.trimmed == 2          # only one 8 KiB buffer cached
    assert pool.free_bytes <= 8192
    pool.close()


def test_bufpool_rejects_bad_alignment():
    with pytest.raises(ValueError):
        AlignedBufferPool(alignment=3000)
    with pytest.raises(ValueError):
        AlignedBufferPool(alignment=1 << 20)   # beyond page guarantee


def test_aio_backend_roundtrip_unaligned_sizes(tmp_path):
    """O_DIRECT padding/ftruncate must be invisible: arbitrary
    (unaligned) blob lengths round-trip exactly."""
    b = AioBackend(str(tmp_path / "aio"))
    for n in (0, 1, 511, 4096, 4097, 10_000, 70_001):
        data = os.urandom(n)
        b.write("k", data)
        assert b.size("k") == n
        assert b.read("k") == data
    b.close()


def test_aio_depth_one_no_executor(tmp_path):
    b = AioBackend(str(tmp_path / "aio"), queue_depth=1)
    data = os.urandom(30_000)
    b.write("k", data)
    assert b.read("k") == data
    b.close()


def test_aio_buffered_fallback_roundtrip(tmp_path):
    """direct=False exercises the buffered + fdatasync + fadvise path
    (what a filesystem without O_DIRECT gets)."""
    b = AioBackend(str(tmp_path / "aio"), direct=False)
    data = os.urandom(10_000)
    b.write_parts("k", [memoryview(data[:4000]), memoryview(data[4000:])])
    pool = AlignedBufferPool()
    with pool.acquire(len(data)) as lease:
        assert bytes(b.readinto("k", lease.mv)) == data
    pool.close()
    b.close()


def test_aio_readinto_unaligned_buffer_bounces(tmp_path):
    """A misaligned caller buffer must still be filled correctly (via
    the pooled aligned bounce)."""
    b = AioBackend(str(tmp_path / "aio"))
    data = os.urandom(9_000)
    b.write("k", data)
    raw = bytearray(len(data) + 1)
    mv = memoryview(raw)[1:]          # deliberately odd base address
    assert bytes(b.readinto("k", mv)) == data
    b.close()


def test_aio_rewrite_shrinking_blob_truncates(tmp_path):
    """In-place overwrite must not leave the previous lease's tail."""
    b = AioBackend(str(tmp_path / "aio"))
    b.write("k", os.urandom(50_000))
    small = os.urandom(5_000)
    b.write("k", small)
    assert b.size("k") == len(small)
    assert b.read("k") == small
    b.close()


def test_fs_write_is_atomic_no_temp_left(tmp_path):
    """The atomic-write contract: blobs appear only complete, temp
    files never survive, and a torn write (simulated) is rejected by
    serde instead of misparsed."""
    b = FilesystemBackend(str(tmp_path / "fs"))
    blob = serialize_leaves([np.arange(1024, dtype=np.float32)])
    b.write("k", blob)
    files = os.listdir(str(tmp_path / "fs"))
    assert files == ["k.act"]         # no .tmp leftovers
    # a crash mid-store under the OLD path left a truncated blob; the
    # serde guard must reject it loudly on "restart"
    with open(str(tmp_path / "fs" / "torn.act"), "wb") as f:
        f.write(blob[:len(blob) // 2])
    with pytest.raises(ValueError):
        deserialize_leaves(unpack(b.read("torn")))


def test_fs_write_failure_cleans_temp(tmp_path, monkeypatch):
    """If the vectored write dies mid-flight, the temp file must not
    accumulate (and the real blob must stay absent)."""
    b = FilesystemBackend(str(tmp_path / "fs"))
    import repro.io.backends as mod

    def boom(fd, parts, offset=0):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(mod, "pwritev_all", boom)
    with pytest.raises(OSError):
        b.write("k", b"x" * 1000)
    assert os.listdir(str(tmp_path / "fs")) == []


# -------------------------------------------------------------- codecs


@pytest.mark.parametrize("codec", sorted(CODECS))
def test_codec_pack_roundtrip(codec):
    payload = b"residual" * 4096
    blob = pack(payload, codec)
    assert bytes(unpack(blob)) == payload


def test_zlib_compresses_compressible_payloads():
    payload = np.zeros(1 << 16, np.float32).tobytes()
    assert len(pack(payload, "zlib")) < len(pack(payload, "raw"))


def test_byteplane_beats_zlib_on_bf16_residuals():
    """The codec's reason to exist: on realistic bf16 activations the
    high (sign+exponent) plane compresses while the mantissa plane is
    noise — byteplane must out-compress whole-stream zlib level 1."""
    import ml_dtypes
    rng = np.random.default_rng(0)
    a = rng.standard_normal(1 << 18).astype(np.float32)
    a[a < 0] *= 0.01
    payload = a.astype(ml_dtypes.bfloat16).tobytes()
    bp = len(pack(payload, "byteplane"))
    zl = len(pack(payload, "zlib"))
    raw = len(pack(payload, "raw"))
    assert bp < zl < raw
    assert bytes(unpack(pack(payload, "byteplane"))) == payload


def test_byteplane_chunked_and_incompressible():
    """Multi-chunk payloads round-trip (parallel encode path) and pure
    noise falls back to the per-chunk raw escape without growth beyond
    the per-chunk header."""
    from repro.io.codecs import BytePlaneCodec
    c = BytePlaneCodec(chunk_bytes=1 << 12)
    noise = os.urandom(5 * (1 << 12) + 123)     # 6 chunks, odd tail
    enc = c.encode(noise)
    assert bytes(c.decode(enc)) == noise
    assert len(enc) <= len(noise) + 16 + 6 * 16
    assert bytes(c.decode(c.encode(b""))) == b""


# ----------------------------------------------------- serde edge cases


def _edge_trees():
    import ml_dtypes
    rng = np.random.default_rng(7)
    return {
        "empty": [np.zeros((0,), np.float32), np.zeros((3, 0, 2),
                                                       np.int32)],
        "zero_d": [np.float32(3.25).reshape(()),
                   np.array(7, dtype=np.int64)],
        "ml_dtypes": [
            rng.standard_normal(257).astype(ml_dtypes.bfloat16),
            rng.standard_normal(64).astype(ml_dtypes.float8_e4m3fn),
            rng.standard_normal(33).astype(ml_dtypes.float8_e5m2),
        ],
        "mixed": [np.arange(100, dtype=np.uint8),
                  np.float32(1.5).reshape(()),
                  rng.standard_normal((17, 3)).astype(np.float16),
                  np.zeros((0, 5), ml_dtypes.bfloat16),
                  rng.integers(-9, 9, (4, 4, 4)).astype(np.int16)],
    }


@pytest.mark.parametrize("case", sorted(_edge_trees()))
@pytest.mark.parametrize("copy", [True, False])
def test_serde_edge_cases_roundtrip(case, copy):
    leaves = _edge_trees()[case]
    out = deserialize_leaves(serialize_leaves(leaves), copy=copy)
    assert len(out) == len(leaves)
    for a, got in zip(leaves, out):
        assert np.asarray(a).shape == got.shape
        assert np.asarray(a).dtype == got.dtype
        np.testing.assert_array_equal(np.asarray(a), got)


@pytest.mark.parametrize("kind", BACKEND_KINDS)
@pytest.mark.parametrize("codec", CODEC_NAMES)
def test_serde_edge_cases_through_backend(kind, codec, tmp_path):
    """Property-style: every edge tree survives the FULL data plane
    (serialize_parts -> encode_parts -> write_parts -> readinto ->
    unpack -> zero-copy deserialize) on every backend x codec pair."""
    b = make_backend(kind, tmp_path)
    trees = _edge_trees()
    pool = AlignedBufferPool()
    for name, leaves in trees.items():
        b.write_parts(name, encode_parts(serialize_parts(leaves), codec))
    for name, leaves in trees.items():
        n = b.size(name)
        assert n is not None and n > 0
        with pool.acquire(n) as lease:
            out = deserialize_leaves(unpack(b.readinto(name, lease.mv)),
                                     copy=False)
            assert len(out) == len(leaves)
            for a, got in zip(leaves, out):
                assert np.asarray(a).dtype == got.dtype
                assert np.asarray(a).shape == got.shape
                np.testing.assert_array_equal(np.asarray(a), got)
    pool.close()
    b.close()


def test_deserialize_views_are_readonly_and_copy_writable():
    blob = serialize_leaves([np.arange(64, dtype=np.float32)])
    views = deserialize_leaves(blob, copy=False)
    assert not views[0].flags.writeable     # borrowers cannot scribble
    copies = deserialize_leaves(blob, copy=True)
    assert copies[0].flags.writeable


def test_unpack_accepts_seed_format_blobs():
    """Pre-subsystem spool files had no container header; unpack must
    pass them through untouched."""
    legacy = serialize_leaves([np.ones((8, 8), np.float32)])
    out = deserialize_leaves(unpack(legacy))
    np.testing.assert_array_equal(out[0], np.ones((8, 8), np.float32))


def test_deserialized_arrays_are_writable():
    out = deserialize_leaves(serialize_leaves(
        [np.arange(16, dtype=np.float32)]))
    assert out[0].flags.writeable
    out[0][0] = 42.0                # must not raise
    assert out[0][0] == 42.0


# ----------------------------------------------- factory / spec strings


def test_parse_bytes_suffixes():
    assert parse_bytes("64kb") == 64 << 10
    assert parse_bytes("1.5mb") == int(1.5 * (1 << 20))
    assert parse_bytes("4096") == 4096


def test_backend_from_spec(tmp_path):
    base = str(tmp_path)
    assert isinstance(backend_from_spec("fs", base_dir=base),
                      FilesystemBackend)
    assert isinstance(backend_from_spec("mem"), HostMemoryBackend)
    s = backend_from_spec("striped@4", base_dir=base)
    assert isinstance(s, StripedBackend) and len(s.directories) == 4
    t = backend_from_spec("tiered:64kb,mem", base_dir=base)
    assert isinstance(t, TieredBackend)
    assert t.capacity_bytes == 64 << 10
    assert isinstance(t.lower, HostMemoryBackend)
    a = backend_from_spec("aio@8", base_dir=base)
    assert isinstance(a, AioBackend) and a.queue_depth == 8
    a2 = backend_from_spec(f"aio:{base}/dio", base_dir=base)
    assert isinstance(a2, AioBackend) and a2.directory == f"{base}/dio"
    with pytest.raises(KeyError):
        backend_from_spec("nvram", base_dir=base)


def test_build_backend_aio_from_config(tmp_path):
    from repro.configs.base import SpoolIoConfig
    ioc = SpoolIoConfig(backend="aio", queue_depth=2,
                        alignment=512, pool_bytes=1 << 20).validate()
    b = build_backend(ioc, default_dir=str(tmp_path))
    assert isinstance(b, AioBackend)
    assert b.queue_depth == 2 and b.alignment == 512
    assert b.pool.alignment == 512
    b.close()


def test_build_backend_from_config(tmp_path):
    from repro.configs.base import SpoolIoConfig
    ioc = SpoolIoConfig(backend="tiered",
                        stripe_dirs=(str(tmp_path / "a"),
                                     str(tmp_path / "b")),
                        host_mem_budget_bytes=1 << 20).validate()
    b = build_backend(ioc, default_dir=str(tmp_path))
    assert isinstance(b, TieredBackend)
    assert isinstance(b.lower, StripedBackend)


# ----------------------------------------- tiered planner bandwidth


def test_effective_bandwidth_blends_tiers():
    tiers = [TierBandwidth("ram", 10e9, 1000),
             TierBandwidth("ssd", 1e9, None)]
    assert effective_write_bandwidth(tiers, 500) == pytest.approx(10e9)
    # 1000 bytes at 10 GB/s + 1000 at 1 GB/s -> 2000/(1.1e-6 s)
    blended = effective_write_bandwidth(tiers, 2000)
    assert 1e9 < blended < 10e9
    assert blended == pytest.approx(2000 / (1000 / 10e9 + 1000 / 1e9))
    # deep overflow converges to the bottom tier's rate
    assert effective_write_bandwidth(tiers, 10 ** 9) == \
        pytest.approx(1e9, rel=0.01)


def test_calibration_measures_every_tier(tmp_path):
    """A calibration burst small enough to fit the RAM budget must still
    exercise the lower tier — an unmeasured tier reads as infinitely
    fast and the planner would treat spill traffic as free."""
    spool = ActivationSpool(make_backend("tiered", tmp_path,
                                         capacity_bytes=1 << 20),
                            codec="zlib", min_offload_elements=16)
    spool.calibrate_backend(64 << 10)
    tiers = spool.planner_bandwidth()
    assert isinstance(tiers, list) and len(tiers) == 2
    assert all(0 < t.write_bw < float("inf") for t in tiers)
    # the zlib codec bounds the store path: planner tiers must be slower
    # than the raw device measurement
    raw = spool.backend.tier_bandwidths()
    assert tiers[0].write_bw <= raw[0].write_bw
    spool.close()


def test_tiered_concurrent_spill_and_delete(tmp_path):
    """Deletes racing an in-flight eviction must not resurrect blobs in
    the lower tier."""
    lower = HostMemoryBackend()
    b = TieredBackend(lower, capacity_bytes=32 << 10)
    for i in range(8):
        b.write(f"k{i}", os.urandom(8 << 10))
    for i in range(8):
        b.delete(f"k{i}")
    assert b.resident_bytes == 0
    assert lower.resident_bytes == 0


def test_plan_offload_accepts_tiers():
    profiles = [ModuleProfile(f"m{i}", 10 ** 6, 0.1) for i in range(6)]
    fast = plan_offload(profiles, [TierBandwidth("ram", 1e12, None)])
    slow = plan_offload(profiles, [TierBandwidth("ssd", 1.0, None)])
    assert fast.num_offloaded == len(profiles) - 1   # keep-last rule
    assert slow.num_offloaded <= 1
    # a RAM budget covering only part of the traffic lands in between
    mid = plan_offload(profiles, [TierBandwidth("ram", 1e12, 2 * 10 ** 6),
                                  TierBandwidth("ssd", 1.0, None)])
    assert slow.num_offloaded <= mid.num_offloaded <= fast.num_offloaded
