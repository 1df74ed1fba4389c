"""Per-kernel validation: sweep shapes/dtypes and assert_allclose against
the pure-jnp oracles (interpret mode executes kernel bodies on CPU).
Gradients flow through the custom_vjp wrappers and are checked against
direct autodiff of the oracle."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.attention import attend_chunked, fused_block
from repro.models.mamba2 import ssd_chunked
from repro.models.rglru import rglru_scan_xla

RNG = np.random.default_rng(42)


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ------------------------------------------------------ flash attention

ATTN_CASES = [
    # (B, Sq, Skv, Hq, Hkv, D, causal, window, cap)
    (1, 128, 128, 4, 4, 32, True, 0, 0.0),      # MHA causal
    (2, 64, 64, 4, 2, 32, True, 0, 0.0),        # GQA
    (2, 64, 64, 4, 1, 32, True, 0, 0.0),        # MQA
    (1, 128, 128, 2, 2, 64, True, 32, 0.0),     # sliding window
    (1, 64, 64, 2, 2, 32, True, 0, 30.0),       # logit softcap (gemma2)
    (2, 64, 64, 4, 4, 32, False, 0, 0.0),       # bidirectional (BERT)
    (1, 96, 96, 2, 2, 32, True, 0, 0.0),        # non-multiple of block
    (1, 16, 16, 2, 2, 128, True, 0, 0.0),       # short seq, wide head
]


@pytest.mark.parametrize(
    "B,Sq,Skv,Hq,Hkv,D,causal,window,cap", ATTN_CASES)
def test_flash_attention_fwd(B, Sq, Skv, Hq, Hkv, D, causal, window, cap):
    q = _rand((B, Sq, Hq, D))
    k = _rand((B, Skv, Hkv, D))
    v = _rand((B, Skv, Hkv, D))
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              logit_cap=cap, interpret=True)
    want = ref.attention_reference(q, k, v, causal=causal, window=window,
                                   logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, tol):
    q = _rand((2, 64, 4, 32), dtype)
    k = _rand((2, 64, 2, 32), dtype)
    v = _rand((2, 64, 2, 32), dtype)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention_reference(q, k, v, causal=True)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_matches_production_xla_path():
    """Kernel == oracle == production chunked path (three-way check)."""
    q = _rand((2, 64, 4, 32))
    k = _rand((2, 64, 2, 32))
    v = _rand((2, 64, 2, 32))
    a = ops.flash_attention(q, k, v, causal=True, interpret=True)
    b = attend_chunked(q, k, v, causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grads():
    q = _rand((1, 32, 2, 16))
    k = _rand((1, 32, 2, 16))
    v = _rand((1, 32, 2, 16))

    def f_k(q, k, v):
        return ops.flash_attention(q, k, v, causal=True,
                                   interpret=True).sum()

    def f_r(q, k, v):
        return ref.attention_reference(q, k, v, causal=True).sum()

    gk = jax.grad(f_k, (0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------- fused causal pair (splash kernels)

def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("Hq,Hkv", [(4, 2), (8, 1)])
@pytest.mark.parametrize("S", [256, 512])
def test_causal_pair_matches_chunked_vjp(S, Hq, Hkv):
    """Forward and (dq, dk, dv) of the fused pair against jax.vjp of the
    chunked XLA path, bf16 GQA; block 128 so that fully masked blocks
    are skipped and the diagonal blocks masked."""
    q = _rand((1, S, Hq, 128), jnp.bfloat16)
    k = _rand((1, S, Hkv, 128), jnp.bfloat16)
    v = _rand((1, S, Hkv, 128), jnp.bfloat16)
    g = _rand((1, S, Hq, 128), jnp.bfloat16)
    out, vjp = jax.vjp(lambda *a: ops.causal_attention(
        *a, block=128, interpret=True), q, k, v)
    want, vjp_ref = jax.vjp(lambda *a: attend_chunked(
        *a, causal=True, chunk=128), q, k, v)
    assert out.dtype == jnp.bfloat16
    for name, a, b in zip(("out", "dq", "dk", "dv"),
                          (out, *vjp(g)), (want, *vjp_ref(g))):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 1e-2, name


_CELL = dict(head_dim=128, causal=True)

ROUTES = [
    # (platform, shapes and options, fused block or 0 = XLA)
    ("tpu", dict(sq=4096, skv=4096, **_CELL), 512),      # remat cell
    ("tpu", dict(sq=1024, skv=1024, **_CELL), 512),      # spool cell
    ("tpu", dict(sq=384, skv=384, **_CELL), 128),
    ("tpu", dict(sq=4096, skv=4096, window=1024, **_CELL), 0),
    ("tpu", dict(sq=4096, skv=4096, logit_cap=50.0, **_CELL), 0),
    ("tpu", dict(sq=4096, skv=4096, kv_len=4000, **_CELL), 0),
    ("tpu", dict(sq=512, skv=4096, q_offset=3584, **_CELL), 0),
    ("tpu", dict(sq=1, skv=4096, q_offset=4095, **_CELL), 0),   # decode
    ("tpu", dict(sq=4096, skv=4096, head_dim=64, causal=True), 0),
    ("tpu", dict(sq=4096, skv=4096, head_dim=128, causal=False), 0),
    ("tpu", dict(sq=4096, skv=4096, sharded=True, **_CELL), 0),
    ("tpu", dict(sq=200, skv=200, **_CELL), 0),          # no block divides
    ("cpu", dict(sq=4096, skv=4096, **_CELL), 0),
    ("gpu", dict(sq=1024, skv=1024, **_CELL), 0),
]


@pytest.mark.parametrize("platform,kw,block", ROUTES)
def test_fused_pair_routing(platform, kw, block):
    assert fused_block(platform, **kw) == block


PARTITIONED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.models.attention import gspmd_partitioned

mesh = jax.make_mesh((2, 2), ("a", "b"))
seen = {"none": gspmd_partitioned(None), "mesh": gspmd_partitioned(mesh)}

def body(key):
    def f(x):
        seen[key] = gspmd_partitioned(mesh)
        return x
    return f

x = jnp.ones((4, 4))
jax.jit(jax.shard_map(body("manual"), mesh=mesh, in_specs=P("a"),
                      out_specs=P("a")))(x)
jax.jit(jax.shard_map(body("partial"), mesh=mesh, in_specs=P("a"),
                      out_specs=P("a"), axis_names={"a"}))(x)
assert seen == {"none": False, "mesh": True, "manual": False,
                "partial": True}, seen
print("OK")
"""


def test_gspmd_partitioned_sees_manual_bodies():
    """The fused pair's `sharded` input: a multi-device mesh partitions
    the call unless it is traced inside a fully manual shard_map body
    (4 forced host devices, in a subprocess)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", PARTITIONED], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


# ------------------------------------------------------------ SSD scan

SSD_CASES = [
    # (B, S, H, P, N, chunk)
    (1, 64, 2, 16, 8, 16),
    (2, 128, 3, 32, 16, 32),
    (1, 256, 1, 64, 128, 128),     # production-like head geometry
    (2, 96, 2, 16, 8, 32),         # S not multiple of chunk -> shrinks
]


@pytest.mark.parametrize("B,S,H,P,N,chunk", SSD_CASES)
def test_ssd_scan_fwd(B, S, H, P, N, chunk):
    xh = _rand((B, S, H, P))
    a = -jnp.abs(_rand((B, S, H), scale=0.2))
    Bs = _rand((B, S, N))
    Cs = _rand((B, S, N))
    y, st = ops.ssd_scan(xh, a, Bs, Cs, chunk=chunk, interpret=True)
    yr, sr = ref.ssd_reference(xh, a, Bs, Cs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr),
                               rtol=2e-4, atol=2e-4)


def test_ssd_scan_matches_production_chunked():
    B, S, H, P, N = 2, 128, 2, 16, 8
    xh = _rand((B, S, H, P))
    a = -jnp.abs(_rand((B, S, H), scale=0.2))
    Bs = _rand((B, S, N))
    Cs = _rand((B, S, N))
    y1, s1 = ops.ssd_scan(xh, a, Bs, Cs, chunk=32, interpret=True)
    y2, s2 = ssd_chunked(xh, a, Bs, Cs, 32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-4, atol=2e-4)


def test_ssd_scan_grads():
    B, S, H, P, N = 1, 64, 2, 8, 4
    xh = _rand((B, S, H, P))
    a = -jnp.abs(_rand((B, S, H), scale=0.2))
    Bs = _rand((B, S, N))
    Cs = _rand((B, S, N))

    gk = jax.grad(lambda *t: ops.ssd_scan(
        *t, chunk=16, interpret=True)[0].sum(), (0, 1, 2, 3))(
        xh, a, Bs, Cs)
    gr = jax.grad(lambda *t: ref.ssd_reference(*t)[0].sum(),
                  (0, 1, 2, 3))(xh, a, Bs, Cs)
    for x, y in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------- RG-LRU scan

RGLRU_CASES = [
    (1, 64, 16, 256, 512),
    (2, 128, 32, 32, 16),          # width split into blocks
    (1, 100, 8, 256, 512),         # S=100 -> chunk shrinks to divisor
]


@pytest.mark.parametrize("B,S,W,chunk,blk_w", RGLRU_CASES)
def test_rglru_scan_fwd(B, S, W, chunk, blk_w):
    la = -jnp.abs(_rand((B, S, W), scale=0.5))
    x = _rand((B, S, W))
    h = ops.rglru_scan(la, x, interpret=True)
    hr = ref.rglru_reference(la, x)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-5, atol=1e-5)


def test_rglru_matches_production_associative_scan():
    la = -jnp.abs(_rand((2, 64, 16), scale=0.5))
    x = _rand((2, 64, 16))
    h1 = ops.rglru_scan(la, x, interpret=True)
    h2 = rglru_scan_xla(la, x)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-4, atol=1e-4)


def test_rglru_grads():
    la = -jnp.abs(_rand((1, 32, 8), scale=0.5))
    x = _rand((1, 32, 8))
    gk = jax.grad(lambda a, b: ops.rglru_scan(
        a, b, interpret=True).sum(), (0, 1))(la, x)
    gr = jax.grad(lambda a, b: ref.rglru_reference(a, b).sum(),
                  (0, 1))(la, x)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)
