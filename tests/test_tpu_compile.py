"""The Pallas kernels compile for a TPU v5e at the published widths.

Interpret mode (tests/test_kernels.py) checks the math on the CPU but not
what the chip's compiler accepts: block shapes must tile (8, 128) and
some primitives have no Mosaic lowering. These cases compile each kernel
for one chip of a described v5e:2x2 topology (nothing runs) and check
that the kernel is in the program.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rglru_scan import rglru_scan_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (B, S, Hq, Hkv, D): qwen2.5-3b and granite-3-2b attention widths
FLASH_WIDTHS = [(1, 4096, 16, 2, 128), (1, 4096, 32, 8, 64)]


@pytest.mark.parametrize("B,S,Hq,Hkv,D", FLASH_WIDTHS)
def test_flash_attention_compiles(one_chip, B, S, Hq, Hkv, D):
    q = _sds(one_chip, (B, S, Hq, D), jnp.bfloat16)
    kv = _sds(one_chip, (B, S, Hkv, D), jnp.bfloat16)
    compiled = flash_attention_fwd.lower(q, kv, kv, causal=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles(one_chip):
    # mamba2-2.7b: 80 heads of P=64, state N=128, chunk 128
    B, S, H, P, N = 1, 4096, 80, 64, 128
    compiled = ssd_scan_fwd.lower(
        _sds(one_chip, (B, S, H, P)), _sds(one_chip, (B, S, H)),
        _sds(one_chip, (B, S, N)), _sds(one_chip, (B, S, N))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rglru_scan_compiles(one_chip):
    # recurrentgemma-9b: RG-LRU width 4096
    B, S, W = 1, 4096, 4096
    compiled = rglru_scan_fwd.lower(_sds(one_chip, (B, S, W)),
                                    _sds(one_chip, (B, S, W))).compile()
    assert "tpu_custom_call" in compiled.as_text()
