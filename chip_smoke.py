#!/usr/bin/env python3
"""Bring-up smoke of the training path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one host with four chips (2x2 mesh)
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # rehearsal, toy sizes

One chip. Each Pallas kernel runs compiled at a published width and is
compared with its oracle in `repro.kernels.ref`. Then qwen2.5-3b at its
published widths, depth cut to 4 layers, trains 3 steps (batch 1 x 4096,
adamw, synthetic Markov data from --seed) through
`TrainSession(engine="jit")` under each activation placement: remat,
offload (XLA pinned_host) and spool (per-layer spool hooks on the fs
backend). The staged engine then runs 2 steps with `SpoolPolicy`.

A second pair runs at 2 layers, 2 steps: remat against the spool arm
with the default `on_fetch_fail="recompute"`. Its lax.cond recompute
branch needs ~3.8 GB more HBM than `"raise"` at these widths, whatever
the depth; compiled for a v5e it needs 16.03 GB at 2 layers and 17.16 GB
at 3, against 16.91 GB (15.75 GiB) usable.

Four chips. qwen2.5-3b widths on a 2x2 data x model mesh, 4 layers,
batch 2 x 2048: the per-shard spool hooks against remat on the same
mesh, 2 steps. The whole model is not run, because its spool would not
fit on disk: compiled for v5e:2x2 at 1 x 4096 tokens per data shard,
each layer spools ~1.6 GB per chip, so 36 layers queue ~230 GB per step,
against 85.8 GB free on the one-chip machine. At the fs spool rate
measured on one chip (29.5 GB in ~175 s) such a step would also take
~20 minutes. The cut keeps the spool and the final checkpoint at the
one-chip run's size.

Checks: every loss is finite and within LOSS_ATOL of the remat arm's at
the same step; the first step's gradient norm, which only a correct
backward reproduces, is within GRAD_RTOL of remat's; a spool arm moves
bytes through the spool, brings every offloaded record back (loaded
from the backend or forwarded from memory), and needs no fetch
fallback and no retry. Step times and peak memory are printed as smoke
output, not as benchmark numbers. The spool and the checkpoints go to
`.smoke_work/` in the checkout and are removed after each arm.

The last line is {"ok": true, "device": {...}}. A failed check, or a run
where JAX finds no TPU, exits non-zero without it. `--tiny` runs the
same phases at toy sizes on any backend, with the kernels in interpret
mode off the TPU, and prints no result line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

WORK = ROOT / ".smoke_work"
ARCH = "qwen2.5-3b"

# Agreement with the remat arm. On a v5e the placements of qwen2.5-3b
# at 4 layers agreed within 3.5e-4 in loss (at ~12.25), while one adamw
# step moves the loss by up to 1.7e-2: the loss limit keeps ~3x
# headroom over the former. The loss of the first step is forward only,
# so the first step's gradient norm, a sum over every parameter's
# gradient, must also agree; zeroed residuals move it by far more.
LOSS_ATOL = 1e-3
GRAD_RTOL = 2.0 ** -7
# Kernel vs oracle, as max|got - ref| over max|ref|: two bf16 ulps at
# the output's scale for bf16 outputs; f32 rounding with headroom for
# the chunked vs sequential summation order for f32 outputs.
BF16_TOL = 2.0 ** -6
F32_TOL = 1e-4


@dataclasses.dataclass(frozen=True)
class Sizes:
    flash: tuple            # (B, S, Hq, Hkv, D), bf16
    ssd: tuple              # (B, S, H, P, N), f32
    rglru: tuple            # (B, S, W), f32
    layers: int             # one chip
    fallback_layers: int    # one chip, spool with the recompute fallback
    seq: int
    ce_chunk: int
    attn_chunk: int
    steps: int              # jit arms
    staged_steps: int
    mesh_layers: int        # four chips
    mesh_seq: int
    mesh_steps: int
    min_offload: int = 1 << 20  # spool size filter (the paper's 2^20)


FULL = Sizes(flash=(1, 4096, 16, 2, 128),      # qwen2.5-3b attention
             ssd=(1, 4096, 80, 64, 128),       # mamba2-2.7b
             rglru=(1, 4096, 4096),            # recurrentgemma-9b
             layers=4, fallback_layers=2, seq=4096, ce_chunk=512,
             attn_chunk=512,
             steps=3, staged_steps=2,
             mesh_layers=4, mesh_seq=2048, mesh_steps=2)
TINY = Sizes(flash=(1, 256, 4, 2, 64), ssd=(1, 256, 4, 16, 32),
             rglru=(1, 128, 256), layers=2, fallback_layers=2, seq=128,
             ce_chunk=32,
             attn_chunk=32, steps=3, staged_steps=2,
             mesh_layers=2, mesh_seq=128, mesh_steps=2, min_offload=1024)


class Smoke:
    """Collects failed checks; the run is ok only if there are none."""

    def __init__(self):
        self.failures = []
        self.t0 = time.perf_counter()

    def check(self, ok, what: str) -> None:
        print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failures.append(what)

    def phase(self, name: str, fn, *args, **kw):
        """Run one phase; an exception fails the run but not the phases
        after it (each chip call should report every fault it can)."""
        print(f"== {name} (t={time.perf_counter() - self.t0:.1f}s)",
              flush=True)
        try:
            return fn(self, *args, **kw)
        except Exception:
            traceback.print_exc()
            self.failures.append(f"{name} raised")
            return None
        finally:
            gc.collect()


def _device_line(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _memory(jax) -> str:
    parts = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        parts.append(f"{st.get('peak_bytes_in_use', 'n/a')}"
                     f"/{st.get('bytes_in_use', 'n/a')}")
    return "peak_bytes_in_use/bytes_in_use per device: " + " ".join(parts)


# ------------------------------------------------------------ kernels

def kernel_phase(sm: Smoke, sizes: Sizes, seed: int, interpret: bool):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.kernels.rglru_scan import rglru_scan_fwd
    from repro.kernels.ssd_scan import ssd_scan_fwd

    ks = jax.random.split(jax.random.key(seed), 9)

    def compare(name, kernel, oracle, args, tol):
        t = time.perf_counter()
        got = jax.block_until_ready(kernel(*args))
        dt = time.perf_counter() - t
        # the oracles contract in full f32 so they are the reference,
        # not a second approximation
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(oracle)(*args))
        for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(want))):
            g = np.asarray(g, np.float32)
            w = np.asarray(w, np.float32)
            scale = float(np.max(np.abs(w)))
            err = float(np.max(np.abs(g - w)))
            sm.check(bool(np.isfinite(g).all()) and err <= tol * scale,
                     f"kernel {name}[{i}] {g.shape}: max|err| {err:.3e} "
                     f"<= {tol:.1e} x max|ref| {scale:.3e}")
        print(f"smoke: kernel {name} first call (compile + run) "
              f"{dt:.3f}s", flush=True)

    B, S, Hq, Hkv, D = sizes.flash
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.bfloat16)
    compare("flash_attention_fwd",
            functools.partial(flash_attention_fwd, causal=True,
                              interpret=interpret),
            functools.partial(ref.attention_reference, causal=True),
            (q, k, v), BF16_TOL)
    del q, k, v

    B, S, H, P, N = sizes.ssd
    xh = jax.random.normal(ks[3], (B, S, H, P), jnp.float32)
    da = -jnp.abs(0.2 * jax.random.normal(ks[4], (B, S, H), jnp.float32))
    bs = jax.random.normal(ks[5], (B, S, N), jnp.float32)
    cs = jax.random.normal(ks[6], (B, S, N), jnp.float32)
    compare("ssd_scan_fwd",
            functools.partial(ssd_scan_fwd, interpret=interpret),
            ref.ssd_reference, (xh, da, bs, cs), F32_TOL)
    del xh, da, bs, cs

    B, S, W = sizes.rglru
    la = -jnp.abs(0.5 * jax.random.normal(ks[7], (B, S, W), jnp.float32))
    x = jax.random.normal(ks[8], (B, S, W), jnp.float32)
    compare("rglru_scan_fwd",
            functools.partial(rglru_scan_fwd, interpret=interpret),
            ref.rglru_reference, (la, x), F32_TOL)


# ------------------------------------------------------------ training

def _config(layers: int, tiny: bool):
    from repro.configs import get_config, reduced
    cfg = get_config(ARCH)
    if tiny:
        cfg = reduced(cfg, d_model=128, heads=4, kv_heads=2, d_ff=256)
    return dataclasses.replace(cfg, num_layers=layers)


def _session(arm: str, cfg, sizes: Sizes, *, batch: int, seed: int,
             mesh=None, on_fetch_fail: str = "raise"):
    from repro.configs.base import SpoolIoConfig
    from repro.core.policies import SpoolPolicy
    from repro.models.transformer import RunSettings
    from repro.session import TrainSession

    work = WORK / arm
    settings = RunSettings(
        attn_impl="xla", attn_chunk=sizes.attn_chunk,
        activation_policy="remat" if arm == "staged" else arm,
        param_dtype=cfg.dtype, ce_chunk=sizes.ce_chunk)
    common = dict(optimizer="adamw", batch_size=batch, seq_len=sizes.seq,
                  seed=seed, settings=settings,
                  min_offload_elements=sizes.min_offload,
                  ckpt_dir=str(work / "ckpt"),
                  spool_dir=str(work / "spool"))
    if arm == "staged":
        return TrainSession(
            cfg, engine="staged", policy=SpoolPolicy(),
            io=SpoolIoConfig(backend="fs", directory=str(work / "spool"),
                             on_fetch_fail="raise"),
            **common)
    io = None
    if arm == "spool":
        io = SpoolIoConfig(backend="fs", directory=str(work / "spool"),
                           host_offload="activations",
                           on_fetch_fail=on_fetch_fail)
    return TrainSession(cfg, engine="jit", io=io, mesh=mesh, **common)


def run_arm(sm: Smoke, arm: str, cfg, sizes: Sizes, steps: int, *,
            batch: int, seed: int, mesh=None, label: str = None,
            on_fetch_fail: str = "raise"):
    """Train `steps` steps in a fresh session; returns plain data only,
    so the session's device state is gone when this returns."""
    import jax

    label = label or arm
    try:
        with _session(arm, cfg, sizes, batch=batch, seed=seed, mesh=mesh,
                      on_fetch_fail=on_fetch_fail) as sess:
            res = sess.run(steps)
            out = {"losses": res.losses,
                   "grad_norms": [r.extra["grad_norm"]
                                  for r in res.reports],
                   "step_s": [r.step_time for r in res.reports],
                   "stats": None, "shards": None}
            if sess.spool is not None:
                sess.spool.wait_io()
                out["stats"] = dataclasses.replace(sess.spool.stats)
            shards = {}
            for r in res.reports:
                for name, d in (r.shard_stats or {}).items():
                    tot = shards.setdefault(name, {})
                    for k, v in d.items():
                        tot[k] = tot.get(k, 0) + v
            out["shards"] = shards or None
            if sess.trainer is not None:
                print(f"smoke: arm={label} staged forward waited for "
                      f"device room {sess.trainer.device_room_waits} "
                      f"times", flush=True)
            del res
    finally:
        shutil.rmtree(WORK / arm, ignore_errors=True)
    gc.collect()
    print(f"smoke: arm={label} losses={out['losses']}", flush=True)
    print(f"smoke: arm={label} grad_norms={out['grad_norms']}", flush=True)
    print(f"smoke: arm={label} step_s (first includes compile)="
          f"{out['step_s']}", flush=True)
    print(f"smoke: arm={label} {_memory(jax)} (peak is the process "
          f"high-water mark so far)", flush=True)
    return out


def check_against_remat(sm: Smoke, results: dict, ref_arm: str = "remat"):
    ref = results.get(ref_arm)
    if ref is None:
        sm.check(False, f"{ref_arm} arm produced losses")
        return
    for arm, r in results.items():
        losses = r["losses"]
        sm.check(len(losses) > 0 and bool(np.all(np.isfinite(losses)))
                 and bool(np.all(np.isfinite(r["grad_norms"]))),
                 f"{arm}: {len(losses)} finite losses and gradient norms")
        if arm == ref_arm:
            continue
        for i, (a, b) in enumerate(zip(losses, ref["losses"])):
            sm.check(abs(a - b) <= LOSS_ATOL,
                     f"{arm} step {i} loss {a:.6f} vs {ref_arm} {b:.6f} "
                     f"within {LOSS_ATOL:.0e}")
        a, b = r["grad_norms"][0], ref["grad_norms"][0]
        sm.check(abs(a - b) <= GRAD_RTOL * abs(b),
                 f"{arm} step 0 gradient norm {a:.6g} vs {ref_arm} "
                 f"{b:.6g} within rtol {GRAD_RTOL:.2e} "
                 f"(rel {abs(a - b) / abs(b):.2e})")


def check_spool(sm: Smoke, arm: str, r: dict, *, per_shard: bool = False):
    st = r["stats"]
    print(f"smoke: arm={arm} spool {st}", flush=True)
    sm.check(st.bytes_offloaded > 0,
             f"{arm}: bytes_offloaded {st.bytes_offloaded} > 0")
    for k in ("fetch_fallbacks", "store_retries", "load_retries"):
        sm.check(getattr(st, k) == 0, f"{arm}: {k} {getattr(st, k)} == 0")
    # each record enqueued one store, which completed or was cancelled,
    # and came back exactly once: loaded from the backend or forwarded
    # from memory
    stored = st.num_stores + st.stores_canceled
    sm.check(stored == st.num_loads + st.num_forwarded,
             f"{arm}: stores {st.num_stores} + cancelled "
             f"{st.stores_canceled} == loads {st.num_loads} + forwarded "
             f"{st.num_forwarded}")
    shards = r["shards"]
    if shards is None:           # staged engine: no hook traffic
        return
    print(f"smoke: arm={arm} per-shard hook traffic {shards}", flush=True)
    tot = {k: sum(s.get(k, 0) for s in shards.values())
           for k in ("offloads", "fetches", "replica_skips",
                     "degraded_fetches")}
    # every stage a shard stored, or skipped as a replica, is fetched
    sm.check(tot["fetches"] == tot["offloads"] + tot["replica_skips"]
             and tot["degraded_fetches"] == 0,
             f"{arm}: fetches {tot['fetches']} == offloads "
             f"{tot['offloads']} + replica skips {tot['replica_skips']}, "
             f"no degraded fetch")
    sm.check(stored == tot["offloads"],
             f"{arm}: stores + cancelled {stored} == hook offloads "
             f"{tot['offloads']}")
    if per_shard:
        for name, s in sorted(shards.items()):
            sm.check(s.get("bytes_in", 0) > 0 and s.get("bytes_out", 0) > 0,
                     f"{arm}: shard {name} spool bytes in "
                     f"{s.get('bytes_in', 0)} / out {s.get('bytes_out', 0)}")


def one_chip(sm: Smoke, sizes: Sizes, seed: int, tiny: bool):
    cfg = _config(sizes.layers, tiny)
    print(f"smoke: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"layers={cfg.num_layers} batch=1 seq={sizes.seq}", flush=True)
    results = {}
    for arm in ("remat", "offload", "spool"):
        r = sm.phase(f"jit arm {arm}", run_arm, arm, cfg, sizes,
                     sizes.steps, batch=1, seed=seed)
        if r is not None:
            results[arm] = r
    r = sm.phase("staged engine", run_arm, "staged", cfg, sizes,
                 sizes.staged_steps, batch=1, seed=seed)
    if r is not None:
        results["staged"] = r
    check_against_remat(sm, results)
    for arm in ("spool", "staged"):
        if arm in results:
            check_spool(sm, arm, results[arm])

    # the spool arm as users get it by default: a failed fetch falls back
    # to recomputing the layer
    cfg = _config(sizes.fallback_layers, tiny)
    print(f"smoke: {cfg.name} layers={cfg.num_layers} spool with "
          f"on_fetch_fail='recompute'", flush=True)
    results = {}
    for arm, fail in (("remat", "raise"), ("spool", "recompute")):
        label = f"{arm}@{cfg.num_layers}L"
        r = sm.phase(f"jit arm {label}", run_arm, arm, cfg, sizes, 2,
                     batch=1, seed=seed, label=label, on_fetch_fail=fail)
        if r is not None:
            results[label] = r
    ref = f"remat@{cfg.num_layers}L"
    check_against_remat(sm, results, ref_arm=ref)
    label = f"spool@{cfg.num_layers}L"
    if label in results:
        check_spool(sm, label, results[label])


def four_chips(sm: Smoke, sizes: Sizes, seed: int, tiny: bool):
    import jax

    from repro.launch.mesh import make_test_mesh

    n = len(jax.devices())
    if n < 4:
        sm.check(False, f"--chips 4 needs 4 devices, have {n}")
        return
    mesh = make_test_mesh((2, 2), ("data", "model"))
    cfg = _config(sizes.mesh_layers, tiny)
    sizes = dataclasses.replace(sizes, seq=sizes.mesh_seq)
    print(f"smoke: {cfg.name} layers={cfg.num_layers} on mesh "
          f"{dict(mesh.shape)} batch=2 seq={sizes.seq}", flush=True)
    results = {}
    for arm in ("remat", "spool"):
        r = sm.phase(f"mesh arm {arm}", run_arm, arm, cfg, sizes,
                     sizes.mesh_steps, batch=2, seed=seed, mesh=mesh)
        if r is not None:
            results[arm] = r
    check_against_remat(sm, results)
    if "spool" in results:
        check_spool(sm, "spool", results["spool"], per_shard=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal at toy sizes on any backend; prints "
                         "no result line")
    args = ap.parse_args()

    import jax
    dev = _device_line(jax)
    print(f"smoke: device kind={dev['kind']!r} platform={dev['platform']} "
          f"count={dev['count']}", flush=True)
    on_tpu = dev["platform"] == "tpu"
    if not on_tpu and not args.tiny:
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev['platform']!r}); nothing was run")

    from repro.runtime.compile_cache import enable_compile_cache
    print(f"smoke: compile cache {enable_compile_cache()}", flush=True)

    sizes = TINY if args.tiny else FULL
    sm = Smoke()
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    print(f"smoke: work dir free bytes {shutil.disk_usage(WORK).free}",
          flush=True)
    try:
        if args.chips == 4:
            four_chips(sm, sizes, args.seed, args.tiny)
        else:
            sm.phase("kernels", kernel_phase, sizes, args.seed,
                     interpret=not on_tpu)
            one_chip(sm, sizes, args.seed, args.tiny)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(f"smoke: total {time.perf_counter() - sm.t0:.1f}s", flush=True)
    if sm.failures:
        print("FAILED:\n  " + "\n  ".join(sm.failures), file=sys.stderr)
        sys.exit(1)
    if args.tiny:
        print("smoke: tiny rehearsal passed (not a chip result)")
        return
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
