"""Serving driver: continuous batching over a paged (or dense) KV
cache (repro.kvcache).

Slots turn over individually — a retiring sequence's slot refills from
the resume/new queues the same step, while the other slots keep
decoding. With `--cache paged` the KV lives in fixed-size device pages;
parked sequences (quantum preemption, `--quantum`) evict their pages
through the activation spool to SSD and prefetch them back under the
other slots' decode compute, so live sequences can exceed the device
slot count. `--cache dense` is the classic per-slot dense layout at the
same attention extent — same logits bitwise, concurrency capped at the
slot count.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b:reduced \
      --requests 32 --batch 8 --max-new 32
  PYTHONPATH=src python -m repro.launch.serve --arch small-gpt \
      --cache paged --quantum 8 --trace serve.trace.json

The old driver (batch-at-a-time, decode the whole batch to completion)
had a dead `while queue or done is None` loop clause and two
accounting skews — the first sampled token of every request was
dropped from the token counts and idle padding slots were billed as
decode work; the scheduler fixes all three (repro.kvcache.scheduler).
"""
from __future__ import annotations

import argparse
import json
import shutil

import jax
import numpy as np

from repro import obs
from repro.configs.base import SpoolIoConfig
from repro.core.spool import build_spool
from repro.kvcache import KVCacheConfig, Server, build_manager
from repro.launch.cacheargs import add_cache_args, cache_overrides
from repro.launch.train import resolve_config
from repro.models.api import build_model
from repro.models.transformer import RunSettings
from repro.runtime.compile_cache import enable_compile_cache


def build_runtime(arch: str, seed: int = 0):
    """Model api + initialized params + decode settings for an arch."""
    cfg = resolve_config(arch)
    if not cfg.has_decode:
        raise SystemExit("encoder-only arch has no decode step")
    api = build_model(cfg)
    settings = RunSettings(attn_impl="xla", attn_chunk=256,
                           param_dtype=cfg.dtype)
    params = api.init(jax.random.key(seed))
    return cfg, api, params, settings


def build_kv_spool(backend: str = "fs", directory=None,
                   codec: str = "byteplane", **io_kwargs):
    """A spool for KV pages: same data plane as training activations
    (bufpool + aio/fs + byteplane), but with the small-tensor bypass off
    — KV pages are small and must actually hit storage. Extra kwargs are
    `SpoolIoConfig` fields (the --cache-* family lands here). Returns
    (spool, owned_tmpdirs)."""
    io_cfg = SpoolIoConfig(backend=backend, directory=directory,
                           codec=codec, **io_kwargs)
    return build_spool(io_cfg, min_offload_elements=0)


def synth_requests(server: Server, n: int, prompt_len: int,
                   max_new: int, vocab: int, seed: int) -> None:
    """Submit the synthetic trace: variable prompt lengths in
    [prompt_len//2, prompt_len], fixed generation budget. Deterministic
    in the seed — the parity tests replay the same trace paged vs
    dense."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        plen = int(rng.integers(max(1, prompt_len // 2), prompt_len + 1))
        server.submit(rng.integers(0, vocab, plen), max_new)


def make_server(api, params, settings, kvcfg: KVCacheConfig, *,
                kind: str = "paged", n_slots: int = 8, spool=None,
                record_logits: bool = False) -> Server:
    cache = build_manager(kind, api, params, settings, kvcfg, n_slots,
                          spool)
    return Server(cache, record_logits=record_logits)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b:reduced")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8,
                    help="decode slots")
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128,
                    help="max logical sequence length (prompt + gen)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache", choices=("paged", "dense"),
                    default="paged")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="tokens per KV page")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="device page-pool size (0: worst-case sizing)")
    ap.add_argument("--quantum", type=int, default=0,
                    help="decode tokens before preemption (0: run to "
                         "retirement)")
    ap.add_argument("--max-live", type=int, default=0,
                    help="admission cap on live sequences (0: none)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="parked sequences prefetched ahead of refill")
    ap.add_argument("--kv-backend", default="fs",
                    choices=("fs", "aio", "mem", "managed"),
                    help="spool storage for evicted pages; 'managed' "
                         "is the repro.cache storage brain (see the "
                         "--cache-* family)")
    ap.add_argument("--kv-dir", default=None,
                    help="spool directory (default: fresh temp dir)")
    ap.add_argument("--kv-codec", default="byteplane",
                    choices=("raw", "zlib", "byteplane"))
    ap.add_argument("--trace", default=None,
                    help="write a Perfetto trace (kv.* page events, "
                         "serve.* scheduling, io.* spool lanes)")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the serve report as JSON")
    add_cache_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    if args.trace:
        obs.enable()
    cfg, api, params, settings = build_runtime(args.arch, args.seed)
    kvcfg = KVCacheConfig(
        page_tokens=args.page_tokens, pool_pages=args.pool_pages,
        max_seq_len=args.cache_len, prefetch_depth=args.prefetch_depth,
        quantum=args.quantum, max_live=args.max_live)

    spool = None
    owned = []
    if args.cache == "paged":
        cache_ov = cache_overrides(args)
        kv_backend = cache_ov.pop("backend", args.kv_backend)
        spool, owned = build_kv_spool(kv_backend, args.kv_dir,
                                      args.kv_codec, **cache_ov)
    try:
        server = make_server(api, params, settings, kvcfg,
                             kind=args.cache, n_slots=args.batch,
                             spool=spool)
        synth_requests(server, args.requests, args.prompt_len,
                       args.max_new, cfg.vocab_size, args.seed)
        report = server.run()
    finally:
        if spool is not None:
            spool.close()
        for d in owned:
            shutil.rmtree(d, ignore_errors=True)

    r = report
    print(f"served {r.requests} requests on {r.n_slots} slots "
          f"({r.cache_kind} cache) in {r.wall_time_s:.2f}s")
    print(f"prefill: {r.prompt_tokens} prompt tokens; "
          f"generated: {r.generated_tokens} tokens "
          f"({r.gen_tok_s:.0f} tok/s overall)")
    print(f"decode:  {r.decode_slot_tokens} slot-tokens over "
          f"{r.decode_steps} steps ({r.decode_tok_s:.0f} tok/s, "
          f"occupancy {r.slot_occupancy:.2f})")
    print(f"live:    peak {r.peak_live} mean {r.mean_live:.1f} "
          f"(preemptions {r.preemptions})")
    print(f"latency: ttft p50 {r.ttft_p50_ms:.1f}ms "
          f"p99 {r.ttft_p99_ms:.1f}ms; inter-token p50 "
          f"{r.itl_p50_ms:.1f}ms p95 {r.itl_p95_ms:.1f}ms "
          f"p99 {r.itl_p99_ms:.1f}ms")
    if r.kv.get("evictions") or r.kv.get("pages_allocated"):
        print(f"kv:      {r.kv['pages_allocated']} pages allocated, "
              f"{r.kv['pages_evicted']} evicted / "
              f"{r.kv['pages_restored']} restored "
              f"({r.kv['evictions']} evictions, "
              f"{r.kv['restores']} restores)")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report.as_dict(), f, indent=2, sort_keys=True)
        print(f"report -> {args.json_out}")
    if args.trace:
        path = obs.write_chrome_trace(args.trace, obs.get_tracer())
        print(f"trace -> {path}")


if __name__ == "__main__":
    main()
