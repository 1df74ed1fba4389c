"""End-to-end training driver (deliverable b).

Both execution paths now route through `repro.session.TrainSession`,
selected by --engine:

  jit     — whole-step jax.jit training wrapped in the fault-tolerant
            TrainLoop (async checkpoints, preemption trap, straggler
            watchdog, resume). With --host-offload opt_state, the
            optimizer state is staged through the SpoolIoConfig-selected
            backend between steps; with --host-offload activations,
            per-layer residuals stream through that backend from inside
            the jitted step (repro.core.hooks io_callback path) — both
            engines share backend/codec selection either way.
  staged  — the TBA host-staged trainer (core/staged.py): per-module
            jitted stages with the ActivationSpool offloading real
            residuals to real disk, placement decided by an
            OffloadPolicy (--strategy maps onto policy objects). This is
            the paper's runnable path on this container.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gpt-124m \
      --steps 300 --batch 8 --seq 256 --engine jit --ckpt /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b:reduced \
      --steps 20 --engine staged --strategy offload
  PYTHONPATH=src python -m repro.launch.train --engine jit \
      --spool-backend mem --host-offload --steps 20
"""
from __future__ import annotations

import argparse
import time

from repro.configs.base import SpoolIoConfig
from repro.launch.cacheargs import add_cache_args, cache_overrides
from repro.runtime.compile_cache import enable_compile_cache
from repro.session import TrainSession, resolve_config  # noqa: F401
# resolve_config is re-exported for back-compat: it used to live here.


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="small-gpt")
    ap.add_argument("--engine", choices=["jit", "staged"], default="jit")
    ap.add_argument("--strategy", default="offload",
                    choices=["keep", "offload", "recompute", "adaptive",
                             "spool"],
                    help="staged engine: offload policy (maps onto "
                         "repro.session policy objects; 'offload' keeps "
                         "the seed meaning, adaptive planning)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", choices=["adamw", "sgd"],
                    default="adamw")
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--min-offload", type=int, default=None,
                    help="min elements to offload through the spool "
                         "(default: paper's 2**20)")
    ap.add_argument("--spool-backend", default="fs",
                    help="storage backend for the activation spool "
                         "(repro.io); honored by BOTH engines. A bare "
                         "kind (fs|striped|mem|tiered|managed|aio) or a "
                         "full repro.io spec string like "
                         "'fault@3:striped@2' or 'tiered:64mb,aio'. "
                         "'aio' is the O_DIRECT zero-copy data plane; "
                         "'managed' is the repro.cache storage brain "
                         "(see the --cache-* family)")
    ap.add_argument("--spool-dir", default=None,
                    help="spool directory (default: fresh temp dir, "
                         "removed on close)")
    ap.add_argument("--stripe-dirs", default=None,
                    help="comma-separated stripe directories for "
                         "--spool-backend striped/tiered (default: 2 "
                         "subdirs of the spool dir)")
    ap.add_argument("--codec", default="raw",
                    choices=["raw", "zlib", "byteplane"],
                    help="payload codec for spooled payloads; "
                         "'byteplane' splits bf16/fp16 into byte planes "
                         "and DEFLATEs only the compressible one")
    ap.add_argument("--host-mem-budget-mb", type=int, default=256,
                    help="tiered backend: host-RAM tier budget in MiB")
    ap.add_argument("--spool-align", type=int, default=4096,
                    help="data plane: buffer-pool / O_DIRECT alignment "
                         "(power of two)")
    ap.add_argument("--spool-queue-depth", type=int, default=4,
                    help="aio backend: concurrent aligned segments "
                         "submitted per blob")
    ap.add_argument("--spool-pool-mb", type=int, default=256,
                    help="idle cap of the shared aligned buffer pool "
                         "in MiB")
    ap.add_argument("--clip-norm", type=float, default=None,
                    metavar="NORM",
                    help="global grad-norm clip (adamw defaults to "
                         "1.0); 0 disables clipping — use it to build "
                         "a serial baseline comparable bit-for-bit "
                         "with --opt-overlap")
    ap.add_argument("--opt-overlap", action="store_true",
                    help="jit engine: eager per-layer optimizer updates "
                         "overlapped with backward — moment leases "
                         "stream through the spool backend while the "
                         "next layer's gradients compute "
                         "(repro.optim.overlap). Bitwise-identical to "
                         "the serial step. Implies a clip-free "
                         "optimizer (global-norm clipping needs every "
                         "gradient before any update); supersedes "
                         "--host-offload opt_state")
    ap.add_argument("--host-offload", nargs="?", const="opt_state",
                    default="none",
                    choices=["none", "opt_state", "activations"],
                    help="jit engine: what to route through the spool "
                         "backend — 'opt_state' stages the optimizer "
                         "state between steps (bare --host-offload "
                         "keeps meaning this); 'activations' streams "
                         "per-layer residuals from inside the jitted "
                         "step (repro.core.hooks); works on a --mesh "
                         "too (per-shard callbacks)")
    ap.add_argument("--mesh", default=None,
                    help="jit engine: device mesh shape, e.g. '2x4' "
                         "(data x model) or '8' (data only). Needs "
                         "that many jax devices (forced host devices "
                         "work: XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8). host-offload modes shard "
                         "their spool traffic per device")
    ap.add_argument("--spool-no-dedupe", action="store_true",
                    help="mesh activation offload: store one residual "
                         "copy PER DEVICE instead of one per replica "
                         "group (debugging / bandwidth experiments)")
    ap.add_argument("--retry-attempts", type=int, default=3,
                    help="resilience: total tries per spool I/O op "
                         "before the failure surfaces (1 disables "
                         "retry)")
    ap.add_argument("--retry-backoff-ms", type=float, default=10.0,
                    help="resilience: first retry delay in ms; doubles "
                         "per attempt, capped at 250 ms")
    ap.add_argument("--on-fetch-fail", default="recompute",
                    choices=["recompute", "raise"],
                    help="resilience: when a residual fetch ultimately "
                         "fails after retries, recompute the segment "
                         "from kept inputs (default) or raise and kill "
                         "the step")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable repro.obs tracing and write a Chrome/"
                         "Perfetto trace-event JSON here on exit "
                         "(load it at https://ui.perfetto.dev)")
    ap.add_argument("--trace-ring", type=int, default=0,
                    help="per-thread trace ring capacity in events "
                         "(default 65536; older events are dropped and "
                         "counted when a ring fills)")
    add_cache_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    mesh = None
    if args.mesh:
        if args.engine != "jit":
            ap.error("--mesh is a jit-engine flag")
        import jax
        from repro.launch.mesh import make_test_mesh
        try:
            shape = tuple(int(d) for d in args.mesh.lower().split("x"))
        except ValueError:
            ap.error(f"bad --mesh {args.mesh!r}; expected e.g. 2x4")
        if any(d < 1 for d in shape) or len(shape) > 3:
            ap.error(f"bad --mesh {args.mesh!r}; expected e.g. 2x4")
        ndev = 1
        for d in shape:
            ndev *= d
        if ndev > jax.device_count():
            ap.error(f"--mesh {args.mesh} needs {ndev} devices, have "
                     f"{jax.device_count()} (hint: XLA_FLAGS="
                     f"--xla_force_host_platform_device_count={ndev})")
        names = {1: ("data",), 2: ("data", "model"),
                 3: ("pod", "data", "model")}[len(shape)]
        if ndev > 1:
            mesh = make_test_mesh(shape, names)

    optimizer = args.optimizer
    if args.opt_overlap:
        if args.engine != "jit":
            ap.error("--opt-overlap is a jit-engine flag")
        if args.clip_norm:
            ap.error("--opt-overlap needs a clip-free optimizer "
                     "(global-norm clipping requires every gradient "
                     "before any update); pass --clip-norm 0 or drop "
                     "the flag")
    if args.opt_overlap or args.clip_norm is not None:
        from repro.optim.optimizers import adamw, sgd
        clip = (None if args.opt_overlap or not args.clip_norm
                else args.clip_norm)
        if args.optimizer == "adamw":
            optimizer = adamw(args.lr, clip_norm=clip)
            if args.opt_overlap:
                print("opt-overlap: using clip-free adamw (global-norm "
                      "clipping is incompatible with eager per-layer "
                      "updates)")
        else:
            optimizer = sgd(args.lr, clip_norm=clip)

    stripe_dirs = tuple(d for d in (args.stripe_dirs or "").split(",")
                        if d)
    cache_ov = cache_overrides(args)
    io = SpoolIoConfig(
        backend=cache_ov.pop("backend", args.spool_backend),
        directory=args.spool_dir,
        stripe_dirs=stripe_dirs, codec=args.codec,
        host_mem_budget_bytes=cache_ov.pop(
            "host_mem_budget_bytes", args.host_mem_budget_mb << 20),
        host_offload=args.host_offload,
        dedupe_replicas=not args.spool_no_dedupe,
        alignment=args.spool_align,
        queue_depth=args.spool_queue_depth,
        pool_bytes=args.spool_pool_mb << 20,
        retry_attempts=args.retry_attempts,
        retry_backoff_s=args.retry_backoff_ms / 1e3,
        on_fetch_fail=args.on_fetch_fail,
        **cache_ov)

    # the context manager guarantees teardown (worker-thread join, temp
    # spool/ckpt dir removal) on exceptions and Ctrl-C too
    with TrainSession(
            args.arch, engine=args.engine,
            policy=args.strategy if args.engine == "staged" else None,
            io=io, optimizer=optimizer, lr=args.lr,
            opt_overlap=args.opt_overlap or None,
            batch_size=args.batch, seq_len=args.seq, seed=args.seed,
            microbatches=args.microbatches, mesh=mesh,
            ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
            metrics_path=args.metrics, spool_dir=args.spool_dir,
            min_offload_elements=args.min_offload,
            trace=args.trace, trace_ring=args.trace_ring,
            install_signal_handlers=(args.engine == "jit")) as session:

        print(f"arch={session.cfg.name} "
              f"params={session.n_params/1e6:.1f}M engine={args.engine}"
              + (f" mesh={dict(mesh.shape)}" if mesh is not None else ""))
        if session.cfg.num_layers > 16:
            import jax
            if jax.device_count() == 1:
                print("note: full-size config on one CPU device — "
                      "consider '<arch>:reduced'")
        if session.spool is not None:
            print(f"spool backend={args.spool_backend} "
                  f"codec={args.codec}")

        def on_report(rep):
            if args.engine == "staged":
                print(f"step {rep.step - 1:4d} loss {rep.loss:.4f} "
                      f"t {rep.step_time:.2f}s "
                      f"act_peak {rep.peak_activation_bytes/1e6:.1f} MB "
                      f"offloaded {rep.stats.bytes_offloaded/1e6:.1f} MB",
                      flush=True)

        t0 = time.time()
        result = session.run(args.steps, resume=args.resume,
                             on_report=on_report)
        dt = time.time() - t0

        if session.spool is not None:
            session.spool.wait_io()     # drain in-flight stores so the
            bk = session.spool.backend  # busy clocks below are closed
            io_stats = bk.stats
            if io_stats.num_writes:
                print(f"backend[{bk.kind}] wrote "
                      f"{io_stats.bytes_written/1e6:.1f} MB @ "
                      f"{io_stats.write_bandwidth/1e9:.2f} GB/s, read "
                      f"{io_stats.bytes_read/1e6:.1f} MB", flush=True)
                dp = session.spool.data_plane_stats()
                print(f"data plane: "
                      f"{dp['backend']['copies_per_byte']:.2f} host "
                      f"copies/byte, pool hit rate "
                      f"{dp['pool']['hit_rate']:.0%} "
                      f"({dp['pool']['bytes_allocated']/1e6:.1f} MB "
                      f"ever allocated)", flush=True)
            if hasattr(bk, "per_device_write_bytes"):
                per_dev = bk.per_device_write_bytes()
                print("stripe write balance:",
                      [f"{b/1e6:.1f}MB" for b in per_dev], flush=True)
            rs = session.spool.stats
            if rs.store_retries or rs.load_retries or rs.fetch_fallbacks:
                print(f"resilience: {rs.store_retries} store retries, "
                      f"{rs.load_retries} load retries, "
                      f"{rs.fetch_fallbacks} recompute fallbacks; "
                      f"backend health={session.spool.health.status}",
                      flush=True)
        if session._opt_bridge is not None and session._opt_bridge.seeded:
            st = session._opt_bridge.stats()
            print(f"opt-overlap: {st['opt_updates']} per-layer updates, "
                  f"fetched {st['opt_fetched_bytes']/1e6:.1f} MB, staged "
                  f"{st['opt_staged_bytes']/1e6:.1f} MB, skipped "
                  f"{st['opt_stage_skips']} unchanged stage-backs "
                  f"({st['opt_skipped_bytes']/1e6:.1f} MB not rewritten)",
                  flush=True)
        if args.trace:
            last_obs = next((r.obs for r in reversed(result.reports)
                             if r.obs), None)
            if last_obs and last_obs["io_busy_s"] > 0:
                print(f"overlap (last step): "
                      f"{last_obs['io_hidden_frac']:.0%} of "
                      f"{last_obs['io_busy_s']*1e3:.1f} ms I/O hidden "
                      f"under compute; exposed stalls: read "
                      f"{last_obs['stall_read_s']*1e3:.1f} ms, decode "
                      f"{last_obs['stall_decode_s']*1e3:.1f} ms, queue "
                      f"{last_obs['stall_queue_s']*1e3:.1f} ms; "
                      f"prefetch hit rate "
                      f"{last_obs['prefetch_hit_rate']:.0%}", flush=True)
            if last_obs and last_obs.get("opt_io_busy_s", 0) > 0:
                print(f"opt overlap (last step): "
                      f"{last_obs['opt_hidden_frac']:.0%} of "
                      f"{last_obs['opt_io_busy_s']*1e3:.1f} ms opt-state "
                      f"I/O hidden under backward", flush=True)
        if args.engine == "jit":
            flagged = (len(session.watchdog.flagged)
                       if session.watchdog else 0)
            print(f"done: {result.state.step} steps in {dt:.1f}s "
                  f"({args.steps and dt/args.steps:.2f}s/step); "
                  f"stragglers flagged: {flagged}")

    # the session just closed — the trace file exists now
    if args.trace:
        print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
