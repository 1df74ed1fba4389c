import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the device
# count and platform at first init). This is a compile-only tool on a
# simulated mesh: pinning the CPU keeps this process and the per-cell
# children it spawns (which inherit the environment) off any attached
# accelerator. Everything else follows.

import argparse
import dataclasses
import gc
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs.base import SHAPES
from repro.configs.registry import (ARCH_IDS, cell_skip_reason, get_config,
                                    get_shape)
from repro.launch.hlo_stats import analyze_hlo, cost_analysis_dict
from repro.launch.mesh import (HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16,
                               make_production_mesh, mesh_axes)
from repro.launch.steps import make_step
from repro.cache.manager import plan_residency
from repro.io.backend import NOMINAL_WRITE_BW
from repro.models.api import build_model
from repro.optim.optimizers import adamw, sgd

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# AdamW fp32 moments for a ~1T-param model cannot fit any per-chip HBM at
# this scale; the paper hit the same wall on A100-40GB and switched to SGD
# (§4.1) — we do the same for the trillion-param cell.
SGD_PARAM_THRESHOLD = 400e9


def _slug(arch: str, shape: str, mesh_name: str) -> str:
    return f"{arch.replace('.', '_')}__{shape}__{mesh_name}"


def model_flops(kind: str, n_params: int, n_active: int,
                tokens: int) -> float:
    """6ND for training (fwd+bwd), 2ND for forward-only serving."""
    n = n_active
    return (6.0 if kind == "train" else 2.0) * n * tokens


def _predict_overlap(host_bytes: float, write_bw: float,
                     t_compute: float, *,
                     opt_bytes: float = 0.0,
                     opt_update_flops: float = 0.0) -> Dict[str, Any]:
    """Roofline prediction of how much activation I/O the step can hide.

    SSDTrain's schedule writes each layer's residuals during the forward
    pass and reads them back during the backward pass, so the store
    window is the forward compute time and the fetch window the backward
    compute time (fwd:bwd ~ 1:2 of the 6ND step). Whatever part of each
    transfer does not fit its window is exposed stall; the keys match
    `repro.obs.overlap.analyze()` so `predicted_vs_measured()` can pair
    this block with a traced run.

    With `opt_bytes > 0` (per-device optimizer-moment bytes, the
    opt-overlap bridge's traffic) the prediction also times the eager
    per-layer optimizer schedule: as each layer's gradients materialize
    in backward, its moments are fetched, the update computed on the
    side stream, and new moments staged back — all inside the backward
    window, sharing bandwidth with activation fetches. Keyword-only so
    existing positional call sites keep their meaning.
    """
    t_store = host_bytes / write_bw          # offload: fwd-side writes
    t_fetch = host_bytes / write_bw          # fetch: bwd-side reads
    t_io = t_store + t_fetch
    t_fwd = t_compute / 3.0                  # 2ND of the 6ND step
    t_bwd = t_compute * 2.0 / 3.0            # 4ND of the 6ND step
    exposed = (max(0.0, t_store - t_fwd) + max(0.0, t_fetch - t_bwd))
    # eager opt schedule: fetch + stage ride the backward window, on the
    # same spool bandwidth the activation fetches use; the side-stream
    # update itself is host compute, bandwidth-free
    t_opt_fetch = opt_bytes / write_bw
    t_opt_stage = opt_bytes / write_bw
    t_opt_update = (opt_update_flops / PEAK_FLOPS_BF16
                    if opt_update_flops else 0.0)
    t_opt_io = t_opt_fetch + t_opt_stage
    opt_window = max(0.0, t_bwd - max(0.0, t_fetch))  # leftover bwd room
    opt_exposed = max(0.0, t_opt_io + t_opt_update - opt_window) \
        if t_opt_io > 0 else 0.0
    return {
        "t_store_s": t_store,
        "t_fetch_s": t_fetch,
        "t_io_s": t_io,
        "t_fwd_s": t_fwd,
        "t_bwd_s": t_bwd,
        "per_stage_io_s": {"fwd_store": t_store, "bwd_fetch": t_fetch,
                           "bwd_opt_fetch": t_opt_fetch,
                           "bwd_opt_stage": t_opt_stage},
        "exposed_wait_s": exposed,
        "io_hidden_frac": (1.0 - exposed / t_io) if t_io > 0 else 1.0,
        "t_opt_io_s": t_opt_io,
        "t_opt_update_s": t_opt_update,
        "opt_exposed_wait_s": min(opt_exposed, t_opt_io),
        "opt_hidden_frac": ((1.0 - min(opt_exposed, t_opt_io) / t_opt_io)
                            if t_opt_io > 0 else 1.0),
    }


def _predict_residency(kind: str, host_bytes: float, n_params: int,
                       chips: int, optimizer: Optional[str],
                       host_bound_bytes: int) -> Dict[str, Any]:
    """Predicted per-class bytes per storage tier at this cell's planned
    micro-batch, from the cache manager's own placement model
    (`repro.cache.plan_residency`): nearest-reuse classes keep the
    bounded pinned-host tier, overflow lands on SSD. The per-class keys
    match the `cache_residency` block a managed-backend run emits in the
    metrics JSONL, so prediction and measurement pair row-for-row, the
    way `predicted_overlap` pairs with the obs tracer."""
    # fp32 moment state staged through the spool between steps: AdamW
    # carries two moments (8 B/param), plain SGD carries none
    opt_b = {"adamw": 8, "sgd": 0}.get(optimizer or "", 0)
    class_bytes = {
        "activation": int(host_bytes),
        "opt_state": (int(n_params / chips) * opt_b
                      if kind == "train" else 0),
        # train cells serve no decode traffic; serving predictions get
        # their KV footprint from the live kvcache, not the dry run
        "kv_page": 0,
    }
    return {
        "host_bound_bytes": int(host_bound_bytes),
        "per_class": plan_residency(class_bytes,
                                    host_bound_bytes=host_bound_bytes),
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, dump_hlo: bool = False,
             policy: Optional[str] = None, attn_chunk: int = 1024,
             force: bool = False, tag: str = "",
             baseline: bool = False,
             io_backend: str = "fs",
             cache_host_bound_mb: int = 256) -> Dict[str, Any]:
    if baseline:
        os.environ["REPRO_NO_BLOCKED_ATTN"] = "1"
        tag = tag or "paperbase"
    mesh_name = ("multi" if multi_pod else "single") + (f"-{tag}" if tag
                                                        else "")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, _slug(arch, shape_name, mesh_name)
                        + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "multi_pod": multi_pod, "kind": shape.kind,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "policy": policy, "attn_chunk": attn_chunk,
    }
    skip = cell_skip_reason(cfg, shape)
    if skip:
        rec.update(status="skip", skip_reason=skip)
        _write(path, rec)
        return rec

    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        axes = mesh_axes(mesh)
        chips = mesh.size
        api = build_model(cfg)
        if shape.kind == "train":
            from repro.launch.steps import _params_sds, count_params
            n_total = count_params(_params_sds(api), exclude=())
            opt = sgd() if n_total > SGD_PARAM_THRESHOLD else adamw()
            rec["optimizer"] = opt.name
            bundle = make_step(api, mesh, axes, shape, optimizer=opt,
                               activation_policy=policy,
                               ce_chunk=0 if baseline else 512)
        else:
            bundle = make_step(api, mesh, axes, shape)

        t0 = time.time()
        with mesh:
            lowered = jax.jit(bundle.fn,
                              out_shardings=bundle.out_shardings) \
                .lower(*bundle.args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        ca = cost_analysis_dict(compiled)
        hlo_text = compiled.as_text()
        ana = analyze_hlo(hlo_text, chips)
        if dump_hlo:
            with open(path.replace(".json", ".hlo.txt"), "w") as f:
                f.write(hlo_text)

        mf = model_flops(shape.kind, bundle.n_params, bundle.n_active,
                         bundle.tokens_per_step)
        flops_dev = ana.flops
        t_compute = flops_dev / PEAK_FLOPS_BF16
        t_memory = ana.hbm_bytes / HBM_BW
        t_coll = ana.collective_wire_bytes / ICI_BW_PER_LINK
        dominant = max(("compute", t_compute), ("memory", t_memory),
                       ("collective", t_coll), key=lambda kv: kv[1])[0]
        rec.update(
            status="ok",
            fsdp=bundle.fsdp,
            n_params=bundle.n_params, n_active=bundle.n_active,
            tokens_per_step=bundle.tokens_per_step,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory_analysis={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "host_argument_bytes": mem.host_argument_size_in_bytes,
                "host_temp_bytes": mem.host_temp_size_in_bytes,
                "peak_device_bytes": (mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      - mem.alias_size_in_bytes),
            },
            xla_cost_analysis={"flops": ca.get("flops"),
                               "bytes_accessed": ca.get("bytes accessed")},
            hlo={**ana.as_dict()},
            roofline={
                "chips": chips,
                "flops_per_device": flops_dev,
                "hbm_bytes_per_device": ana.hbm_bytes,
                "wire_bytes_per_device": ana.collective_wire_bytes,
                "host_bytes_per_device": ana.host_bytes,
                "t_compute_s": t_compute,
                "t_memory_s": t_memory,
                "t_collective_s": t_coll,
                "dominant": dominant,
                "model_flops_global": mf,
                "useful_flops_ratio": (mf / (flops_dev * chips)
                                       if flops_dev else None),
                # Offloaded-activation traffic projected onto the chosen
                # repro.io storage backend at its nominal write rate:
                # would the store path keep up with this cell?
                "io_backend": io_backend,
                "io_write_bw": NOMINAL_WRITE_BW[io_backend],
                "t_host_io_s": (ana.host_bytes
                                / NOMINAL_WRITE_BW[io_backend]),
            },
            # Predicted overlap for the SSDTrain schedule: stores overlap
            # the forward pass, fetches overlap the backward pass. The
            # fields mirror repro.obs.overlap.analyze() so a --trace run
            # can be checked against this prediction with
            # repro.obs.overlap.predicted_vs_measured().
            predicted_overlap=_predict_overlap(
                ana.host_bytes, NOMINAL_WRITE_BW[io_backend], t_compute,
                # fp32 moments per device, fetched+staged every step by
                # the eager per-layer schedule (adamw: 8 B/param)
                opt_bytes=(int(bundle.n_params / chips)
                           * {"adamw": 8, "sgd": 0}.get(
                               rec.get("optimizer") or "", 0))),
            # Predicted tier residency per tensor class under the
            # managed cache's placement model — pairs with the
            # cache_residency block of a --cache-managed run's metrics
            predicted_residency=_predict_residency(
                shape.kind, ana.host_bytes, bundle.n_params, chips,
                rec.get("optimizer"), cache_host_bound_mb << 20),
        )
    except Exception as e:  # record the failure, don't kill the sweep
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _write(path, rec)
    return rec


def _write(path: str, rec: Dict) -> None:
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)


def _cell_cmd(arch: str, shape: str, mesh: str, out_dir: str,
              extra) -> list:
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh, "--out", out_dir]
    return cmd + extra


def sweep(meshes, out_dir: str, force: bool, timeout: int,
          extra_args) -> int:
    """Run every runnable cell in its own subprocess (isolates compile
    memory; a crash doesn't kill the sweep)."""
    failures = 0
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            for mesh_name in meshes:
                slug = _slug(arch, shape.name, mesh_name)
                path = os.path.join(out_dir, slug + ".json")
                if os.path.exists(path) and not force:
                    continue
                if cell_skip_reason(cfg, shape):
                    run_cell(arch, shape.name,
                             multi_pod=(mesh_name == "multi"),
                             out_dir=out_dir)
                    continue
                print(f"[sweep] {slug}", flush=True)
                t0 = time.time()
                try:
                    r = subprocess.run(
                        _cell_cmd(arch, shape.name, mesh_name, out_dir,
                                  extra_args),
                        timeout=timeout, capture_output=True, text=True)
                    if r.returncode != 0:
                        failures += 1
                        _write(path, {
                            "arch": arch, "shape": shape.name,
                            "mesh": mesh_name, "status": "error",
                            "error": "subprocess failed",
                            "stderr": r.stderr[-4000:]})
                except subprocess.TimeoutExpired:
                    failures += 1
                    _write(path, {"arch": arch, "shape": shape.name,
                                  "mesh": mesh_name, "status": "error",
                                  "error": f"timeout after {timeout}s"})
                print(f"[sweep] {slug} done in {time.time()-t0:.0f}s",
                      flush=True)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="sweep every cell (subprocess per cell)")
    ap.add_argument("--out", default=os.path.normpath(RESULTS_DIR))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--dump-hlo", action="store_true")
    ap.add_argument("--policy", default=None,
                    choices=["keep", "remat", "offload", "save_names"])
    ap.add_argument("--attn-chunk", type=int, default=1024)
    ap.add_argument("--tag", default="", help="suffix for variant runs")
    ap.add_argument("--baseline", action="store_true",
                    help="disable beyond-paper graph opts (blocked "
                         "attention, chunked CE) for before/after runs")
    ap.add_argument("--io-backend", default="fs",
                    choices=sorted(NOMINAL_WRITE_BW),
                    help="repro.io backend whose nominal write bandwidth "
                         "prices the projected host-offload traffic")
    ap.add_argument("--cache-host-bound-mb", type=int, default=256,
                    help="pinned-host bound used by the "
                         "predicted_residency block (pair with the "
                         "--cache-host-bound-mb of the measured run)")
    ap.add_argument("--timeout", type=int, default=2400)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    extra = []
    if args.policy:
        extra += ["--policy", args.policy]
    if args.dump_hlo:
        extra += ["--dump-hlo"]
    if args.force:
        extra += ["--force"]
    if args.attn_chunk != 1024:
        extra += ["--attn-chunk", str(args.attn_chunk)]
    if args.tag:
        extra += ["--tag", args.tag]
    if args.io_backend != "fs":
        extra += ["--io-backend", args.io_backend]
    if args.cache_host_bound_mb != 256:
        extra += ["--cache-host-bound-mb", str(args.cache_host_bound_mb)]

    if args.all:
        n = sweep(meshes, args.out, args.force, args.timeout, extra)
        sys.exit(1 if n else 0)

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    for mesh_name in meshes:
        rec = run_cell(args.arch, args.shape,
                       multi_pod=(mesh_name == "multi"), out_dir=args.out,
                       dump_hlo=args.dump_hlo, policy=args.policy,
                       attn_chunk=args.attn_chunk, force=args.force,
                       tag=args.tag, baseline=args.baseline,
                       io_backend=args.io_backend,
                       cache_host_bound_mb=args.cache_host_bound_mb)
        status = rec.get("status")
        if status == "ok":
            rl = rec["roofline"]
            print(f"{args.arch} x {args.shape} [{mesh_name}] OK "
                  f"lower={rec['lower_s']}s compile={rec['compile_s']}s "
                  f"dominant={rl['dominant']} "
                  f"t=(c {rl['t_compute_s']:.3e}, m {rl['t_memory_s']:.3e},"
                  f" coll {rl['t_collective_s']:.3e})s")
            print("memory:", rec["memory_analysis"])
            po = rec.get("predicted_overlap")
            if po:
                print(f"predicted overlap [{rl['io_backend']}]: "
                      f"{po['io_hidden_frac']:.0%} of "
                      f"{po['t_io_s']:.3e}s I/O hidden "
                      f"(store {po['t_store_s']:.3e}s in fwd "
                      f"{po['t_fwd_s']:.3e}s, fetch "
                      f"{po['t_fetch_s']:.3e}s in bwd "
                      f"{po['t_bwd_s']:.3e}s)")
            pr = rec.get("predicted_residency")
            if pr:
                per = {cls: (f"{b['host_ram_bytes'] >> 20}MiB host + "
                             f"{b['ssd_bytes'] >> 20}MiB ssd")
                       for cls, b in pr["per_class"].items()}
                print(f"predicted residency (host bound "
                      f"{pr['host_bound_bytes'] >> 20}MiB): {per}")
        elif status == "skip":
            print(f"{args.arch} x {args.shape} [{mesh_name}] SKIP: "
                  f"{rec['skip_reason']}")
        else:
            print(f"{args.arch} x {args.shape} [{mesh_name}] ERROR: "
                  f"{rec.get('error')}")
            print(rec.get("traceback", "")[-2000:])
            sys.exit(1)


if __name__ == "__main__":
    main()
