"""The benchmark's run of one cell, below the look for a chip.

Everything a cell is made of is found by name: the workload file
`bench/workloads/<cell>.json`, the configuration file it names
`bench/configs/<config>.json`, the module of the family that file
names `bench/families/<family>.py` (its mapping to the program, its
FLOPs, its layout and its plain blocks), and one reader per metric
`bench/metrics/<metric>.py`, which `BENCHMARK.json` lists for the cell.
Adding a cell, a configuration, a family or a metric adds files; no
code here names one.

A run, in order:
  set-up    weights and optimizer state made on the device from the
            seed; the program's `TrainSession(engine="jit")` built for
            the cell; its first SETUP_STEPS steps driven through
            `TrainSession.run` and the harness's loader (the first one
            compiles). After step 1 the optimizer's first moment gives
            the first gradient as the optimizer got it; after the last
            set-up step the parameters give their change.
  window    the same `run` call goes on: the loader hands out batches
            until `seconds` have passed, then stops at a step boundary.
  after     peak memory is read, the program's state freed, and the
            plain reference follows the set-up steps from the same seed.
"""
from __future__ import annotations

import contextlib
import gc
import hashlib
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# steps driven in set-up and followed by the reference: the first step's
# gradient and the third step's parameter change are compared
SETUP_STEPS = 3


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------- lookup

def _by_name(kind: str, name: str, suffix: str = ".json") -> Path:
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return path


def load_benchmark() -> Dict[str, Any]:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_workload(name: str) -> Dict[str, Any]:
    with open(_by_name("workloads", name)) as f:
        wl = json.load(f)
    wl["name"] = name
    return wl


def load_config(name: str) -> Dict[str, Any]:
    with open(_by_name("configs", name)) as f:
        return json.load(f)


def _module(kind: str, name: str):
    path = _by_name(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[["RunRecord"], Optional[float]]:
    return _module("metrics", name).read


def family(conf: Dict[str, Any]):
    """The module `bench/families/<family>.py` that the configuration's
    `family` key names: its mapping to the program, its FLOPs, its
    layout and its plain blocks (bench/families/dense.py lists them)."""
    name = conf["family"]
    try:
        return _module("families", name)
    except FileNotFoundError:
        found = sorted(p.stem for p in (BENCH / "families").glob("*.py"))
        raise ValueError(f"configuration {conf.get('name')!r} names family "
                         f"{name!r}; bench/families has {found}") from None


def cell_metrics(bench: Dict[str, Any], cell: str, trace: bool) \
        -> List[Dict[str, Any]]:
    """The metrics the cell reports: end-to-end ones untraced, per-layer
    ones traced; a metric with a `workloads` list only in those cells."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell in m["workloads"]]


def load_peaks(kind: str) -> Dict[str, float]:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (has {sorted(table)})")
    return table[kind]


# ------------------------------------------------------- configuration

def optimizer(opt: Dict[str, Any]):
    from repro.optim.optimizers import adamw
    if opt["name"] != "adamw":
        raise ValueError(f"optimizer {opt['name']!r} has no mapping here")
    return adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                 weight_decay=opt["weight_decay"],
                 clip_norm=opt["clip_norm"])


def build_session(wl, cfg, opt, loader, work: Path):
    from repro.configs.base import SpoolIoConfig
    from repro.models.transformer import RunSettings
    from repro.session import TrainSession
    policy = wl["activation_policy"]
    settings = RunSettings(attn_impl="xla", attn_chunk=wl["attn_chunk"],
                           activation_policy=policy,
                           param_dtype=cfg.dtype, ce_chunk=wl["ce_chunk"])
    io = None
    if wl.get("spool"):
        io = SpoolIoConfig(directory=str(work / "spool"), **wl["spool"])
    return TrainSession(cfg, engine="jit", io=io, optimizer=opt,
                        batch_size=wl["batch"], seq_len=wl["seq_len"],
                        settings=settings, loader=loader,
                        ckpt_dir=str(work / "ckpt"), ckpt_every=0,
                        spool_dir=str(work / "spool"),
                        min_offload_elements=wl.get("min_offload_elements"))


@contextlib.contextmanager
def no_final_checkpoint():
    """`TrainLoop.run` always ends with a checkpoint of the whole state
    (~9 GB for these cells). It falls outside set-up and window, and the
    benchmark keeps what a run writes to disk small, so it is skipped."""
    from repro.runtime import trainer
    saved = trainer.save_train_state
    trainer.save_train_state = lambda *a, **k: None
    try:
        yield
    finally:
        trainer.save_train_state = saved


# ------------------------------------------------------------- memory

_CALLBACK_ID = re.compile(r"(backend_config\s*=\s*)\"[^\"]*\"")


def compiled_memory(step_fn, args, cache_dir: Path) -> Dict[str, int]:
    """argument / output / alias / temp bytes of the compiled step, from
    `memory_analysis()`. Kept in a file keyed by a hash of the lowered
    module's text (callback descriptors left out, since they change from
    process to process), so a step that cannot be cached compiles twice
    only in a checkout's first run."""
    lowered = step_fn.lower(*args)
    text = _CALLBACK_ID.sub(r'\1""', lowered.as_text())
    key = hashlib.sha256(text.encode()).hexdigest()[:32]
    path = cache_dir / f"{key}.json"
    if path.is_file():
        with open(path) as f:
            return json.load(f)
    ma = lowered.compile().memory_analysis()
    out = {"argument": int(ma.argument_size_in_bytes),
           "output": int(ma.output_size_in_bytes),
           "alias": int(ma.alias_size_in_bytes),
           "temp": int(ma.temp_size_in_bytes)}
    cache_dir.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, path)
    return out


# --------------------------------------------------------------- record

@dataclass
class RunRecord:
    """What the metric readers read."""
    workload: Dict[str, Any]
    chips: int
    setup_s: float
    window_s: float
    window_steps: int
    window_tokens: int
    step_times: List[float]
    step_ends: List[float]                  # host clock, from window start
    flops_per_token: float
    peak: Dict[str, float]
    peak_bytes_in_use: int
    compiled: Dict[str, int]
    spool_bytes: Optional[int] = None       # window total, spool cells
    trace: Optional[Dict[str, Any]] = None
    traced_steps: int = 0
    # what the readers of the program's counters and spans read
    # (bench/runstate.py): the window's StepReports in order (None where
    # no harness run made the record), the program's repro.obs spans of
    # the traced steps, the host clock of the trace's start mark, and
    # the profiler's directory
    reports: Optional[List[Any]] = None
    spans: List[Any] = field(default_factory=list)
    mark_ns: Optional[int] = None
    prof_dir: Optional[str] = None
    # what one reader worked out for the others (bench/runstate.py)
    found: Dict[str, Any] = field(default_factory=dict)


# ----------------------------------------------------------------- run

def count_failed(reports, stats) -> int:
    """Window steps that failed: a non-finite loss, or a fetch that fell
    back to recompute. One more when the spool's record partition is
    broken at the end (every record stored or cancelled once, and
    loaded or forwarded once), since a broken spool hides there."""
    n = 0
    for rep in reports:
        bad = not math.isfinite(rep.loss)
        if rep.stats is not None and rep.stats.fetch_fallbacks:
            bad = True
        if bad:
            log(f"step {rep.step} failed: loss {rep.loss}, stats "
                f"{rep.stats}")
        n += int(bad)
    if stats is not None and (stats.num_stores + stats.stores_canceled
                              != stats.num_loads + stats.num_forwarded):
        log(f"record partition broken: {stats}")
        n += 1
    return n


@dataclass
class _Probe:
    """Readings taken at step boundaries inside `TrainSession.run`."""
    reports: List[Any] = field(default_factory=list)
    ends: List[float] = field(default_factory=list)
    grad_norms: Optional[Dict[str, float]] = None
    change_norms: Optional[Dict[str, float]] = None


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             t0: float) -> Dict[str, Any]:
    """One run of `cell`; returns the result line's object. `t0` is the
    host-clock time the process started its set-up."""
    import jax

    from bench import compare, weights
    from bench import trace as trace_mod
    from bench.loader import UniformTokens, WindowLoader
    from repro import obs
    from repro.runtime.trainer import TrainState

    bench = load_benchmark()
    wl = load_workload(cell)
    conf = load_config(wl["config"])
    fam = family(conf)
    cfg = fam.program_config(conf)
    opt_conf = wl["optimizer"]
    devices = jax.devices()[:wl["chips"]]
    dev = devices[0]
    peak = load_peaks(dev.device_kind)
    clock = time.perf_counter

    work = Path(tempfile.mkdtemp(prefix="bench_work_"))
    prof_dir = work / "profile"
    # repro.obs records the program's host spans only while the profiler
    # traces: its per-step analysis would otherwise load every step
    traced = {"steps": 0, "on": False, "mark": None, "tracer": None}

    def on_handout(i: int) -> None:
        if not trace:
            return
        if i == 0:
            traced["tracer"] = obs.enable()
            trace_mod.start(str(prof_dir))
            traced["on"] = True
            with jax.profiler.TraceAnnotation(trace_mod.START_MARK):
                traced["mark"] = time.perf_counter_ns()
        elif i == wl["trace_steps"] and traced["on"]:
            _stop_trace()

    def _stop_trace() -> None:
        with jax.profiler.TraceAnnotation(trace_mod.END_MARK):
            pass
        jax.profiler.stop_trace()
        obs.disable()
        traced["on"] = False
        traced["steps"] = probe_window_steps()

    tokens = UniformTokens(conf["vocab_size"], seed)
    loader = WindowLoader(tokens, batch=wl["batch"], seq_len=wl["seq_len"],
                          setup_steps=SETUP_STEPS, seconds=seconds,
                          clock=clock, on_handout=on_handout)
    probe = _Probe()

    def probe_window_steps() -> int:
        return max(len(probe.reports) - SETUP_STEPS, 0)

    rules = conf["init"]
    b1 = opt_conf["b1"]
    sess = None
    try:
        sess = build_session(wl, cfg, optimizer(opt_conf), loader, work)
        shapes = fam.param_shapes(conf)
        prog_shapes = jax.eval_shape(sess.api.init, jax.random.key(0))
        if jax.tree.structure(shapes) != jax.tree.structure(prog_shapes) \
                or jax.tree.leaves(shapes) != jax.tree.leaves(prog_shapes):
            raise ValueError(f"the program's weights {prog_shapes} are "
                             f"not the configuration's {shapes}")
        params = weights.make_params(seed, shapes, rules)
        sess._state = TrainState(0, params, jax.jit(sess.optimizer.init)(
            params))
        del params
        log(f"weights made at t={clock() - t0:.1f}s")

        def on_report(rep) -> None:
            probe.reports.append(rep)
            probe.ends.append(clock())
            n = len(probe.reports)
            if n == 1:
                mu = sess._loop.state.opt_state.mu
                probe.grad_norms = compare.leaf_norms(mu, 1.0 / (1.0 - b1))
            if n == SETUP_STEPS:
                probe.change_norms = compare.change_norms(
                    sess._loop.state.params, seed, rules)
            if n <= SETUP_STEPS:
                log(f"set-up step {n}: loss {rep.loss:.6f} step "
                    f"{rep.step_time:.3f}s t={clock() - t0:.1f}s")

        failed = 0
        with no_final_checkpoint():
            try:
                sess.run(10 ** 9, on_report=on_report)
            except Exception as e:      # a step that raised counts
                log(f"run raised {type(e).__name__}: {e}")
                failed += 1
        if traced["on"]:
            _stop_trace()
        if len(probe.reports) < SETUP_STEPS or loader.window_start is None:
            raise RuntimeError(f"set-up did not finish: "
                               f"{len(probe.reports)} steps")
        setup_s = loader.window_start - t0
        win = probe.reports[SETUP_STEPS:]
        window_s = (probe.ends[-1] - loader.window_start) if win else 0.0
        attempted = len(win) + failed
        spool_bytes = None
        stats = None
        if sess.spool is not None:
            sess.spool.wait_io()
            stats = sess.spool.stats
            spool_bytes = sum(r.stats.bytes_offloaded for r in win
                              if r.stats is not None)
            log(f"spool: {stats}")
        failed += count_failed(win, stats)
        mem = dev.memory_stats() or {}
        peak_in_use = int(mem.get("peak_bytes_in_use", 0))

        state = sess._loop.state
        sds = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        compiled = compiled_memory(
            sess._step_fn, (sds(state.params), sds(state.opt_state),
                            tokens.batch(0, wl["batch"], wl["seq_len"])),
            ROOT / ".bench_cache" / "memory")
        trace_red = None
        spans = traced["tracer"].snapshot() if traced["tracer"] else []
        if trace:
            trace_red = _reduce_trace(prof_dir, traced["mark"], spans)
        prog = {"losses": [r.loss for r in probe.reports[:SETUP_STEPS]],
                "grad_leaf_norms": probe.grad_norms,
                "change_leaf_norms": probe.change_norms}
        # every label of the benchmark's batches is a real target
        tokens_in_window = len(win) * wl["batch"] * wl["seq_len"]
        rec = RunRecord(
            workload=wl, chips=wl["chips"], setup_s=setup_s,
            window_s=window_s, window_steps=len(win),
            window_tokens=tokens_in_window,
            step_times=[r.step_time for r in win],
            step_ends=[t - loader.window_start
                       for t in probe.ends[SETUP_STEPS:]],
            flops_per_token=fam.flops_per_token(conf, wl["seq_len"]),
            peak=peak, peak_bytes_in_use=peak_in_use, compiled=compiled,
            spool_bytes=spool_bytes, trace=trace_red,
            traced_steps=traced["steps"], reports=win, spans=spans,
            mark_ns=traced["mark"], prof_dir=str(prof_dir))
        log(f"window: {len(win)} steps in {window_s:.3f}s, setup "
            f"{setup_s:.1f}s, peak_bytes_in_use {peak_in_use}, compiled "
            f"{compiled}")
        log(f"window step times: {[round(t, 3) for t in rec.step_times]}")
        metrics = {}
        for m in cell_metrics(bench, cell, trace):
            v = metric_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

        # free the program's state before the reference runs
        del state, win, rec
        probe.reports = []
        sess.close()
        sess = None
        gc.collect()
        log(f"program freed: bytes_in_use "
            f"{(dev.memory_stats() or {}).get('bytes_in_use')}")
        t_ref = clock()
        ref = reference_readings(conf, wl, seed, tokens, quant=None)
        log(f"reference took {clock() - t_ref:.1f}s")
        nums = compare.numbers(prog, ref)
        correct, shown = compare.judge(nums, wl["limits"])
        log(f"worst leaves: grad {nums['grad_worst_leaf']}, change "
            f"{nums['change_worst_leaf']}; left out of change_gap: "
            f"{nums['leaves_left_out']}")
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak_in_use}
        result = {"correct": bool(correct), "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if trace_red is not None:
            device["busy_s"] = trace_red["busy_s"]
            device["window_s"] = trace_red["window_s"]
            result["breakdown"] = {"device_ops": trace_red["device_ops"],
                                   "idle_gaps": trace_red["idle_gaps"]}
        result["compared"] = shown
        return result
    finally:
        if sess is not None:
            sess.close()
        if traced["tracer"] is not None:
            obs.disable()
        shutil.rmtree(work, ignore_errors=True)


def _reduce_trace(prof_dir: Path, mark_ns: Optional[int], events) \
        -> Optional[Dict[str, Any]]:
    """Reduce the profiler trace; the program's spans are moved onto the
    trace's clock by the start mark, taken on both clocks."""
    from bench import trace as trace_mod
    path = trace_mod.find_xplane(str(prof_dir))
    if path is None:
        return None
    pd = trace_mod.load(path)
    win = trace_mod.marks(pd)
    extra = []
    if win is not None and mark_ns is not None:
        shift = win[0] - mark_ns
        extra = [(e[0], e[2] + shift, e[2] + e[3] + shift)
                 for e in events if e[3] > 0]
    return trace_mod.reduce(pd, extra)


def reference_readings(conf, wl, seed: int, tokens, *,
                       quant: Optional[str] = None,
                       batch_fn: Optional[Callable] = None,
                       residual_from=None) -> Dict[str, Any]:
    """The plain reference's readings over the set-up steps, from the
    seed's weights and batches. `quant`, `batch_fn` (which alters each
    batch) and `residual_from` plant the control and the faults that
    the comparison must catch (bench/control.py)."""
    import jax

    from bench import compare, weights
    from bench.loader import setup_batches
    from bench.reference.model import Reference, train_three

    fam = family(conf)
    params = weights.make_params(seed, fam.param_shapes(conf), conf["init"])
    batches = setup_batches(tokens, SETUP_STEPS, wl["batch"],
                            wl["seq_len"])
    if batch_fn is not None:
        batches = [batch_fn(b) for b in batches]
    ref = Reference(conf, fam.blocks(conf), quant=quant,
                    residual_from=residual_from)
    out = train_three(ref, params, batches, wl["optimizer"],
                      compare.leaf_norms)
    change = compare.change_norms(out.pop("params"), seed, conf["init"])
    return {"losses": out["losses"],
            "grad_leaf_norms": out["grad_leaf_norms"],
            "change_leaf_norms": change, "grad_norm": out["grad_norm"]}
