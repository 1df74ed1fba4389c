#!/usr/bin/env python3
"""Readings that set a training cell's limits, on the chip at the cell's
own size (not part of a benchmark run).

    python bench/control.py --workload <cell> --seeds 1 2 3

For each seed the plain float32 reference is compared, by the cell's own
comparison (bench/compare.py), with what stands in the program's place:

  control     the reference with every matmul from float8_e4m3 operands,
              the precision below the configuration's bfloat16
  half_batch  the reference with the second half of each batch's tokens
              left out of the loss (the mean taken over the rest)
  residual    spool cells: layer 1's backward gets layer 0's residuals,
              the answer the spool produces altered where it produces it
  token       one input token of each batch altered: read and reported,
              though no step-level number can see one token in a
              thousand (PERF.md)

A step that returns its state unchanged reads change_gap 1 by the
measure itself and needs no run. Prints one JSON line per seed and
reading; a chip is required, as for the benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def half_batch(b):
    labels = b["labels"].copy()
    labels[:, labels.shape[1] // 2:] = -1
    return dict(b, labels=labels)


def alter_token(b):
    tokens = b["tokens"].copy()
    s = tokens.shape[1] // 2
    tokens[0, s] = (tokens[0, s] + 1) % 1000
    return dict(b, tokens=tokens)


PLANTED = {"control": dict(quant="fp8"),
           "half_batch": dict(batch_fn=half_batch),
           "residual": dict(residual_from=(1, 0)),
           "token": dict(batch_fn=alter_token)}


def planted_for(wl) -> tuple:
    """The readings that apply to a cell: the residual fault needs the
    spool."""
    return tuple(k for k in PLANTED
                 if k != "residual" or wl["activation_policy"] == "spool")


def readings(cell: str, seed: int, which=None):
    from bench import compare, harness
    from bench.loader import UniformTokens
    wl = harness.load_workload(cell)
    which = planted_for(wl) if which is None else which
    conf = harness.load_config(wl["config"])
    tokens = UniformTokens(conf["vocab_size"], seed)
    ref = harness.reference_readings(conf, wl, seed, tokens)
    out = {}
    for name in which:
        got = harness.reference_readings(conf, wl, seed, tokens,
                                         **PLANTED[name])
        out[name] = compare.numbers(got, ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 3
    from bench.run import compile_cache_dir
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    for seed in args.seeds:
        t = time.perf_counter()
        for name, nums in readings(args.workload, seed).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "planted": name,
                              **{k: nums[k] for k in
                                 ("loss_gap", "grad_gap", "change_gap",
                                  "grad_worst_leaf",
                                  "change_worst_leaf")}}), flush=True)
        print(f"control: seed {seed} took {time.perf_counter() - t:.1f}s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
