#!/usr/bin/env python3
"""Run one cell as bench/run.py does, with the per-layer metrics of
bench/pending.json added to those BENCHMARK.json lists.

    python bench/pending.py --workload <cell> --seed <n> --seconds <s> \\
        --trace 1

A metric waits in bench/pending.json while a cell it reads does not
report the end-to-end metric it moves: the spool cell's host-callback
metrics move `tokens_per_s`, which that cell does not report yet
(PERF.md, Open questions). `per_layer` holds entries in BENCHMARK.json's
form; `workloads_added` names cells to add to a listed metric.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import harness, run  # noqa: E402

_listed = harness.load_benchmark


def load_benchmark():
    bench = _listed()
    with open(harness.BENCH / "pending.json") as f:
        pending = json.load(f)
    added = pending["workloads_added"]
    for m in bench["per_layer"]:
        if m["name"] in added:
            m["workloads"] += added[m["name"]]
    bench["per_layer"] += pending["per_layer"]
    return bench


def main() -> int:
    harness.load_benchmark = load_benchmark
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
