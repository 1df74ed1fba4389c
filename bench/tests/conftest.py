"""Fixtures for the benchmark's own tests (run them explicitly:
`python -m pytest bench/tests`). A tiny cell tree in a temporary
directory stands in for bench/, so the harness runs end to end on the
CPU at toy sizes. Besides the real families it holds the test families
of bench/tests/data/families/, which join it as files only."""
from __future__ import annotations

import copy
import importlib.util
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY_CONFIG = {
    "name": "tiny", "source": "test", "family": "dense",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 500, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "attention_bias": True, "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}


#: the keys of each test family (bench/tests/data/families/) beyond a
#: dense decoder's: local and global layers alternating, one segment of
#: two blocks; one dense layer, then expert layers, two segments
FAMILY_KEYS = {
    "dense_window": dict(family="dense_window", num_hidden_layers=4,
                         sliding_window=8, local_global_period=2),
    "moe": dict(family="moe", num_hidden_layers=3, first_k_dense_replace=1,
                moe_intermediate_size=32, n_routed_experts=8,
                num_experts_per_tok=2, n_shared_experts=1),
}
TEST_FAMILIES = Path(__file__).resolve().parent / "data" / "families"


def tiny_config(**kw):
    conf = dict(TINY_CONFIG, **kw)
    src = json.loads((ROOT / "bench" / "configs" /
                      "qwen2.5-3b-4l.json").read_text())
    conf["init"] = copy.deepcopy(src["init"])
    conf["init"]["router"] = {"fan_in": [-2]}
    return conf


def legacy():
    """The dense-only mapping, FLOPs and reference, as they were before
    the families (bench/tests/data/legacy_dense.py)."""
    path = Path(__file__).resolve().parent / "data" / "legacy_dense.py"
    spec = importlib.util.spec_from_file_location("legacy_dense", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_workload(policy: str, config: str = "tiny", **kw):
    wl = json.loads((ROOT / "bench" / "workloads" /
                     "qwen2.5-3b-4l.spool-1x1024.json").read_text())
    wl.update(config=config, activation_policy=policy, seq_len=64,
              min_offload_elements=256,
              attn_chunk=16, ce_chunk=16, trace_steps=2)
    if policy != "spool":
        wl["spool"] = None
    # tiny-size limits, set from tiny CPU readings: sound runs read
    # loss_gap ~3e-4, grad_gap ~5e-3, change_gap ~1.7e-2; the float8
    # control read loss_gap >= 2.1e-3 and grad_gap >= 3.2e-2, half the
    # batch change_gap >= 0.26, a state left unchanged change_gap 1
    wl["limits"] = {"loss_gap": 1e-3, "grad_gap": 1.5e-2,
                    "change_gap": 0.1}
    wl.update(kw)
    return wl


@pytest.fixture
def tiny_bench(tmp_path, monkeypatch):
    """A bench tree with tiny cells `tiny.spool` and `tiny.remat` (and
    `tiny4.remat`, 4:1 heads without qkv bias; `tiny_window.remat` and
    `tiny_moe.remat` of the test families), the real metric readers,
    families and peaks, and a BENCHMARK.json naming them."""
    b = tmp_path / "bench"
    (b / "configs").mkdir(parents=True)
    (b / "workloads").mkdir()
    shutil.copytree(harness.BENCH / "metrics", b / "metrics")
    shutil.copytree(harness.BENCH / "families", b / "families")
    for f in TEST_FAMILIES.glob("*.py"):
        shutil.copy(f, b / "families" / f.name)
    peaks = json.loads((harness.BENCH / "peaks.json").read_text())
    # a stand-in peak, so the CPU rehearsal runs every reader
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11}
    (b / "peaks.json").write_text(json.dumps(peaks))
    confs = {"tiny": tiny_config(),
             "tiny4": tiny_config(name="tiny4", num_attention_heads=8,
                                  num_key_value_heads=2, head_dim=8,
                                  attention_bias=False),
             "tiny_window": tiny_config(name="tiny_window",
                                        **FAMILY_KEYS["dense_window"]),
             # in float32: in bfloat16 a few tokens' top-2 experts
             # differ from the float32 reference's, and the router's
             # gradient gap swings from seed to seed (PERF.md)
             "tiny_moe": tiny_config(name="tiny_moe", torch_dtype="float32",
                                     **FAMILY_KEYS["moe"])}
    for name, c in confs.items():
        (b / "configs" / f"{name}.json").write_text(json.dumps(c))
    cells = {"tiny.spool": tiny_workload("spool"),
             "tiny.remat": tiny_workload("remat"),
             "tiny4.remat": tiny_workload("remat", config="tiny4"),
             "tiny_window.remat": tiny_workload("remat",
                                                config="tiny_window"),
             "tiny_moe.remat": tiny_workload("remat", config="tiny_moe")}
    for name, w in cells.items():
        (b / "workloads" / f"{name}.json").write_text(json.dumps(w))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [
        {"name": n, "config": w["config"], "traffic": n.split(".")[1],
         "chips": 1, "why": "test"} for n, w in cells.items()]
    # a metric kept to some cells keeps to the tiny cells of that kind
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            spool = any(".spool-" in c for c in m["workloads"])
            m["workloads"] = (["tiny.spool"] if spool else
                              [n for n in cells if n.endswith(".remat")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "BENCH", b)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    return tmp_path
