"""A CPU-sized cell for the tests: a two-layer Qwen2-shaped model under a
two-step mix, written into a temporary checkout beside the real files."""
from __future__ import annotations

import json
import os
import shutil

from spec import BENCH, ROOT

CONFIG = {
    "source": "test-sized Qwen2 block",
    "family": "dense",
    "arch": "qwen2-1.5b",
    "program": {"name": "tiny-qwen2", "n_layers": 2, "d_model": 64,
                "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                "vocab": 256},
    "dtype": "bfloat16",
    "config": {"model_type": "qwen2", "hidden_size": 64,
               "intermediate_size": 128, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "vocab_size": 256, "rms_norm_eps": 1e-06,
               "rope_theta": 1000000.0, "tie_word_embeddings": True},
    "reduced": {},
}

TRAFFIC = {
    "clients": 4, "local_steps": 2, "batch_size": 4, "seq_len": 16,
    "examples": 64,
    "partition": {"scheme": "mixed", "dirichlet_alpha": 5.0,
                  "dirichlet_share": 0.75},
    "task": {"vocab": None, "n_classes": 4, "topic_tokens": 8, "noise": 0.25},
    "pretrain": {"batches": 2, "tokens_per_batch": 64},
    "density": 0.05, "lr": 0.05, "eps": 0.001, "zo_backend": "ref",
    "uplink": "none", "check": {"rounds": 3, "g_steps": 3},
    "trace": {"seconds": 0.5, "min_rounds": 2},
}

# In float32 the program and the reference differ by rounding alone
# (g within 1e-4, norms within 1e-7, the mask's top coordinates alike at
# this size), so a planted fault stands far above these limits.
LIMITS = {"numbers": {"mask_count_gap": {"limit": 0.0},
                      "mask_gap": {"limit": 0.02},
                      "g_norm_gap": {"limit": 1e-2},
                      "update_norm_gap": {"limit": 1e-5},
                      "change_norm_gap": {"limit": 1e-5}}}

CELL = "tiny-qwen2.zo-t2-s16"


def make_root(tmp: str, dtype: str = "float32") -> tuple:
    """(root, bench) of a checkout holding the real BENCHMARK.json and
    ``bench/`` plus the tiny cell's files and entries, its weights in
    ``dtype``."""
    bench = os.path.join(tmp, "bench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-qwen2", "source": "test",
                            "file": "bench/configs/tiny-qwen2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-qwen2",
                              "traffic": "zo-t2-s16", "chips": 1,
                              "why": "test"})
    files = {"BENCHMARK.json": spec,
             "bench/configs/tiny-qwen2.json": dict(
                 CONFIG, dtype=dtype,
                 program=dict(CONFIG["program"], dtype=dtype)),
             "bench/traffic/zo-t2-s16.json": TRAFFIC,
             f"bench/limits/{CELL}.json": LIMITS}
    for rel, obj in files.items():
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(obj, f)
    return tmp, bench
