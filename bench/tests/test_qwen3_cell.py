"""A CPU-sized Qwen3 cell through ``run.py``'s normal path, against
``families/dense.py``'s reference, in float32: two layers, 4 query heads of
32 over 2 KV heads (the query, 128 wide, is wider than d_model 64), qk_norm,
no q/k/v bias, a tied head.  A sound run is correct; each planted fault,
the float8 control and a program that leaves q_norm/k_norm out of its
forward are refused."""
import json
import os

import pytest

import calibrate
import run
import tiny
from test_harness import (_altered, _double_density, _half_batch,
                          _half_clients, _magnitude_mask)

CONFIG = {
    "source": "test-sized Qwen3 block",
    "family": "dense",
    "arch": "qwen3-4b",
    "program": {"name": "tiny-qwen3", "n_layers": 2, "d_model": 64,
                "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 128,
                "vocab": 256, "dtype": "float32"},
    "dtype": "float32",
    "config": {"model_type": "qwen3", "hidden_size": 64,
               "intermediate_size": 128, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "vocab_size": 256, "rms_norm_eps": 1e-06,
               "rope_theta": 1000000, "attention_bias": False,
               "tie_word_embeddings": True},
    "reduced": {},
}
CELL = "tiny-qwen3.zo-t2-s16"


def make_root(tmp: str) -> tuple:
    """(root, bench) of ``tiny.make_root``'s checkout plus this cell, under
    the tiny cell's traffic and limits."""
    root, bench = tiny.make_root(tmp)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-qwen3", "source": "test",
                            "file": "bench/configs/tiny-qwen3.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-qwen3",
                              "traffic": "zo-t2-s16", "chips": 1,
                              "why": "test"})
    files = {"BENCHMARK.json": spec,
             "bench/configs/tiny-qwen3.json": CONFIG,
             f"bench/limits/{CELL}.json": tiny.LIMITS}
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    return root, bench


def _run(tmp_path, capsys, seed=11):
    root, bench = make_root(str(tmp_path / "root"))
    rc = run.run(["--workload", CELL, "--seed", str(seed), "--seconds",
                  "0.2", "--trace", "0"], root=root, bench=bench,
                 require_tpu=False, cache_dir=str(tmp_path / "cache"))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.fixture
def root_dir(tmp_path):
    (tmp_path / "root").mkdir()
    return tmp_path


def test_sound_qwen3_run_is_correct(root_dir, capsys):
    res = _run(root_dir, capsys, seed=2**31 + 7)
    assert res["correct"], res["check"]
    assert res["check"]["g_norm_gap"]["value"] < 1e-3


def _no_qk_norm(monkeypatch):
    from repro.models import layers
    orig = layers._project_qkv

    def project(x, p, cfg):
        return orig(x, p, cfg.replace(qk_norm=False))  # q, k left unnormed
    monkeypatch.setattr(layers, "_project_qkv", project)


@pytest.mark.parametrize("fault,number", [
    (_half_batch, "g_norm_gap"), (_half_clients, "update_norm_gap"),
    (_altered, "g_norm_gap"), (_magnitude_mask, "mask_gap"),
    (_double_density, "mask_count_gap"), (_no_qk_norm, "g_norm_gap")])
def test_qwen3_fault_makes_run_incorrect(root_dir, capsys, monkeypatch,
                                         fault, number):
    fault(monkeypatch)
    res = _run(root_dir, capsys)
    assert not res["correct"]
    c = res["check"][number]
    assert c["value"] is None or c["value"] > c["limit"], res["check"]


def test_qwen3_control_is_not_correct(root_dir, capsys):
    """The reference in float8 in the program's place fails a limit."""
    root, bench = make_root(str(root_dir / "root"))
    rc = calibrate.main(["--workload", CELL, "--seeds", "5", "--variants",
                         "1"], root=root, bench=bench, require_tpu=False,
                        cache_dir=str(root_dir / "cache"))
    assert rc == 0
    by = {d["who"]: d for d in map(json.loads,
                                   capsys.readouterr().out.splitlines())}
    lim = {k: v["limit"] for k, v in tiny.LIMITS["numbers"].items()}
    assert by["program"]["g_norm_gap"] <= lim["g_norm_gap"]
    assert by["program"]["mask_gap"] <= lim["mask_gap"]
    assert (by["control"]["g_norm_gap"] > lim["g_norm_gap"]
            or by["control"]["mask_gap"] > lim["mask_gap"])
