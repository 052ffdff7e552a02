"""A new configuration, traffic mix and per-layer metric are new files and
entries: the harness finds them by name and no existing file changes."""
import hashlib
import json
import os

import tiny
from spec import BENCH, load_cell


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files(tmp_path):
    root, bench = tiny.make_root(str(tmp_path))
    before = _digest(bench)
    with open(os.path.join(bench, "metrics", "rounds_in_window.py"), "w") as f:
        f.write("def read(run):\n    return len(run['window']['round_s'])\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["per_layer"].append({
        "name": "rounds_in_window", "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "FL server",
        "moves": "zo_tokens_per_s", "workloads": [tiny.CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = load_cell(tiny.CELL, root, bench)
    assert cell.config["program"]["n_layers"] == 2
    assert cell.traffic["local_steps"] == 2
    assert cell.family.__name__ == "bench_family_dense"
    names = [m.name for m in cell.per_layer]
    assert "rounds_in_window" in names
    m = cell.per_layer[names.index("rounds_in_window")]
    assert m.read({"window": {"round_s": [0.1, 0.2]}}) == 2
    assert {k for k in cell.limits} == {
        "mask_count_gap", "mask_gap", "g_norm_gap", "update_norm_gap",
        "change_norm_gap"}
    # every file the benchmark had is unchanged; only new ones were added
    after = _digest(bench)
    assert all(after[k] == v for k, v in before.items())


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer and cell.limits
        e2e = {m.name for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2


def test_seeds_are_31_bit_and_differ():
    from spec import derive_seeds
    for seed in (0, 1, 2**31 + 5, 2**40, -3):
        s = derive_seeds(seed)
        assert all(0 <= v < 2**31 for v in s.values())
        assert len(set(s.values())) == len(s)
    assert derive_seeds(2**31 + 5) == derive_seeds(2**31 + 5)
    assert derive_seeds(2**31 + 5) != derive_seeds(2**31 + 6)
