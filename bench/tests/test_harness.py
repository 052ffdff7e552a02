"""The rest of a run on the CPU, at the test size, with the chip check
skipped: the reference agrees with the program, and each fault planted
under the timed path, and the control, make ``correct`` come out false."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import run
import tiny


def _run(tmp_path, capsys, seed=7, dtype="float32"):
    root, bench = tiny.make_root(str(tmp_path / "root"), dtype)
    rc = run.run(["--workload", tiny.CELL, "--seed", str(seed),
                  "--seconds", "0.2", "--trace", "0"], root=root,
                 bench=bench, require_tpu=False,
                 cache_dir=str(tmp_path / "cache"))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.fixture
def root_dir(tmp_path):
    (tmp_path / "root").mkdir()
    return tmp_path


def test_sound_run_is_correct(root_dir, capsys):
    res = _run(root_dir, capsys, seed=2**31 + 99)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert res["check"]["g_norm_gap"]["value"] < 1e-3
    assert {"zo_tokens_per_s", "setup_s"} <= set(res["metrics"])


def _unchanged(monkeypatch):
    from repro.core.server import FederatedZO
    orig = FederatedZO.run_round

    def run_round(self, *a, **k):
        keep = self.params
        out = orig(self, *a, **k)
        self.params = keep  # the round returns its state unchanged
        return out
    monkeypatch.setattr(FederatedZO, "run_round", run_round)


def _half_batch(monkeypatch):
    import repro.data.synthetic as syn
    orig = syn.make_task_fns

    def make_task_fns(model, spec):
        loss, per_example, ev = orig(model, spec)

        def half(params, batch):
            n = batch["tokens"].shape[0] // 2
            return loss(params, {k: v[:n] for k, v in batch.items()})
        return half, per_example, ev
    monkeypatch.setattr(syn, "make_task_fns", make_task_fns)


def _half_clients(monkeypatch):
    from repro.core import virtual_path as VP
    orig = VP.aggregate

    def aggregate(deltas, n_reporting=None):
        m = max(1, deltas.shape[0] // 2)  # the others' uploads left out
        return orig(deltas[:m], m)
    monkeypatch.setattr(VP, "aggregate", aggregate)


def _altered(monkeypatch):
    from repro.core import zo as ZO
    orig = ZO.make_local_run

    def make_local_run(*a, **k):
        inner = orig(*a, **k)

        def run(params, keys, batches, delta0):
            delta, gs = inner(params, keys, batches, delta0)
            return delta, gs.at[0].add(1.0 + jnp.abs(gs[0]))
        return run
    monkeypatch.setattr(ZO, "make_local_run", make_local_run)


def _magnitude_mask(monkeypatch):
    import repro.core as core
    from repro.core.masks import magnitude_mask

    def sensitivity_mask(loss_fn, params, batches, density):
        return magnitude_mask(params, density)  # the wrong coordinates
    monkeypatch.setattr(core, "sensitivity_mask", sensitivity_mask)


def _double_density(monkeypatch):
    import repro.core as core
    orig = core.sensitivity_mask

    def sensitivity_mask(loss_fn, params, batches, density):
        return orig(loss_fn, params, batches, 2 * density)
    monkeypatch.setattr(core, "sensitivity_mask", sensitivity_mask)


@pytest.mark.parametrize("fault,number", [
    (_unchanged, "update_norm_gap"), (_half_batch, "g_norm_gap"),
    (_half_clients, "update_norm_gap"), (_altered, "g_norm_gap"),
    (_magnitude_mask, "mask_gap"), (_double_density, "mask_count_gap")])
def test_fault_makes_run_incorrect(root_dir, capsys, monkeypatch, fault,
                                   number):
    fault(monkeypatch)
    res = _run(root_dir, capsys)
    assert not res["correct"]
    c = res["check"][number]
    assert c["value"] is None or c["value"] > c["limit"], res["check"]


def test_control_is_not_correct(root_dir, capsys):
    """The reference in float8 in the program's place fails ``g_norm_gap``."""
    root, bench = tiny.make_root(str(root_dir / "root"))
    rc = calibrate.main(["--workload", tiny.CELL, "--seeds", "5",
                         "--variants", "1"], root=root, bench=bench,
                        require_tpu=False, cache_dir=str(root_dir / "cache"))
    assert rc == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    by = {d["who"]: d for d in lines}
    limit = tiny.LIMITS["numbers"]["g_norm_gap"]["limit"]
    assert by["program"]["g_norm_gap"] <= limit
    assert by["control"]["g_norm_gap"] > limit
    assert by["half_batch"]["g_norm_gap"] > limit
    assert by["program"]["mask_gap"] <= \
        tiny.LIMITS["numbers"]["mask_gap"]["limit"]
    assert by["control"]["mask_gap"] > \
        tiny.LIMITS["numbers"]["mask_gap"]["limit"]
    assert by["half_clients"]["update_norm_gap"] > \
        tiny.LIMITS["numbers"]["update_norm_gap"]["limit"]
    assert np.isfinite(by["control"]["g"]).all()


def test_refuses_a_program_dtype_other_than_the_stated_one(root_dir,
                                                          capsys):
    """The stored dtype is the configuration file's, not the program's
    registry entry: a program that would run another refuses to start."""
    root, bench = tiny.make_root(str(root_dir / "root"), "float32")
    path = f"{bench}/configs/tiny-qwen2.json"
    with open(path) as f:
        conf = json.load(f)
    conf["program"]["dtype"] = "bfloat16"
    with open(path, "w") as f:
        json.dump(conf, f)
    with pytest.raises(SystemExit, match="dtype"):
        run.run(["--workload", tiny.CELL, "--seed", "1", "--seconds", "0.2"],
                root=root, bench=bench, require_tpu=False,
                cache_dir=str(root_dir / "cache"))
    assert capsys.readouterr().out == ""


def test_refuses_to_run_off_a_tpu(root_dir, capsys):
    root, bench = tiny.make_root(str(root_dir / "root"))
    rc = run.run(["--workload", tiny.CELL, "--seed", "1", "--seconds", "1"],
                 root=root, bench=bench, cache_dir=str(root_dir / "cache"))
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_reference_imports_nothing_of_the_program():
    import os
    for f in ("reference.py", os.path.join("families", "dense.py"),
              "generate.py", "tracefile.py"):
        with open(os.path.join(run.BENCH, f)) as fh:
            assert "repro" not in fh.read(), f
