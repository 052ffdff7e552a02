"""Rehearse ``chip_smoke.py``'s control flow on the CPU at small sizes.

The script itself runs only on a TPU; these tests call its train, serve
and kernel phase functions on CPU-sized architectures (kernels in
interpret mode), with the fewest clients, rounds and requests that still
reach every branch: a flagged VP client in its own T=1 group
(qwen2-1.5b-reduced), a compiling first round and a steady second one, a
cached second wave of requests, and both decode routes compared.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

ARCHS = ["tiny", "qwen2-1.5b-reduced"]


@pytest.mark.parametrize("arch", ARCHS)
def test_train_phase(arch):
    rep = chip_smoke.train_phase(arch, clients=2, rounds=2, T=2, batch=4)
    assert rep["dtype"] == ["float32"]
    assert len(rep["losses"]) == 2
    assert len(rep["smoke_round_s"]["steady"]) == 1
    # every group on the one ZO route
    assert set(rep["zo_routes"].values()) == {"ref"}
    assert "T=100 x2" in rep["zo_routes"]  # the VP calibration group
    assert rep["mask_changed_share"] > 0.5
    if arch == "qwen2-1.5b-reduced":
        # VP flags a client, which then runs in a T=1 group of its own
        assert rep["vp_flagged"] and "T=1 x1" in rep["zo_routes"]


@pytest.mark.parametrize("arch", ARCHS)
def test_serve_phase(arch):
    rep = chip_smoke.serve_phase(arch, requests=2, max_prompt=12, s_max=32)
    assert rep["decode_route"] == "pallas" and rep["ref_decode_route"] == "ref"
    assert rep["requests"] == 2 and len(rep["tokens"]) == 2
    # interpret mode computes in f32 on both routes
    assert rep["logit_rel_err"] < 1e-5


def test_kernel_phase():
    rep = chip_smoke.kernel_phase("qwen2-1.5b-reduced", B=4, S=128)
    assert rep["block_place_exact"]
    assert max(v for v in rep.values() if isinstance(v, float)) <= rep["rtol"]


def test_device_phase_refuses_the_cpu():
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.device_phase(1)


def test_script_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
