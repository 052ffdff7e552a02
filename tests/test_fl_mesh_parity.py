"""Sharded-vs-single-device parity of the federated ZO round (ISSUE 5).

The mesh needs forced host devices *before* jax initializes, so the heavy
check runs ``tools/fl_mesh_parity.py`` in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` and asserts on its
JSON report: round-aggregated params and GradIP trajectories bit-match
across 1x1 and 2x2 meshes, VPCS flags and CommLog byte accounting are
identical, and the ``make_fl_train_loop`` mesh route agrees to tolerance.

The in-process tests cover the pieces that don't need devices: the GradIP
reduction dispatch (pallas kernel vs jnp dot) and the mesh-spec parsing.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import random_mask
from repro.core.gradip import _resolve_gradip_backend, gradip_trajectory
from repro.core.seeds import round_keys

REPO = os.path.join(os.path.dirname(__file__), "..")
TOOL = os.path.join(REPO, "tools", "fl_mesh_parity.py")


@pytest.fixture(scope="module")
def parity_report(tmp_path_factory):
    out = tmp_path_factory.mktemp("parity") / "report.json"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, TOOL, "--meshes", "1x1,2x2", "--json", str(out)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    with open(out) as f:
        return json.load(f)


def test_round_aggregate_bitmatch_across_meshes(parity_report):
    for spec in ("1x1", "2x2"):
        assert parity_report["meshes"][spec]["params_bitmatch"], spec


def test_gradip_trajectories_bitmatch_across_meshes(parity_report):
    for spec in ("1x1", "2x2"):
        assert parity_report["meshes"][spec]["gradip_bitmatch"], spec


def test_vpcs_flags_equal_across_meshes(parity_report):
    for spec in ("1x1", "2x2"):
        assert parity_report["meshes"][spec]["vpcs_flags_equal"], spec


def test_updated_leaves_keep_their_shardings(parity_report):
    """The server's aggregate-and-update program returns every parameter
    leaf with the sharding the plan placed it with."""
    for spec in ("1x1", "2x2"):
        assert parity_report["meshes"][spec]["shardings_kept"], spec


def test_comm_bytes_accounting_invariant_under_sharding(parity_report):
    """The FL protocol traffic (scalar uploads, seed/scalar downlinks) is a
    property of the algorithm, not of the round's mesh layout."""
    for spec in ("1x1", "2x2"):
        assert parity_report["meshes"][spec]["comm_bytes_equal"], spec


def test_hf_train_loop_mesh_route(parity_report):
    """make_fl_train_loop under constrain_params + mesh ShardCtx (the
    resolve_attn_backend sharded path) agrees with the unsharded loop."""
    for spec in ("1x1", "2x2"):
        assert parity_report["meshes"][spec]["hf_loop_allclose"], spec


# -- in-process pieces -------------------------------------------------------

def _toy_space(n=3000, seed=0):
    key = jax.random.key(seed)
    params = {"w": jax.random.normal(key, (64, 128), jnp.float32),
              "b": jnp.zeros((128,), jnp.float32)}
    return params, random_mask(params, density=0.3, seed=seed,
                               balanced=False)


def test_gradip_backend_parity():
    """Pallas blocked reduction vs jnp dot: same trajectories (float tol —
    different summation orders), same shapes."""
    _, space = _toy_space()
    T = 7
    keys = round_keys(0, 0, T)
    gs = jnp.linspace(-1.0, 1.0, T, dtype=jnp.float32)
    gp = jax.random.normal(jax.random.key(9), (space.n,), jnp.float32)
    ip_p, n_p, c_p = gradip_trajectory(space, keys, gs, gp,
                                       backend="pallas")
    ip_r, n_r, c_r = gradip_trajectory(space, keys, gs, gp, backend="ref")
    assert ip_p.shape == ip_r.shape == (T,)
    np.testing.assert_allclose(np.asarray(ip_p), np.asarray(ip_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(n_p), np.asarray(n_r), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_r),
                               rtol=1e-4, atol=1e-6)


def test_gradip_auto_resolution():
    """auto -> pallas for concrete single-device vectors, ref for tracers."""
    gp = jnp.ones((256,), jnp.float32)
    assert _resolve_gradip_backend(None, gp) == "pallas"
    assert _resolve_gradip_backend("auto", np.ones((4,), np.float32)) \
        == "pallas"
    seen = {}

    def f(v):
        seen["route"] = _resolve_gradip_backend("auto", v)
        return v

    jax.jit(f)(gp)
    assert seen["route"] == "ref"
    with pytest.raises(ValueError):
        _resolve_gradip_backend("bogus", gp)


def test_parse_mesh_spec():
    from repro.launch.mesh import parse_mesh_spec
    mc = parse_mesh_spec("2x2")
    assert (mc.data, mc.model, mc.pods) == (2, 2, 1)
    assert mc.n_devices == 4 and mc.batch_axes == ("data",)
    mc3 = parse_mesh_spec("2x16x16")
    assert (mc3.pods, mc3.data, mc3.model) == (2, 16, 16)
    assert mc3.batch_axes == ("pod", "data")
    assert parse_mesh_spec("single").n_devices == 256
    assert parse_mesh_spec("multi").n_devices == 512
    with pytest.raises(ValueError):
        parse_mesh_spec("2x")
    with pytest.raises(ValueError):
        parse_mesh_spec("weird")


def test_fl_plan_specs_without_devices():
    """FLShardPlan spec logic that needs no real mesh devices."""
    from repro.configs.base import MeshConfig
    from repro.sharding.fl import FLShardPlan
    P = jax.sharding.PartitionSpec
    mc = MeshConfig(data=2, model=2)
    plan = FLShardPlan.__new__(FLShardPlan)
    object.__setattr__(plan, "mesh", None)
    object.__setattr__(plan, "mesh_cfg", mc)
    object.__setattr__(plan, "rule", "fsdp")
    assert plan.batch_axes == ("data", "model") and plan.dp == 4
    assert plan.client_batch_spec(8, 3) == P(("data", "model"), None, None)
    assert plan.client_batch_spec(7, 2) == P(None, None)  # ragged fleet
    object.__setattr__(plan, "rule", "tp")
    assert plan.batch_axes == ("data",) and plan.dp == 2
    with pytest.raises(ValueError):
        FLShardPlan(None, mc, rule="bogus")
