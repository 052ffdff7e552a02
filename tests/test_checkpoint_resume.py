"""Bit-exact checkpoint/resume of the federated server (DESIGN.md §11).

In-process: save at round r, restore into a *fresh* server, run both to
R — params, GradIP logs, CommLog, client pointers, velocity and history
must be bit-identical, including across plan=None <-> 1x1 FLShardPlan
(mesh-reshape restore) and through fault rounds.  The full cross-process
drill — SIGKILL mid-round on a 2x2 mesh, resume unsharded — runs
``tools/kill_recover.py`` in a subprocess with forced host devices.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import CheckpointError
from repro.configs.base import FLConfig
from repro.configs.tiny import TINY
from repro.core import random_mask
from repro.core.server import Client, FederatedZO
from repro.data.synthetic import TaskSpec, make_task_fns, sample_dataset
from repro.fault import FaultPlan
from repro.models import Model

REPO = os.path.join(os.path.dirname(__file__), "..")
TOOL = os.path.join(REPO, "tools", "kill_recover.py")
SPEC = TaskSpec(vocab=min(TINY.vocab, 512))


@pytest.fixture(scope="module")
def prob():
    model = Model(TINY)
    params = model.init(jax.random.key(0))
    loss, _, evaluate = make_task_fns(model, SPEC)
    space = random_mask(params, density=1e-2, seed=0, balanced=False)
    gp = jnp.full((space.n,), 0.01, jnp.float32)
    return dict(params=params, loss=loss, evaluate=evaluate, space=space,
                gp=gp)


def mk_server(prob, plan=None, momentum=0.5, n_clients=3, T=2):
    fl = FLConfig(n_clients=n_clients, local_steps=T, batch_size=2,
                  server_momentum=momentum)
    clients = [Client(i, sample_dataset(SPEC, 8, seed=i), 2)
               for i in range(n_clients)]
    return FederatedZO(prob["loss"], prob["params"], prob["space"], fl,
                       clients, eval_fn=prob["evaluate"], plan=plan)


def flat(tree):
    return np.concatenate([np.asarray(x, np.float32).ravel()
                           for x in jax.tree.leaves(tree)])


def assert_servers_equal(a, b):
    assert np.array_equal(flat(a.params), flat(b.params))
    assert (a.comm.up_bytes, a.comm.down_bytes) == \
        (b.comm.up_bytes, b.comm.down_bytes)
    assert a.round == b.round
    assert [c.ptr for c in a.clients] == [c.ptr for c in b.clients]
    assert a.early_stopped == b.early_stopped
    assert a.history == b.history
    for cid in a.gradip_log:
        ea, eb = a.gradip_log[cid], b.gradip_log[cid]
        assert len(ea) == len(eb)
        for u, v in zip(ea, eb):
            assert (u is None) == (v is None)
            if u is not None:
                assert np.array_equal(u, v)
    if a.velocity is None:
        assert b.velocity is None
    else:
        assert np.array_equal(np.asarray(a.velocity),
                              np.asarray(b.velocity))


def run_rounds(srv, n, prob, fault_plan=None):
    for _ in range(n):
        faults = (fault_plan.round_faults(srv.round)
                  if fault_plan is not None else None)
        srv.run_round(gp_vec=prob["gp"], faults=faults)


def test_resume_bitexact_unsharded(prob, tmp_path):
    path = str(tmp_path / "ckpt.msgpack")
    ref = mk_server(prob)
    run_rounds(ref, 4, prob)
    donor = mk_server(prob)
    run_rounds(donor, 2, prob)
    donor.save_checkpoint(path)
    fresh = mk_server(prob)
    meta = fresh.load_checkpoint(path)
    assert meta["round"] == 2
    run_rounds(fresh, 2, prob)
    assert_servers_equal(ref, fresh)


def test_resume_through_fault_rounds(prob, tmp_path):
    """The fault schedule is rebuilt from flags on resume (FaultPlan is
    deterministic), so a run interrupted inside a faulty stretch —
    pending straggler uploads in flight — continues bit-exactly."""
    path = str(tmp_path / "ckpt.msgpack")
    fp = FaultPlan(3, 6, drop_rate=0.2, late_rate=0.3, max_staleness=2,
                   seed=5)
    ref = mk_server(prob, momentum=0.0)
    run_rounds(ref, 6, prob, fp)
    donor = mk_server(prob, momentum=0.0)
    run_rounds(donor, 3, prob, fp)
    donor.save_checkpoint(path)
    fresh = mk_server(prob, momentum=0.0)
    fresh.load_checkpoint(path)
    assert len(fresh._pending) == len(donor._pending)
    for p, q in zip(fresh._pending, donor._pending):
        assert (p["arrive"], p["cid"], p["src_round"], p["gip_idx"]) == \
            (q["arrive"], q["cid"], q["src_round"], q["gip_idx"])
        assert np.array_equal(p["gs"], q["gs"])
    run_rounds(fresh, 3, prob, FaultPlan(3, 6, drop_rate=0.2,
                                         late_rate=0.3, max_staleness=2,
                                         seed=5))
    assert_servers_equal(ref, fresh)


def test_mesh_reshape_restore_both_directions(prob, tmp_path):
    """plan=None -> 1x1 FLShardPlan and back: checkpoints store host
    arrays, restore re-places per the *target* plan, values unchanged."""
    from repro.sharding.fl import make_fl_plan
    plan = make_fl_plan(spec="1x1")
    ref = mk_server(prob)
    run_rounds(ref, 4, prob)

    # unsharded donor -> sharded survivor
    p1 = str(tmp_path / "a.msgpack")
    donor = mk_server(prob)
    run_rounds(donor, 2, prob)
    donor.save_checkpoint(p1)
    onto_mesh = mk_server(prob, plan=plan)
    onto_mesh.load_checkpoint(p1)
    run_rounds(onto_mesh, 2, prob)
    assert_servers_equal(ref, onto_mesh)

    # sharded donor -> unsharded survivor
    p2 = str(tmp_path / "b.msgpack")
    donor_m = mk_server(prob, plan=plan)
    run_rounds(donor_m, 2, prob)
    donor_m.save_checkpoint(p2)
    off_mesh = mk_server(prob)
    off_mesh.load_checkpoint(p2)
    run_rounds(off_mesh, 2, prob)
    assert_servers_equal(ref, off_mesh)


def test_early_stop_flags_survive_resume(prob, tmp_path):
    path = str(tmp_path / "ckpt.msgpack")
    ref = mk_server(prob)
    ref.early_stopped = {1}
    run_rounds(ref, 3, prob)
    donor = mk_server(prob)
    donor.early_stopped = {1}
    run_rounds(donor, 1, prob)
    donor.save_checkpoint(path)
    fresh = mk_server(prob)  # no flags set: must come from the file
    fresh.load_checkpoint(path)
    assert fresh.early_stopped == {1}
    run_rounds(fresh, 2, prob)
    assert_servers_equal(ref, fresh)


def test_config_mismatch_refused(prob, tmp_path):
    path = str(tmp_path / "ckpt.msgpack")
    donor = mk_server(prob, T=2)
    donor.run_round()
    donor.save_checkpoint(path)
    other_T = mk_server(prob, T=3)
    with pytest.raises(CheckpointError, match="config mismatch"):
        other_T.load_checkpoint(path)
    fewer = mk_server(prob, n_clients=2)
    with pytest.raises(CheckpointError, match="config mismatch"):
        fewer.load_checkpoint(path)


# -- the cross-process drill: die on a 2x2 mesh, recover unsharded ------------

@pytest.fixture(scope="module")
def kill_recover_report(tmp_path_factory):
    out = tmp_path_factory.mktemp("kr") / "report.json"
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, TOOL, "--rounds", "4", "--kill-at", "2",
         "--mesh-b", "2x2", "--json", str(out)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    with open(out) as f:
        return json.load(f)


def test_kill_recover_sigkill_observed(kill_recover_report):
    checks = kill_recover_report["checks"]
    assert checks["victim_sigkilled"]
    assert checks["latest_at_kill_round"]
    assert checks["resumed_from_kill_round"]


def test_kill_recover_final_state_bitexact(kill_recover_report):
    """Recovered-from-SIGKILL final checkpoint == uninterrupted run's,
    with the victim sharded 2x2 and the survivor unsharded."""
    checks = kill_recover_report["checks"]
    assert checks["leaves_bitmatch"]
    for field in ("round", "up_bytes", "down_bytes", "ptrs", "history"):
        assert checks[f"meta_{field}_equal"], field
