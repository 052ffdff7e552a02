"""Sharded-vs-single-device parity check for the federated ZO round.

Runs the same MEERKAT problem (tiny model, Non-IID clients, MEERKAT-VP
calibration, T>1 and high-frequency rounds) once unsharded and once per
requested mesh spec (``sharding/fl.FLShardPlan``), then asserts:

* round-aggregated parameters **bit-match** (``rule="fsdp"``/"replicate"),
* per-client GradIP trajectories bit-match,
* VPCS flag sets are identical,
* the updated parameter leaves keep the shardings the plan placed them
  with,
* CommLog byte accounting is identical (the FL protocol traffic must not
  depend on how the round is sharded),
* the ``make_fl_train_loop`` mesh route (global batch over the mesh batch
  axes, ``constrain_params``, mesh ``ShardCtx`` so ``resolve_attn_backend``
  sees the sharded mesh) matches the unsharded loop to float tolerance
  (its in-graph scalar aggregation is a psum whose ordering is
  mesh-dependent — DESIGN.md §9).

The process must be started with enough host devices for the largest
mesh, e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python tools/fl_mesh_parity.py --meshes 1x1,2x2

``tests/test_fl_mesh_parity.py`` runs exactly that as a subprocess;
CI runs it directly.  Exit code 0 iff every check passes; ``--json PATH``
writes the detailed per-mesh report.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.configs.tiny import TINY
from repro.core import (Client, FederatedZO, pretrain_gradient_vec,
                        random_mask)
from repro.core.fl_step import make_fl_train_loop
from repro.data.corpus import pretrain_batches
from repro.data.partition import dirichlet_partition, subset
from repro.data.synthetic import TaskSpec, make_task_fns, sample_dataset
from repro.models import Model
from repro.sharding.fl import make_fl_plan

SPEC = TaskSpec()


def flat_params(tree):
    return np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree.leaves(tree)])


def build_problem(seed: int = 0, n_clients: int = 4, density: float = 1e-2):
    model = Model(TINY)
    params = model.init(jax.random.key(seed))
    loss, per_example, evaluate = make_task_fns(model, SPEC)
    space = random_mask(params, density=density, seed=seed + 3,
                        balanced=False)
    pre = pretrain_batches(SPEC, n_batches=2, batch_size=16, seed=seed + 4)
    gp = pretrain_gradient_vec(lambda p, b: model.loss(p, b), params, space,
                               pre)
    train = sample_dataset(SPEC, 512, seed=seed + 1)
    return dict(model=model, params=params, loss=loss,
                per_example=per_example, space=space, gp=gp, train=train)


def make_clients(prob, n_clients: int, batch: int = 16):
    parts = dirichlet_partition(prob["train"]["label"], n_clients, 0.5,
                                seed=0)
    return [Client(k, subset(prob["train"], p), batch)
            for k, p in enumerate(parts)]


def run_server(prob, plan, *, T: int, rounds: int, n_clients: int):
    """One full MEERKAT-VP run; returns everything parity compares."""
    fl = FLConfig(n_clients=n_clients, local_steps=T, lr=5e-2, eps=1e-3,
                  seed=0, vp_calibration_steps=8,
                  vp_init_steps=4, vp_later_steps=4, vp_rho_later=2.0,
                  vp_sigma=0.25, vp_sigma_relative=True)
    srv = FederatedZO(prob["loss"], prob["params"], prob["space"], fl,
                      make_clients(prob, n_clients), plan=plan)
    placed = [l.sharding for l in jax.tree.leaves(srv.params)]
    _, flagged, _ = srv.calibrate_vp(prob["gp"])
    for _ in range(rounds):
        srv.run_round(gp_vec=prob["gp"])
    return dict(
        params=flat_params(srv.params),
        # the server's update returns each leaf with the sharding it was
        # placed with (a spec may drop its trailing Nones)
        shardings_kept=all(l.sharding.is_equivalent_to(s, l.ndim) for l, s
                           in zip(jax.tree.leaves(srv.params), placed)),
        gradip={cid: np.stack(v) for cid, v in srv.gradip_log.items()},
        flags=sorted(srv.early_stopped),
        comm=(srv.comm.up_bytes, srv.comm.down_bytes))


def run_hf_loop(prob, plan, *, n_steps: int, n_clients: int, batch: int = 8):
    """The ``make_fl_train_loop`` mesh route: global client batch sharded
    over the plan's batch axes, weights constrained per the plan's rule,
    model forwards under the plan's mesh ``ShardCtx`` (this is where
    ``resolve_attn_backend`` sees ``ctx.mesh`` in a real jitted step)."""
    base_model = prob["model"]
    ctx = base_model.ctx if plan is None else plan.shard_ctx(base_model.ctx)
    model = Model(TINY, ctx=ctx)
    _, per_example, _ = make_task_fns(model, SPEC)
    loop = make_fl_train_loop(
        lambda p, b: per_example(p, b), prob["space"], eps=1e-3, lr=5e-2,
        n_clients=n_clients, n_steps=n_steps,
        constrain_params=None if plan is None else plan.constrain_params_fn())
    B = n_clients * batch
    data = sample_dataset(SPEC, n_steps * B, seed=7)
    batches = {k: jnp.asarray(v).reshape(n_steps, B, *v.shape[1:])
               for k, v in data.items()}
    params, key = prob["params"], jax.random.key(11)
    if plan is not None:
        P = jax.sharding.PartitionSpec
        params = plan.place_params(params)
        key = plan.place_replicated(key)
        ba = plan.batch_axes if B % plan.dp == 0 else None
        batches = {k: jax.device_put(v, plan.named(
            P(None, ba, *([None] * (v.ndim - 2)))))
            for k, v in batches.items()}
    p_T, gs, metrics = jax.jit(loop)(params, key, batches)
    return dict(params=flat_params(p_T), gs=np.asarray(gs),
                loss=float(metrics["loss"]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--meshes", default="1x1,2x2",
                    help="comma-separated mesh specs to check against the "
                         "unsharded reference")
    ap.add_argument("--rule", default="fsdp",
                    choices=["fsdp", "tp", "replicate"])
    ap.add_argument("--T", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--hf-steps", type=int, default=4,
                    help="steps for the make_fl_train_loop route check")
    ap.add_argument("--json", default=None, help="write report here")
    a = ap.parse_args()

    bit_exact_rule = a.rule in ("fsdp", "replicate")
    prob = build_problem(n_clients=a.clients)
    ref = run_server(prob, None, T=a.T, rounds=a.rounds,
                     n_clients=a.clients)
    ref_hf = run_hf_loop(prob, None, n_steps=a.hf_steps,
                         n_clients=a.clients)
    report = {"rule": a.rule, "meshes": {}, "ok": True}
    for spec in a.meshes.split(","):
        plan = make_fl_plan(spec=spec, rule=a.rule)
        got = run_server(prob, plan, T=a.T, rounds=a.rounds,
                         n_clients=a.clients)
        got_hf = run_hf_loop(prob, plan, n_steps=a.hf_steps,
                             n_clients=a.clients)
        checks = {
            "params_bitmatch": bool(np.array_equal(ref["params"],
                                                   got["params"])),
            "params_allclose": bool(np.allclose(ref["params"],
                                                got["params"], atol=2e-5)),
            "gradip_bitmatch": all(
                np.array_equal(ref["gradip"][c], got["gradip"][c])
                for c in ref["gradip"]),
            "vpcs_flags_equal": ref["flags"] == got["flags"],
            "shardings_kept": got["shardings_kept"],
            "comm_bytes_equal": ref["comm"] == got["comm"],
            "hf_loop_allclose": bool(
                np.allclose(ref_hf["params"], got_hf["params"], atol=2e-5)
                and np.allclose(ref_hf["gs"], got_hf["gs"], atol=2e-4)),
        }
        required = ["params_allclose", "vpcs_flags_equal", "shardings_kept",
                    "comm_bytes_equal", "hf_loop_allclose"]
        if bit_exact_rule:
            required += ["params_bitmatch", "gradip_bitmatch"]
        ok = all(checks[k] for k in required)
        report["meshes"][spec] = {**checks, "ok": ok,
                                  "n_devices": plan.mesh_cfg.n_devices}
        report["ok"] = report["ok"] and ok
        print(f"[{'ok' if ok else 'FAIL'}] mesh {spec} rule={a.rule}: " +
              " ".join(f"{k}={v}" for k, v in checks.items()))
    if a.json:
        os.makedirs(os.path.dirname(a.json) or ".", exist_ok=True)
        with open(a.json, "w") as f:
            json.dump(report, f, indent=1)
        print("wrote", a.json)
    sys.exit(0 if report["ok"] else 1)


if __name__ == "__main__":
    main()
