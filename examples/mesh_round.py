"""Sharded federated round: the same MEERKAT round, on a device mesh.

    PYTHONPATH=src python examples/mesh_round.py            # 2x2 host mesh
    PYTHONPATH=src python examples/mesh_round.py --mesh 4x1

Forces a host-device mesh (XLA_FLAGS, before jax import), builds a
``sharding/fl.FLShardPlan`` (parameters FSDP-sharded per
``sharding/rules.py``, the client axis over the mesh batch axes), runs
rounds both unsharded and sharded, and verifies the tentpole invariant:
**the aggregated update and every GradIP trajectory are bit-identical** —
seed-replay virtual-path reconstruction does not care how the round was
sharded (DESIGN.md §9).
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--mesh", default="2x2", help="DxM host-device mesh spec")
ap.add_argument("--rounds", type=int, default=3)
ap.add_argument("--T", type=int, default=4)
a = ap.parse_args()

from repro.launch.mesh import (host_device_flag,  # noqa: E402 — no jax
                               parse_mesh_spec)   # device state touched

n_dev = parse_mesh_spec(a.mesh).n_devices
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + host_device_flag(n_dev)).strip()

import jax  # noqa: E402  (after the XLA_FLAGS setup, by design)
import numpy as np  # noqa: E402

from repro.configs.base import FLConfig  # noqa: E402
from repro.configs.tiny import TINY  # noqa: E402
from repro.core import (Client, FederatedZO,  # noqa: E402
                        pretrain_gradient_vec, sensitivity_mask)
from repro.data.corpus import pretrain_batches  # noqa: E402
from repro.data.partition import dirichlet_partition, subset  # noqa: E402
from repro.data.synthetic import (TaskSpec, make_task_fns,  # noqa: E402
                                  sample_dataset)
from repro.models import Model  # noqa: E402
from repro.sharding import make_fl_plan  # noqa: E402

spec = TaskSpec()
model = Model(TINY)
params = model.init(jax.random.key(0))
loss, _, evaluate = make_task_fns(model, spec)
pre = pretrain_batches(spec, n_batches=4, batch_size=16)
space = sensitivity_mask(lambda p, b: model.loss(p, b), params, pre,
                         density=1e-2)
gp = pretrain_gradient_vec(lambda p, b: model.loss(p, b), params, space, pre)

train = sample_dataset(spec, 1024, seed=1)
K = 4


def make_server(plan):
    parts = dirichlet_partition(train["label"], K, alpha=0.5, seed=0)
    clients = [Client(k, subset(train, p), 16) for k, p in enumerate(parts)]
    fl = FLConfig(n_clients=K, local_steps=a.T, lr=5e-2, eps=1e-3)
    return FederatedZO(loss, params, space, fl, clients, plan=plan)


print(f"single-device reference ({a.rounds} rounds, T={a.T}, K={K}) ...")
ref = make_server(None)
for _ in range(a.rounds):
    ref.run_round(gp_vec=gp)

plan = make_fl_plan(spec=a.mesh)  # rule="fsdp": bit-exact by design
print(f"mesh {a.mesh}: {plan.mesh_cfg.n_devices} devices, "
      f"params {plan.rule}-sharded, client axis over {plan.batch_axes}")
srv = make_server(plan)
for _ in range(a.rounds):
    srv.run_round(gp_vec=gp)

flat = lambda t: np.concatenate([np.asarray(x).ravel()
                                 for x in jax.tree.leaves(t)])
bit_params = bool(np.array_equal(flat(ref.params), flat(srv.params)))
bit_gradip = all(
    np.array_equal(np.stack(ref.gradip_log[c]), np.stack(srv.gradip_log[c]))
    for c in ref.gradip_log)
print(f"aggregated params bit-identical: {bit_params}")
print(f"GradIP trajectories bit-identical: {bit_gradip}")
print(f"comm per client per round: up 4*T = {4 * a.T} B "
      f"(mesh-invariant: {ref.comm.up_bytes == srv.comm.up_bytes})")
if not (bit_params and bit_gradip):
    sys.exit(1)
print("sharded round == single-device round, bit for bit.")
