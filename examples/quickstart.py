"""Quickstart: MEERKAT sparse-ZO federated fine-tuning in ~50 lines.

    PYTHONPATH=src python examples/quickstart.py               # full demo
    PYTHONPATH=src python examples/quickstart.py --rounds 10   # CI smoke

Builds a tiny decoder LM, selects the transferable sensitivity mask from a
C4-proxy corpus (0.1%-style extreme sparsity, scaled for the tiny model),
partitions a synthetic classification task across 8 Non-IID clients
(Dirichlet alpha=0.5), and runs high-frequency (T=1) MEERKAT rounds —
clients upload one scalar per step, the server reconstructs their virtual
paths and aggregates.  Runs on CPU.
"""
import argparse

import jax
import numpy as np

from repro.configs.base import FLConfig
from repro.configs.tiny import TINY
from repro.core import Client, FederatedZO, sensitivity_mask
from repro.data.corpus import pretrain_batches
from repro.data.partition import dirichlet_partition, subset
from repro.data.synthetic import TaskSpec, make_task_fns, sample_dataset
from repro.models import Model

ap = argparse.ArgumentParser()
ap.add_argument("--rounds", type=int, default=150)
ap.add_argument("--clients", type=int, default=8)
a = ap.parse_args()

spec = TaskSpec()
model = Model(TINY)
params = model.init(jax.random.key(0))
loss, per_example, evaluate = make_task_fns(model, spec)

# 1. transferable sparse mask from pre-training-gradient sensitivity (§2.1)
pre = pretrain_batches(spec, n_batches=8, batch_size=32)
space = sensitivity_mask(lambda p, b: model.loss(p, b), params, pre,
                         density=1e-2)
print(f"mask: {space.n} / {model.n_params} params "
      f"({space.n / model.n_params:.2%} density)")

# 2. Non-IID clients (Dirichlet alpha=0.5)
train = sample_dataset(spec, 2048, seed=1)
parts = dirichlet_partition(train["label"], n_clients=a.clients, alpha=0.5)
clients = [Client(k, subset(train, p), batch_size=16)
           for k, p in enumerate(parts)]

# 3. high-frequency MEERKAT (T=1): scalar-only sync every local step
fl = FLConfig(n_clients=a.clients, local_steps=1, lr=5e-2, eps=1e-3,
              density=1e-2)
server = FederatedZO(loss, params, space, fl, clients, eval_fn=evaluate)

ev = sample_dataset(spec, 512, seed=2)
eval_batch = {k: np.asarray(v) for k, v in ev.items()}
m0 = evaluate(params, eval_batch)
print(f"before: acc={float(m0['acc']):.3f}")
server.run(a.rounds, eval_every=max(1, a.rounds // 3),
           eval_batch=eval_batch, verbose=True)
m = evaluate(server.params, eval_batch)
print(f"after {a.rounds} rounds: acc={float(m['acc']):.3f}  "
      f"(upload/client/round = 4 bytes)")
