"""Federated MEERKAT training driver.

Runs sparse-ZO federated fine-tuning of any registered architecture at its
published widths (``--arch qwen2-1.5b``), of its CPU-sized variant
(``--arch qwen2-1.5b-reduced``), or of the tiny model, on the synthetic
classification-LM task family with Dirichlet Non-IID clients — Algorithm 2
end to end:
mask calibration from the C4-proxy corpus, per-round seed ladders, client
local ZO steps, server virtual-path reconstruction and aggregation, and
optional MEERKAT-VP calibration + early stopping.

``--mesh DxM`` runs every round sharded on a device mesh
(``sharding/fl.FLShardPlan``): parameters per ``sharding/rules.py``
(``--mesh-rule``, FSDP by default), the client axis over the mesh batch
axes.  On a CPU host the requested device count is forced via XLA_FLAGS
before the backend starts (pre-parsed from argv in :func:`main`); on TPU
the same spec maps onto the physical topology.

Examples:
  PYTHONPATH=src python -m repro.launch.train --rounds 40 --T 10
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b-reduced \\
      --method full
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \\
      --density 1e-3 --clients 4 --rounds 3 --T 2   # one TPU v5e
  PYTHONPATH=src python -m repro.launch.train --vp --partition mixed
  PYTHONPATH=src python -m repro.launch.train --mesh 2x2 --rounds 4
  PYTHONPATH=src python -m repro.launch.train --checkpoint-dir runs/ckpt \\
      --checkpoint-every 1 --rounds 8   # then: same + --resume
  PYTHONPATH=src python -m repro.launch.train --drop-rate 0.2 --late-rate 0.1
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import jax
import numpy as np

from repro import obs
from repro.checkpoint.state import FINAL_NAME, LATEST_NAME
from repro.configs import get_config
from repro.configs.base import FLConfig
from repro.configs.tiny import TINY
from repro.core import (Client, DenseSpace, FederatedZO, LoRASpace,
                        magnitude_mask, pretrain_gradient_vec, random_mask,
                        sensitivity_mask)
from repro.data.corpus import pretrain_batches
from repro.data.partition import (dirichlet_partition, iid_partition,
                                  single_label_partition, subset)
from repro.data.synthetic import TaskSpec, make_task_fns, sample_dataset
from repro.launch.mesh import host_device_flag, parse_mesh_spec
from repro.models import Model
from repro.models.transformer import DEFAULT_CTX


def _force_mesh_devices(argv):
    """If --mesh asks for more devices than the host platform exposes,
    force the count via XLA_FLAGS.  Runs before the first device query —
    the count is fixed when the backend initializes (importing jax does
    not initialize it)."""
    spec = None
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            spec = argv[i + 1]
        elif a.startswith("--mesh="):
            spec = a.split("=", 1)[1]
    if not spec:
        return
    if "--xla_force_host_platform_device_count" in \
            os.environ.get("XLA_FLAGS", ""):
        return
    try:
        n = parse_mesh_spec(spec).n_devices
    except ValueError:
        return  # argparse will reject the spec with a proper error
    if n > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + host_device_flag(n)).strip()


def build_space(method, loss_fn, params, pre, density, seed):
    if method == "meerkat":
        return sensitivity_mask(loss_fn, params, pre, density)
    if method == "magnitude":
        return magnitude_mask(params, density)
    if method == "random":
        return random_mask(params, density, seed=seed, balanced=False)
    if method == "full":
        return DenseSpace(params)
    if method == "lora":
        return LoRASpace(params)
    raise ValueError(method)


def main(argv=None):
    """Run the driver on ``argv`` (default ``sys.argv[1:]``); returns the
    :class:`FederatedZO` server after its last round."""
    argv = sys.argv[1:] if argv is None else list(argv)
    _force_mesh_devices(argv)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny",
                    help="tiny, a registered arch at its published widths, "
                         "or <arch>-reduced for its CPU-sized variant")
    ap.add_argument("--method", default="meerkat",
                    choices=["meerkat", "magnitude", "random", "full", "lora"])
    ap.add_argument("--partition", default="dirichlet",
                    choices=["iid", "dirichlet", "single_label", "mixed"])
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--T", type=int, default=10)
    ap.add_argument("--lr", type=float, default=5e-2)
    ap.add_argument("--eps", type=float, default=1e-3)
    ap.add_argument("--density", type=float, default=1e-2)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-backend", default="auto",
                    choices=["auto", "pallas", "online", "dense"],
                    help="forward-attention route for the ZO loss forwards")
    ap.add_argument("--mesh", default=None,
                    help="run rounds sharded on a device mesh: DxM / PxDxM "
                         "host devices (e.g. 2x2), or single|multi for the "
                         "production 16x16 / 2x16x16 topologies")
    ap.add_argument("--mesh-rule", default="fsdp",
                    choices=["fsdp", "tp", "replicate"],
                    help="parameter sharding rule under --mesh "
                         "(sharding/fl.py; fsdp is bit-exact vs single "
                         "device, tp is allclose-level)")
    ap.add_argument("--vp", action="store_true",
                    help="MEERKAT-VP: calibrate GradIP + early-stop")
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--out", default=None, help="write history json here")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="write server snapshots here (ckpt_latest every "
                         "--checkpoint-every rounds, ckpt_final at the end)")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="rounds between snapshots under --checkpoint-dir")
    ap.add_argument("--resume", action="store_true",
                    help="restore ckpt_latest from --checkpoint-dir and "
                         "continue to --rounds (bit-exact vs uninterrupted)")
    ap.add_argument("--drop-rate", type=float, default=0.0,
                    help="per-(round, client) offline probability "
                         "(repro.fault.FaultPlan)")
    ap.add_argument("--late-rate", type=float, default=0.0,
                    help="per-(round, client) straggler probability; "
                         "uploads land 1..--max-staleness rounds late")
    ap.add_argument("--max-staleness", type=int, default=2,
                    help="straggler staleness bound in rounds")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the deterministic fault schedule")
    ap.add_argument("--kill-at-round", type=int, default=None,
                    help="SIGKILL this process mid-round r (fault-injection "
                         "harness; see tools/kill_recover.py)")
    ap.add_argument("--sample-frac", type=float, default=1.0,
                    help="per-round participation fraction; < 1 enables the "
                         "seeded ClientSampler (cohort size "
                         "max(1, round(frac*K)); DESIGN.md §12)")
    ap.add_argument("--sample-weighted", action="store_true",
                    help="weight cohort draws by client dataset size "
                         "(uniform otherwise)")
    ap.add_argument("--quantize", default="none",
                    choices=["none", "int8", "int4", "int8-nearest",
                             "int4-nearest"],
                    help="uplink codec for the ZO scalars "
                         "(core/quantize.py exact-replay quantizer)")
    a = ap.parse_args(argv)
    with obs.span("train.total") as total:
        server, m = _train(a, ap)
    print(f"final: acc={m['acc']:.4f} loss={m['loss']:.4f} "
          f"({total.seconds:.0f}s total)  comm: up={server.comm.up_bytes}B "
          f"down={server.comm.down_bytes}B")
    # where the run's time went: span seconds by name, counters, and
    # compile seconds by program (repro/obs.py)
    print("obs " + json.dumps(obs.totals(), sort_keys=True))
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"history": server.history, "final": m,
                       "args": vars(a)}, f, indent=1)
        print("wrote", a.out)
    return server


def _train(a, ap):
    """Build the model, coordinate space, clients and server from the
    parsed flags and run the rounds; returns (server, final metrics)."""
    cfg = TINY if a.arch == "tiny" else get_config(a.arch)
    if a.method == "lora" and cfg.lora_rank == 0:
        cfg = cfg.replace(lora_rank=4)
    spec = TaskSpec(vocab=min(cfg.vocab, 512), seq_len=16)
    ctx = dataclasses.replace(DEFAULT_CTX, attn_backend=a.attn_backend)
    plan = None
    if a.mesh:
        from repro.sharding.fl import make_fl_plan
        plan = make_fl_plan(spec=a.mesh, rule=a.mesh_rule)
        print(f"mesh: {a.mesh} ({plan.mesh_cfg.n_devices} devices, "
              f"rule={a.mesh_rule}, client axis over {plan.batch_axes})")
    model = Model(cfg, ctx=ctx)
    print(f"arch={cfg.name} params={model.n_params:,} ({cfg.dtype}) "
          f"method={a.method}")

    params = model.init(jax.random.key(a.seed))
    loss, per_example, evaluate = make_task_fns(model, spec)
    lm_loss_fn = lambda p, b: model.loss(p, b)
    pre = pretrain_batches(spec, n_batches=8, batch_size=32, seed=a.seed + 3)

    with obs.span("train.space") as sp:
        space = build_space(a.method, lm_loss_fn, params, pre, a.density,
                            a.seed)
    print(f"space: n={space.n:,} coords ({sp.seconds:.1f}s)")

    train = sample_dataset(spec, 2048, seed=a.seed + 1)
    ev = sample_dataset(spec, 512, seed=a.seed + 2)
    eval_batch = {k: np.asarray(v) for k, v in ev.items()}
    labels = train["label"]
    if a.partition == "iid":
        parts = iid_partition(len(labels), a.clients, seed=a.seed)
    elif a.partition == "dirichlet":
        parts = dirichlet_partition(labels, a.clients, a.alpha, seed=a.seed)
    elif a.partition == "single_label":
        parts = single_label_partition(labels, a.clients, seed=a.seed)
    else:  # mixed: 3/4 mildly heterogeneous + 1/4 single-label extremes
        nb = max(1, a.clients * 3 // 4)
        parts = (dirichlet_partition(labels, nb, 5.0, seed=a.seed)
                 + single_label_partition(labels, a.clients - nb,
                                          seed=a.seed + 1))
    clients = [Client(k, subset(train, p), a.batch)
               for k, p in enumerate(parts)]

    fl = FLConfig(n_clients=a.clients, rounds=a.rounds, local_steps=a.T,
                  lr=a.lr, eps=a.eps, density=a.density, seed=a.seed,
                  batch_size=a.batch, vp_calibration_steps=100,
                  vp_init_steps=20, vp_later_steps=20, vp_rho_later=2.0,
                  vp_sigma=0.25, vp_sigma_relative=True,
                  sample_frac=a.sample_frac,
                  sample_weighted=a.sample_weighted, quantize=a.quantize)
    gp = None
    if a.vp and not a.resume:
        # (resume restores the calibrated VPCS flags and the consumed data
        # pointers; recalibrating would reset both and break bit-exactness)
        gp = pretrain_gradient_vec(lm_loss_fn, params, space, pre)
    server = FederatedZO(loss, params, space, fl, clients, eval_fn=evaluate,
                         plan=plan)
    del params  # the server holds the weights (placed on the mesh if any)
    if server.sampler is not None or server.codec.spec != "none":
        m = "full" if server.sampler is None else server.sampler.m
        print(f"fleet: cohort {m}/{a.clients} per round"
              + (" (weighted)" if a.sample_weighted else "")
              + f", uplink codec {server.codec.spec}")

    fault_plan = None
    if a.drop_rate or a.late_rate or a.kill_at_round is not None:
        from repro.fault import FaultPlan
        kills = (a.kill_at_round,) if a.kill_at_round is not None else ()
        fault_plan = FaultPlan(a.clients, a.rounds, drop_rate=a.drop_rate,
                               late_rate=a.late_rate,
                               max_staleness=a.max_staleness,
                               seed=a.fault_seed, kill_rounds=kills)
        print("faults:", fault_plan.summary())

    if a.resume:
        if not a.checkpoint_dir:
            ap.error("--resume requires --checkpoint-dir")
        latest = os.path.join(a.checkpoint_dir, LATEST_NAME)
        server.load_checkpoint(latest)
        print(f"resumed from {latest} at round {server.round}")

    if gp is not None:
        results, flagged, _ = server.calibrate_vp(gp)
        print(f"VPCS flagged clients {flagged} "
              f"(rho_later={[round(r.rho_later, 2) for r in results]})")

    m0 = server.evaluate(eval_batch)
    print(f"round {server.round}: acc={m0['acc']:.4f} loss={m0['loss']:.4f}")
    server.run(max(0, a.rounds - server.round), eval_every=a.eval_every,
               eval_batch=eval_batch, verbose=True, fault_plan=fault_plan,
               checkpoint_dir=a.checkpoint_dir,
               checkpoint_every=a.checkpoint_every)
    if a.checkpoint_dir:
        final = server.save_checkpoint(os.path.join(a.checkpoint_dir,
                                                    FINAL_NAME))
        print("wrote", final)
    return server, server.evaluate(eval_batch)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
