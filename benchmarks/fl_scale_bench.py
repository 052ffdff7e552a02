"""Federated-round scaling benchmark: round time + comm bytes vs
client count x mesh shape x fleet knobs (ISSUE 5 tentpole, grown to the
fleet scale of ISSUE 10; writes ``runs/bench/BENCH_fl_scale.json``).

For each (arch in {tiny, qwen3-4b-reduced}) x (client count) x (mesh
spec), a **subprocess** (XLA must learn the forced host-device count
before jax initializes) runs ``FederatedZO`` rounds under the
``sharding/fl.FLShardPlan`` mesh route and reports:

* ``round_s``          — median wall time of a full federated round,
* ``comm_up/down``     — FL protocol bytes per round (``CommLog``; must be
  mesh-invariant — gated),
* ``collectives``      — per-device intra-mesh collective bytes of the
  compiled client-group HLO (``launch/hlo_tools.collective_bytes``): the
  cost sharding *adds* (ZeRO-3 weight gather) next to the scalars the FL
  protocol moves — the paper's 1000x saving is only meaningful when both
  are visible,
* the production 16x16 mesh (256 host devices) as a **dry-run row**:
  lower + compile + collective extraction only, execution skipped
  (matching ``launch/dryrun.py`` semantics).

**Fleet rows** (``--cohort``/``--quantize``; DESIGN.md §12) scale the
client count K into the thousands with a fixed sampled cohort ``m`` and
a quantized uplink, at T=1 (Alg. 3 high-frequency downlink — seeds +
scalars, independent of model size).  Executed at K in {64, 512};
K=4096 runs compile-only with *analytic* per-round comm bytes (the
protocol traffic is a closed form of (m, T, n_dirs, codec) — gated to
match the measured rows at smaller K).  Fleet gates:

* ``comm_bytes_scale_sublinear_in_K`` — per-round protocol bytes grow
  strictly slower than K at fixed cohort (they are constant),
* ``uplink_model_independent``        — fleet uplink+downlink bytes are
  identical across architectures (seeds + scalars only),
* ``quant_uplink_saves_bytes``        — int8 rows bill less uplink than
  the f32 rows of the same cell,
* ``round_time_sublinear_in_K``       — wall-clock per round grows
  sublinearly in K at fixed cohort size.

``zo_backend="ref"`` everywhere so mesh shapes compare the same per-step
route (the fused-vs-ref axis is BENCH_zo_step's job).

A CPU harness: each worker runs with ``JAX_PLATFORMS=cpu`` unless the
caller set it, so its forced host meshes never wait on an accelerator.
Its timings are XLA:CPU timings, not device numbers.

Usage:
  PYTHONPATH=src python -m benchmarks.fl_scale_bench              # full grid
  PYTHONPATH=src python -m benchmarks.fl_scale_bench --smoke      # CI subset
  PYTHONPATH=src python -m benchmarks.fl_scale_bench --fleet-only # merge
      just the fleet rows into an existing BENCH_fl_scale.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

RUNS_DIR = os.path.join(os.path.dirname(__file__), "..", "runs", "bench")
ARCHS = ("tiny", "qwen3-4b")
EXEC_MESHES = ("none", "1x1", "2x2")
DRYRUN_MESH = "16x16"
FLEET_COHORT = 16

# the fleet axis: (arch, K, quantize, compile_only) at T=1, mesh none
FLEET_CELLS = (
    ("tiny", 64, "none", False),
    ("tiny", 64, "int8", False),
    ("tiny", 512, "int8", False),
    ("qwen3-4b", 64, "int8", False),
    ("tiny", 4096, "int8", True),
    ("qwen3-4b", 4096, "int8", True),
)


def mesh_devices(spec: str) -> int:
    if spec == "none":
        return 1
    from repro.launch.mesh import parse_mesh_spec  # no jax device state
    return parse_mesh_spec(spec).n_devices


# --------------------------------------------------------------------------
# worker: one (arch, clients, mesh, cohort, quantize) cell, fresh process
# --------------------------------------------------------------------------

def worker(a) -> dict:
    import jax
    import jax.numpy as jnp  # noqa: F401
    import numpy as np

    from repro.checkpoint.state import server_state_sizes
    from repro.configs import get_config
    from repro.configs.base import FLConfig
    from repro.configs.tiny import TINY
    from repro.core import (Client, ClientSampler, FederatedZO, make_codec,
                            random_mask, round_keys)
    from repro.data.partition import dirichlet_partition, subset
    from repro.data.synthetic import TaskSpec, make_task_fns, sample_dataset
    from repro.launch.hlo_tools import COLLECTIVE_FACTOR, collective_bytes
    from repro.models import Model
    from repro.sharding.fl import make_fl_plan

    cfg = TINY if a.arch == "tiny" else get_config(a.arch).reduced()
    spec = TaskSpec(vocab=min(cfg.vocab, 512), seq_len=16)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    loss, _, _ = make_task_fns(model, spec)
    space = random_mask(params, density=1e-2, seed=3, balanced=False)

    fleet = 0 < a.cohort < a.clients
    m = a.cohort if fleet else a.clients
    # compile-only fleet cells only ever run the m-wide group program, so
    # materializing thousands of client datasets would be pure waste: the
    # K axis enters through the *analytic* protocol bytes below
    n_build = m if (fleet and a.compile_only) else a.clients
    train = sample_dataset(spec, max(2048, n_build * a.T * 16), seed=1)
    parts = dirichlet_partition(train["label"], n_build, 0.5, seed=0)
    clients = [Client(k, subset(train, p), a.batch)
               for k, p in enumerate(parts)]
    plan = (None if a.mesh == "none"
            else make_fl_plan(spec=a.mesh, rule=a.rule))
    fl = FLConfig(n_clients=a.clients, local_steps=a.T, lr=5e-2, eps=1e-3,
                  seed=0, zo_backend="ref", batch_size=a.batch,
                  quantize=a.quantize)
    sampler = (ClientSampler(range(a.clients), m=m, seed=0)
               if fleet and not a.compile_only else None)
    srv = FederatedZO(loss, params, space, fl, clients, plan=plan,
                      sampler=sampler)

    rec = {"arch": cfg.name, "mesh": a.mesh, "rule": a.rule,
           "n_devices": 1 if plan is None else plan.mesh_cfg.n_devices,
           "clients": a.clients, "T": a.T, "space_n": space.n,
           "n_params": model.n_params,
           "cohort": a.cohort, "quantize": a.quantize,
           "mode": "compile-only" if a.compile_only else "exec"}

    if not a.compile_only:
        # warm every jit cache (client group + virtual-path recon) with a
        # real round, then time
        srv.run_round()
        times = []
        for _ in range(a.reps):
            up0, down0 = srv.comm.up_bytes, srv.comm.down_bytes
            t0 = time.time()
            srv.run_round()
            times.append(time.time() - t0)
        rec["round_s"] = round(float(np.median(times)), 4)
        rec["comm_up_bytes_per_round"] = srv.comm.up_bytes - up0
        rec["comm_down_bytes_per_round"] = srv.comm.down_bytes - down0
        sizes = server_state_sizes(srv)
        rec["server_model_state_bytes"] = sizes["model_state_bytes"]
        rec["server_per_client_state_bytes"] = \
            sizes["per_client_state_bytes"]
        if a.quantize != "none":
            # quantization error on real round scalars: an identity-twin
            # server (same seeds, same cohort draws) produces the
            # unquantized uploads; roundtrip them through this cell's codec
            twin = FederatedZO(
                loss, params, space,
                FLConfig(n_clients=a.clients, local_steps=a.T, lr=5e-2,
                         eps=1e-3, seed=0, zo_backend="ref",
                         batch_size=a.batch),
                clients, plan=plan,
                sampler=(ClientSampler(range(a.clients), m=m, seed=0)
                         if fleet else None))
            gs = twin.run_round()
            codec = make_codec(a.quantize)
            g = np.concatenate([np.asarray(v, np.float32).ravel()
                                for v in gs.values()])
            dec = np.concatenate(
                [codec.decode(codec.encode(np.asarray(v))).ravel()
                 for v in gs.values()])
            rec["quant_rel_err"] = round(
                float(np.linalg.norm(dec - g)
                      / (np.linalg.norm(g) + 1e-30)), 6)
    else:
        # analytic protocol bytes: uplink = m encoded scalar blocks,
        # downlink = m seed+scalar packets (T=1 high-freq) — a closed
        # form of (m, T, n_dirs, codec), gated against the measured
        # rows at smaller K
        n_scalars = a.T * getattr(fl, "n_dirs", 1)
        rec["comm_up_bytes_per_round"] = m * srv.codec.nbytes(n_scalars)
        rec["comm_down_bytes_per_round"] = m * srv._down_bytes(a.T)
        rec["comm_analytic"] = True

    # collective extraction needs the Compiled object, which only the AOT
    # lower().compile() path exposes — one extra compile per cell, paid
    # after the timing loop (and the *only* compile in compile-only mode,
    # the 16x16 dry-run and K=4096 fleet rows).  Fleet cells probe the
    # m-wide cohort program on the first m clients — the sampler's RNG
    # must not advance outside run_round.
    probe = clients[:m]
    batches = srv._stack([c.next_batches(a.T) for c in probe])
    for c in probe:
        c.ptr = 0
    grp = srv._batch_run_for(a.T, m, template_batches=batches)
    keys = round_keys(fl.seed, 0, a.T)
    keys_d, batches_d = srv._place_group(keys, batches, m)
    t0 = time.time()
    compiled = grp.lower(srv.params, keys_d, batches_d).compile()
    rec["compile_s"] = round(time.time() - t0, 2)
    coll = collective_bytes(compiled.as_text())
    rec["collectives"] = coll
    rec["collective_wire_bytes_per_device"] = sum(
        COLLECTIVE_FACTOR[op] * b for op, b in coll.items())
    rec["ok"] = True
    return rec


# --------------------------------------------------------------------------
# parent: spawn one subprocess per cell with the right XLA_FLAGS
# --------------------------------------------------------------------------

def run_cell(arch: str, clients: int, mesh: str, rule: str, T: int,
             reps: int, compile_only: bool, cohort: int = 0,
             quantize: str = "none", batch: int = 16) -> dict:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    n = mesh_devices(mesh)
    if n > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}"
                            ).strip()
    out = tempfile.NamedTemporaryFile(suffix=".json", delete=False)
    out.close()
    cmd = [sys.executable, "-m", "benchmarks.fl_scale_bench", "--worker",
           "--arch", arch, "--clients", str(clients), "--mesh", mesh,
           "--rule", rule, "--T", str(T), "--reps", str(reps),
           "--cohort", str(cohort), "--quantize", quantize,
           "--batch", str(batch), "--out-json", out.name]
    if compile_only:
        cmd.append("--compile-only")
    t0 = time.time()
    rec = {"arch": arch, "mesh": mesh, "rule": rule, "clients": clients,
           "T": T, "cohort": cohort, "quantize": quantize, "ok": False}
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=3600)
        if proc.returncode == 0 and os.path.getsize(out.name):
            with open(out.name) as f:
                rec = json.load(f)
        else:
            rec["error"] = (proc.stderr or proc.stdout)[-2000:]
    except subprocess.TimeoutExpired:
        rec["error"] = "timeout (3600s)"  # record the cell, keep the grid
    finally:
        rec["wall_s"] = round(time.time() - t0, 1)
        os.unlink(out.name)
    status = "ok " if rec.get("ok") else "FAIL"
    fleet = f"m={cohort} {quantize} " if cohort else ""
    print(f"[{status}] {arch} K={clients} mesh={mesh} {fleet}"
          f"{'(compile-only) ' if compile_only else ''}"
          f"round={rec.get('round_s', '-')}s wall={rec['wall_s']}s",
          flush=True)
    return rec


def _fleet_key(r) -> tuple:
    return (r["arch"], r.get("T"), r.get("cohort", 0),
            r.get("quantize", "none"))


def gates(rows) -> dict:
    """Protocol gates over the result grid.  Gates that have nothing to
    compare in this grid report ``None`` (not compared) rather than
    passing vacuously; ``comm_invariant_across_mesh`` requires at least
    one cell measured on >= 2 distinct meshes."""
    ok_rows = [r for r in rows if r.get("ok")]

    # mesh invariance: same (arch, K, T, cohort, quantize) cell, >= 2
    # meshes, identical protocol bytes — fleet rows run one mesh and are
    # simply not compared here
    per_cell, meshes = {}, {}
    for r in ok_rows:
        if r.get("mode") == "exec" and "comm_up_bytes_per_round" in r:
            cell = (r["arch"], r["clients"], r.get("T"),
                    r.get("cohort", 0), r.get("quantize", "none"))
            per_cell.setdefault(cell, set()).add(
                (r["comm_up_bytes_per_round"],
                 r["comm_down_bytes_per_round"]))
            meshes.setdefault(cell, set()).add(r["mesh"])
    multi = [c for c, ms in meshes.items() if len(ms) >= 2]
    comm_invariant = (all(len(per_cell[c]) == 1 for c in multi)
                      if multi else None)

    # fleet gates: group fleet rows (cohort > 0) by everything but K
    fleet = [r for r in ok_rows if r.get("cohort", 0) > 0
             and "comm_up_bytes_per_round" in r]
    by_cell = {}
    for r in fleet:
        by_cell.setdefault(_fleet_key(r), {})[r["clients"]] = r

    def tot(r):
        return (r["comm_up_bytes_per_round"]
                + r["comm_down_bytes_per_round"])

    sub_bytes, sub_time = [], []
    for ks in by_cell.values():
        Ks = sorted(ks)
        for k1, k2 in zip(Ks, Ks[1:]):
            a, b = ks[k1], ks[k2]
            sub_bytes.append(tot(b) * k1 < tot(a) * k2)  # strictly sublinear
            if "round_s" in a and "round_s" in b:
                sub_time.append(b["round_s"] * k1 < a["round_s"] * k2)
    comm_sublinear = all(sub_bytes) if sub_bytes else None
    time_sublinear = all(sub_time) if sub_time else None

    # model independence: same (K, T, cohort, quantize), >= 2 archs,
    # identical protocol bytes (seeds + scalars carry no model dims)
    by_arch = {}
    for r in fleet:
        key = (r["clients"], r.get("T"), r.get("cohort", 0),
               r.get("quantize", "none"))
        by_arch.setdefault(key, {})[r["arch"]] = (
            r["comm_up_bytes_per_round"], r["comm_down_bytes_per_round"])
    multi_arch = [v for v in by_arch.values() if len(v) >= 2]
    model_indep = (all(len(set(v.values())) == 1 for v in multi_arch)
                   if multi_arch else None)

    # quantization savings: same (arch, K, T, cohort), int vs none
    savings = []
    by_quant = {}
    for r in fleet:
        key = (r["arch"], r["clients"], r.get("T"), r.get("cohort", 0))
        by_quant.setdefault(key, {})[r.get("quantize", "none")] = \
            r["comm_up_bytes_per_round"]
    for v in by_quant.values():
        if "none" in v:
            for q, up in v.items():
                if q != "none":
                    savings.append(up < v["none"])
    quant_saves = all(savings) if savings else None

    return {"comm_invariant_across_mesh": comm_invariant,
            "comm_bytes_scale_sublinear_in_K": comm_sublinear,
            "round_time_sublinear_in_K": time_sublinear,
            "uplink_model_independent": model_indep,
            "quant_uplink_saves_bytes": quant_saves,
            "all_ok": all(r.get("ok") for r in rows) and bool(rows)}


def fleet_cells(smoke: bool):
    """Fleet-axis cells: (arch, K, mesh, compile_only, T, cohort, quant)."""
    if smoke:
        picks = (("tiny", 64, "int8", False), ("tiny", 4096, "int8", True))
    else:
        picks = FLEET_CELLS
    return [(arch, K, "none", co, 1, FLEET_COHORT, q)
            for arch, K, q, co in picks]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--mesh", default="none")
    ap.add_argument("--rule", default="fsdp")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--T", type=int, default=2)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--cohort", type=int, default=0,
                    help="fleet mode: fixed sampled cohort size (0 = every "
                         "client participates)")
    ap.add_argument("--quantize", default="none",
                    help="uplink codec for the fleet rows "
                         "(none|int8|int4[-nearest])")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--out-json", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset; writes BENCH_fl_scale_smoke.json")
    ap.add_argument("--fleet-only", action="store_true",
                    help="run only the fleet-axis cells and merge them "
                         "into the existing BENCH_fl_scale.json")
    a = ap.parse_args()

    if a.worker:
        rec = worker(a)
        with open(a.out_json, "w") as f:
            json.dump(rec, f, indent=1)
        return

    if a.smoke:
        # CI vehicle: one executed mesh pair + the 256-host-device
        # production mesh as a compile-only dry-run + the fleet axis
        # (sampled cohort, quantized uplink, K up to 4096 analytic)
        cells = [("tiny", 4, m, False, a.T, 0, "none")
                 for m in ("none", "2x2")]
        cells += [("tiny", 256, DRYRUN_MESH, True, a.T, 0, "none")]
        cells += fleet_cells(smoke=True)
        reps = 1
    elif a.fleet_only:
        cells = fleet_cells(smoke=False)
        reps = 3
    else:
        cells = [(arch, K, m, False, a.T, 0, "none")
                 for arch in ARCHS for K in (4, 8) for m in EXEC_MESHES]
        # production-mesh dry-run rows: 256 host devices, compile only
        cells += [(arch, 256, DRYRUN_MESH, True, a.T, 0, "none")
                  for arch in ARCHS]
        cells += fleet_cells(smoke=False)
        reps = 3
    rows = [run_cell(arch, K, mesh, a.rule, T, reps, co, cohort=m,
                     quantize=q, batch=a.batch)
            for arch, K, mesh, co, T, m, q in cells]

    os.makedirs(RUNS_DIR, exist_ok=True)
    name = "BENCH_fl_scale_smoke" if a.smoke else "BENCH_fl_scale"
    path = os.path.join(RUNS_DIR, f"{name}.json")
    if a.fleet_only and os.path.exists(path):
        with open(path) as f:
            prior = json.load(f)
        keep = [r for r in prior.get("rows", [])
                if r.get("cohort", 0) == 0]  # refresh the fleet rows
        rows = keep + rows
    result = {"bench": "fl_scale", "rule": a.rule, "T": a.T,
              "zo_backend": "ref", "fleet_cohort": FLEET_COHORT,
              "rows": rows, "gates": gates(rows)}
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"gates: {result['gates']}")
    print("wrote", os.path.abspath(path))


if __name__ == "__main__":
    main()
