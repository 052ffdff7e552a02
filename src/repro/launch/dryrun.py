"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on the production mesh and extract the roofline raw data.

A host-device dry run: it pins ``JAX_PLATFORMS=cpu`` and forces 512 CPU
devices before JAX starts, so it never takes an accelerator from another
process (the production meshes are 256/512 host devices).

For each combo we do up to three compiles:

1. ``full``  — full-depth model with lax.scan over layer periods: proves the
   sharding lowers/compiles, and yields ``memory_analysis()`` (per-device
   argument/temp/output bytes — scan reuses one period's buffers, as on TPU).
2. ``fit1`` / ``fit2`` — depth-1 and depth-2 variants with every scan fully
   unrolled: XLA's HloCostAnalysis counts while-loop bodies once, so FLOPs /
   bytes / collective-bytes from a scanned module undercount by the trip
   count.  From the two unrolled points we fit ``f(n) = outside + n*body``
   and extrapolate exactly to the full depth.  (Methodology validated in
   EXPERIMENTS.md §Dry-run; the sLSTM time recurrence stays a scan — its
   per-step FLOPs are negligible and documented.)

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out runs/dryrun
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, get_config, get_shape
from repro.configs.base import InputShape, MeshConfig, ModelConfig
from repro.core.fl_step import make_fl_train_step
from repro.core.masks import abstract_mask
from repro.core.spaces import MaskedSpace
from repro.launch.hlo_tools import (COLLECTIVE_OPS, collective_bytes,
                                    cost_analysis)
from repro.launch.mesh import make_mesh_from_config, mesh_config
from repro.models import abstract_cache, abstract_params, decode_step, prefill
from repro.models.init import active_param_count, param_count
from repro.models.model import input_specs
from repro.models.transformer import ShardCtx, lm_loss
from repro.sharding.rules import (batch_specs, cache_specs, fsdp_only_specs,
                                  param_specs)

P = jax.sharding.PartitionSpec

DTYPE = jnp.bfloat16
FL_EPS = 1e-3
FL_LR = 1e-4


def _shallow_cfg(cfg: ModelConfig, n: int) -> ModelConfig:
    kw = dict(n_layers=cfg.period * n)
    if cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=n)
    return cfg.replace(**kw)


def _largest_block(S: int, target: int) -> int:
    """Largest divisor of S that is <= target (for q-block chunking)."""
    b = min(target, S)
    while S % b:
        b -= 1
    return b


def make_ctx(cfg: ModelConfig, shape: InputShape, mesh, mc: MeshConfig,
             unroll_all: bool = False, n_periods: Optional[int] = None):
    dp = mc.data * mc.pods
    seq_shard = shape.global_batch % dp != 0
    B_loc = max(1, shape.global_batch // dp)
    S = shape.seq_len + (cfg.n_patches if cfg.frontend == "vision_stub" else 0)
    q_block = 0
    if shape.kind != "decode" and S > 2048:
        # keep per-device f32 scores [B_loc, H, q_block, S] under ~1.5 GB
        budget = int(1.5e9)
        h_loc = max(1, cfg.n_heads // mc.model)
        target = max(128, budget // max(1, B_loc * h_loc * S * 4))
        q_block = _largest_block(S, min(target, 2048))
    mlstm_block = 0
    if cfg.xlstm is not None and shape.kind != "decode" and S > 2048:
        mlstm_block = _largest_block(S, 512)
    return ShardCtx(
        mesh=mesh, batch_axes=mc.batch_axes, model_axis="model",
        use_sharded_moe=cfg.moe is not None and shape.kind != "decode"
        and not seq_shard,
        attn_q_block=q_block, mamba_chunk=64, mlstm_block=mlstm_block,
        scan_unroll=(n_periods or cfg.n_periods) if unroll_all else 1,
        unroll_chunks=unroll_all, seq_shard=seq_shard,
        # dry-run models the Pallas selective-scan kernel's HBM footprint
        # (read dt/B/C/x once, write y once) — §Perf pair 3
        mamba_mode="stub" if shape.kind != "decode" else "scan")


def build_lowerable(cfg: ModelConfig, shape: InputShape, mesh,
                    mc: MeshConfig, step_kind: str, unroll_all: bool = False):
    """Returns (jitted_fn, abstract_args) ready for .lower()."""
    ctx = make_ctx(cfg, shape, mesh, mc, unroll_all=unroll_all)
    ap = abstract_params(cfg, dtype=DTYPE)
    pspecs = param_specs(cfg, ap, mc,
                         train=step_kind in ("zo_fl", "first_order"))
    sh = lambda spec: jax.sharding.NamedSharding(mesh, spec)
    bspecs = batch_specs(cfg, shape, mc)
    binputs = input_specs(cfg, shape, dtype=DTYPE)

    if step_kind == "zo_dp":
        # Beyond-paper ZO sharding (§Perf pair 2): no tensor parallelism —
        # all mesh axes act as the FL-client/data axis, weights are pure
        # FSDP and get gathered once per layer period inside the scan.
        all_axes = tuple(mc.axis_names)
        pspecs = fsdp_only_specs(cfg, ap, mc)
        ctx = dataclasses.replace(
            ctx, batch_axes=all_axes, use_sharded_moe=False,
            online_attn=True, attn_q_block=512)
        bspecs = {k: P(*((all_axes,) + (None,) * (len(v) - 1)))
                  for k, v in bspecs.items()}
        step_kind = "zo_fl"
    pshard = jax.tree.map(sh, pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    bshard = {k: sh(v) for k, v in bspecs.items()}

    if step_kind == "zo_fl":
        idx_tree, eff_density = abstract_mask(ap, density=1e-3)
        ishard = jax.tree.map(lambda l: sh(P(None)), idx_tree,
                              is_leaf=lambda x: isinstance(
                                  x, jax.ShapeDtypeStruct))
        dp = 1
        for a in ctx.batch_axes:
            dp *= int(mesh.shape[a])
        n_clients = dp if shape.global_batch % dp == 0 else 1

        def constrain_params(p):
            return jax.tree.map(
                lambda a, s: jax.lax.with_sharding_constraint(a, sh(s)),
                p, pspecs, is_leaf=lambda x: isinstance(x, P))

        def fn(params, idx_tree, seed, batch):
            space = MaskedSpace(idx_tree)
            step = make_fl_train_step(
                lambda p, b: lm_loss(p, b, cfg, ctx, per_example=True),
                space, eps=FL_EPS, lr=FL_LR, n_clients=n_clients,
                constrain_params=constrain_params)
            return step(params, jax.random.key(seed), batch)

        jf = jax.jit(fn, in_shardings=(pshard, ishard, sh(P()), bshard),
                     out_shardings=(pshard, sh(P(None)), None),
                     donate_argnums=(0,))
        args = (ap, idx_tree, jax.ShapeDtypeStruct((), jnp.uint32), binputs)
        return jf, args

    if step_kind == "first_order":
        def fn(params, batch):
            g = jax.grad(lambda p: lm_loss(p, batch, cfg, ctx))(params)
            return jax.tree.map(lambda p, gg: p - FL_LR * gg.astype(p.dtype),
                                params, g)

        jf = jax.jit(fn, in_shardings=(pshard, bshard),
                     out_shardings=pshard, donate_argnums=(0,))
        return jf, (ap, binputs)

    if step_kind == "prefill":
        def fn(params, batch):
            return prefill(params, batch, cfg, ctx)

        jf = jax.jit(fn, in_shardings=(pshard, bshard))
        return jf, (ap, binputs)

    if step_kind == "decode":
        S_tot = shape.seq_len + (cfg.n_patches
                                 if cfg.frontend == "vision_stub" else 0)
        ac = abstract_cache(cfg, shape.global_batch, S_tot, dtype=DTYPE)
        cspecs = cache_specs(cfg, ac, shape, mc)
        cshard = jax.tree.map(sh, cspecs, is_leaf=lambda x: isinstance(x, P))

        def fn(params, token, cache):
            return decode_step(params, token, cache, cfg, ctx)

        jf = jax.jit(fn, in_shardings=(pshard, bshard["token"], cshard),
                     out_shardings=(None, cshard), donate_argnums=(2,))
        return jf, (ap, binputs["token"], ac)

    raise ValueError(step_kind)


STEP_FOR_SHAPE = {"train": "zo_fl", "prefill": "prefill", "decode": "decode"}


def applicable(cfg: ModelConfig, shape: InputShape) -> bool:
    if shape.name == "long_500k":
        return cfg.supports_long_context
    return True


def run_combo(arch: str, shape_name: str, multi_pod: bool,
              step_kind: Optional[str] = None, fit: bool = True,
              verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mc = mesh_config(multi_pod=multi_pod)
    mesh = make_mesh_from_config(mc)
    step_kind = step_kind or STEP_FOR_SHAPE[shape.kind]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "step": step_kind, "ok": False,
           "n_params": param_count(cfg),
           "n_active_params": active_param_count(cfg),
           "n_devices": mc.n_devices}
    if not applicable(cfg, shape):
        rec["skipped"] = "long_500k requires a sub-quadratic mixer (DESIGN.md)"
        return rec
    try:
        # ---- full-depth compile: sharding proof + memory analysis ----------
        t0 = time.time()
        jf, args = build_lowerable(cfg, shape, mesh, mc, step_kind)
        lowered = jf.lower(*args)
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_est_bytes": int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
        }
        ca = cost_analysis(compiled)
        rec["cost_full_scan"] = {"flops": float(ca.get("flops", 0.0)),
                                 "bytes": float(ca.get("bytes accessed", 0.0))}
        rec["collectives_full_scan"] = collective_bytes(compiled.as_text())

        # ---- unrolled depth-1/2 compiles -> exact extrapolation -------------
        if fit:
            pts = {}
            for n in (1, 2):
                cfg_n = _shallow_cfg(cfg, n)
                jfn, argsn = build_lowerable(cfg_n, shape, mesh, mc,
                                             step_kind, unroll_all=True)
                cn = jfn.lower(*argsn).compile()
                can = cost_analysis(cn)
                pts[n] = {
                    "flops": float(can.get("flops", 0.0)),
                    "bytes": float(can.get("bytes accessed", 0.0)),
                    "coll": collective_bytes(cn.as_text()),
                }
            rec["fit_points"] = pts
            nper = cfg.n_periods
            def extrap(k):
                return pts[1][k] + (pts[2][k] - pts[1][k]) * (nper - 1)
            rec["cost"] = {"flops": extrap("flops"), "bytes": extrap("bytes")}
            rec["collectives"] = {
                op: pts[1]["coll"][op]
                + (pts[2]["coll"][op] - pts[1]["coll"][op]) * (nper - 1)
                for op in COLLECTIVE_OPS}
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(rec["error"])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--step", default=None,
                    help="override step kind (zo_fl|first_order|prefill|decode)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-fit", action="store_true",
                    help="skip the depth-1/2 cost-fit compiles")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = sorted(ASSIGNED) if (args.all or not args.arch) else [args.arch]
    shapes = (["train_4k", "prefill_32k", "decode_32k", "long_500k"]
              if (args.all or not args.shape) else [args.shape])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'multi' if mp else 'single'}"
                if args.step:
                    tag += f"_{args.step}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip] {tag} (cached)")
                    continue
                print(f"[run ] {tag} ...", flush=True)
                t0 = time.time()
                # fit compiles only needed on the single-pod roofline mesh
                rec = run_combo(arch, shape, mp, step_kind=args.step,
                                fit=(not args.no_fit) and not mp)
                rec["wall_s"] = round(time.time() - t0, 1)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = "ok" if rec["ok"] else (
                    "SKIP" if "skipped" in rec else "FAIL")
                print(f"[{status:4s}] {tag} wall={rec['wall_s']}s", flush=True)


if __name__ == "__main__":
    main()
