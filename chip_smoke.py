"""Smoke run of the federated ZO trainer and the server on one TPU.

Drives the main path once, in this one process, through the entry points a
user calls, at Qwen2-1.5B's published widths (28 layers, d_model 1536,
vocabulary 151,936, bf16 weights from a seed):

* device — the default device is a TPU and the Pallas kernels compile
  (no interpret mode);
* train  — ``repro.launch.train.main`` with the MEERKAT sensitivity mask
  at density 1e-3, MEERKAT-VP calibration over a mixed partition (three
  Dirichlet clients and one single-label client), a few rounds of a few
  local steps, and evaluation after every round;
* kernels — each Pallas kernel of the main path against its float32
  reference at Qwen2-1.5B's head shapes (flash attention forward and
  backward, flash-decode, GradIP, the ZO perturb and update);
* serve  — ``repro.launch.serve.main`` on four long prompts (prefill above
  1,024 tokens) with the flash-decode kernel, then the same requests with
  the plain ``ref`` decode route, comparing the first decode step's logits.

Each phase prints one JSON line.  Times there are smoke numbers taken on
the way (compile against steady), not benchmark numbers.  The last line is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
Where JAX finds no TPU the script exits non-zero and prints no result.

``--four-chips`` runs only the sharded round: the same training problem
on a 2x2 mesh (FSDP plan) and unsharded, and whether the aggregated
parameters and the GradIP trajectories match bit for bit.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips of one host
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-1.5b"
# First-decode-step logits, flash-decode kernel against the grouped jnp
# route: max |kernel - ref| <= LOGIT_RTOL * max |ref|.  Both routes keep
# the KV cache and the residual stream in bf16 and differ only in rounding
# inside attention, once per layer; through 28 layers of random weights
# that drifts the logits by about 3% of their scale (measured on a v5e:
# 2.6% with 24-token prompts, where both routes attend over the same few
# keys, and 2.9% at 1,376).  The kernel itself is held to KERNEL_RTOL
# against float32 in the kernel phase; this check catches a route that
# is wired wrong end to end (greedy tokens cannot: random weights tie).
LOGIT_RTOL = 5e-2
# Kernel against its f32 reference on the same bf16 inputs: the kernels
# accumulate in f32, so only rounding of the bf16 operands and outputs
# (2^-9) and of f32 sums separates them.
KERNEL_RTOL = 1e-2


def _emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _memory() -> dict:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_limit")}


def _cfg(arch: str):
    from repro.configs import get_config
    from repro.configs.tiny import TINY
    return TINY if arch == "tiny" else get_config(arch)


def device_phase(n_chips: int) -> dict:
    """The default backend is a TPU with ``n_chips`` devices and kernels
    compile; raises SystemExit otherwise."""
    import jax

    from repro.kernels.ops import _default_interpret
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default device is "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < n_chips:
        raise SystemExit(f"need {n_chips} TPU chips, JAX sees {len(devs)}")
    if _default_interpret():
        raise SystemExit("Pallas kernels would run in interpret mode")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def kernel_phase(arch: str = ARCH, *, B: int = 4, S: int = 1024,
                 seed: int = 0) -> dict:
    """Every main-path kernel against its plain float32 reference
    (``kernels/ref.py``, the dense attention route) at the arch's head
    shapes, on random bf16 inputs.  Queries are scaled so attention is
    peaked: a wrong block, head or length mask then errs at O(1) of the
    output, while the kernels' own rounding stays near bf16's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels import ref as R
    from repro.models.init import param_count
    from repro.models.layers import forward_attention

    cfg = _cfg(arch)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = iter(jax.random.split(jax.random.key(seed), 16))

    def normal(shape, scale=1.0, dtype=jnp.bfloat16):
        return (scale * jax.random.normal(next(ks), shape)).astype(dtype)

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))

    lengths = jnp.asarray([S, S // 2 + 3, S // 3 + 1, 17][:B], jnp.int32)
    rep = {}
    with jax.default_matmul_precision("highest"):
        q = normal((B, KV, H // KV, hd), 4.0)
        k, v = normal((B, S, KV, hd)), normal((B, S, KV, hd))
        rep["flash_decode"] = rel(
            ops.flash_decode(q, k, v, lengths),
            R.decode_attention_ref(q.astype(jnp.float32), k, v, lengths))

        q = normal((B, S, H, hd), 4.0)
        cot = normal((B, S, H, hd), 1.0, jnp.float32)

        def attn(backend, q, k, v):
            return forward_attention(q, k, v, cfg, lengths=lengths,
                                     backend=backend).astype(jnp.float32)

        def loss(backend, q, k, v):
            return jnp.sum(attn(backend, q, k, v) * cot)

        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        rep["flash_attention_fwd"] = rel(attn("pallas", q, k, v),
                                         attn("dense", *f32))
        g_k = jax.grad(functools.partial(loss, "pallas"), (0, 1, 2))(q, k, v)
        g_r = jax.grad(functools.partial(loss, "dense"), (0, 1, 2))(*f32)
        rep["flash_attention_bwd"] = max(rel(a, b) for a, b in zip(g_k, g_r))

        n = max(1, round(param_count(cfg) * 1e-3))  # mask at 1e-3
        gp, z = normal((n,), 1.0, jnp.float32), normal((n,), 1.0, jnp.float32)
        rep["gradip"] = rel(ops.gradip_flat(gp, z, 0.5),
                            R.gradip_reduce_ref(gp, z, 0.5))
    w, z = normal((1 << 22,)), normal((1 << 22,), 1.0, jnp.float32)
    plus, minus = ops.zo_dual_perturb_flat(w, z, None, 1e-3)
    p_ref, m_ref = R.dual_perturb_ref(w, z, 1.0, 1e-3)
    rep["zo_dual_perturb_exact"] = bool(
        jnp.array_equal(plus, p_ref) and jnp.array_equal(minus, m_ref))
    rep["zo_fused_update_exact"] = bool(jnp.array_equal(
        ops.zo_fused_update_flat(w, z, None, -0.05),
        R.fused_update_ref(w, z, 1.0, -0.05)))
    rep["rtol"] = KERNEL_RTOL
    bad = [k for k, e in rep.items()
           if e is False or (isinstance(e, float) and e > KERNEL_RTOL)]
    assert not bad, (bad, rep)
    return rep


def _train_argv(arch, *, clients, rounds, T, batch, density, seed, mesh=None):
    argv = ["--arch", arch, "--method", "meerkat", "--density", str(density),
            "--vp", "--partition", "mixed", "--clients", str(clients),
            "--rounds", str(rounds), "--T", str(T), "--batch", str(batch),
            "--eval-every", "1", "--seed", str(seed)]
    return argv + (["--mesh", mesh, "--mesh-rule", "fsdp"] if mesh else [])


def train_phase(arch: str = ARCH, *, clients: int = 4, rounds: int = 3,
                T: int = 2, batch: int = 16, density: float = 1e-3,
                seed: int = 0) -> dict:
    """Federated training through ``repro.launch.train.main``; returns the
    phase report (raises AssertionError on a wrong result)."""
    import jax
    import numpy as np

    from repro.launch import train
    from repro.models import Model
    from repro.models.layers import resolve_attn_backend

    cfg = _cfg(arch)
    t0 = time.perf_counter()
    server = train.main(_train_argv(arch, clients=clients, rounds=rounds,
                                    T=T, batch=batch, density=density,
                                    seed=seed))
    wall = time.perf_counter() - t0
    leaves = jax.tree.leaves(server.params)
    losses = [h["loss"] for h in server.history]
    gradip = np.concatenate([np.ravel(t) for t in server.vp_trajectories])
    # the mask coordinates that the rounds actually moved: an update that
    # bf16 rounding swallows leaves its coordinate unchanged
    v0 = np.asarray(server.space.slice(Model(cfg).init(jax.random.key(seed))))
    v1 = np.asarray(server.space.slice(server.params))
    seq = server.clients[0].data["tokens"].shape[1]
    rep = {
        "arch": cfg.name,
        "params": int(sum(l.size for l in leaves)),
        "dtype": sorted({str(l.dtype) for l in leaves}),
        "mask_coords": int(server.space.n),
        "zo_routes": {f"T={t} x{n}": r
                      for (t, n), r in sorted(server.zo_routes.items())},
        "attn_routes": {
            "zo_forward": resolve_attn_backend("auto", cfg, S=seq,
                                               differentiable=False),
            "mask_grad": resolve_attn_backend("auto", cfg, S=seq,
                                              differentiable=True)},
        "vp_flagged": sorted(server.early_stopped),
        "losses": losses,
        "gradip_abs_max": float(np.max(np.abs(gradip))),
        "mask_changed_share": float(np.mean(v0 != v1)),
        "smoke_round_s": {"first_compile_and_run": server.round_seconds[0],
                          "steady": server.round_seconds[1:]},
        "smoke_wall_s": wall,
        "memory": _memory(),
    }
    assert rep["params"] == Model(cfg).n_params, rep["params"]
    assert rep["dtype"] == [cfg.dtype], rep["dtype"]
    assert losses and np.all(np.isfinite(losses)), losses
    assert gradip.size and np.all(np.isfinite(gradip)), "non-finite GradIP"
    assert rep["mask_changed_share"] > 0, "no mask coordinate moved"
    return rep


def _serve_argv(arch, backend, *, requests, max_prompt, s_max, seed):
    return ["--arch", arch, "--backend", backend, "--requests", str(requests),
            "--max-batch", str(requests), "--max-new", "1",
            "--max-prompt", str(max_prompt), "--s-max", str(s_max),
            "--seed", str(seed)]


def serve_phase(arch: str = ARCH, *, requests: int = 4,
                max_prompt: int = 1500, s_max: int = 2048,
                seed: int = 0) -> dict:
    """Serving through ``repro.launch.serve.main``: the auto decode route
    (the flash-decode kernel) against ``ref`` on the first decode step's
    logits — logits, not greedy tokens, because random weights tie."""
    import numpy as np

    from repro.launch import serve
    from repro.models.layers import resolve_attn_backend, resolve_decode_backend

    def run(backend):
        t0 = time.perf_counter()
        eng = serve.main(_serve_argv(arch, backend, requests=requests,
                                     max_prompt=max_prompt, s_max=s_max,
                                     seed=seed))
        dt = time.perf_counter() - t0
        # every request generated one token, so the engine's logits are
        # those of the first (and only) decode step
        return eng, np.asarray(eng.last_logits, np.float32), dt

    eng, logits, t_first = run("auto")
    cfg = eng.cfg
    prompts = [eng.done[r].tokens for r in sorted(eng.done)]
    s_pad = max(-(-len(p) // eng.bucket) * eng.bucket for p in prompts)
    misses = eng.stats["compile_misses"]
    for p in prompts:  # the same requests again: every program is cached
        eng.submit(p, max_new_tokens=1)
    t0 = time.perf_counter()
    again = eng.run()
    t_steady = time.perf_counter() - t0
    rep = {
        "arch": cfg.name,
        "requests": len(prompts),
        "prompt_lens": [int(len(p)) for p in prompts],
        "tokens": [int(o[0]) for o in again],
        "decode_route": resolve_decode_backend(eng.ctx.decode_backend, cfg,
                                               eng.ctx),
        "prefill_route": resolve_attn_backend(eng.ctx.attn_backend, cfg,
                                              eng.ctx, S=s_pad,
                                              differentiable=False),
        "prefill_len": s_pad,
        "smoke_s": {"first_compile_and_run": t_first,
                    "steady_same_requests": t_steady},
        "compile_misses": [misses, eng.stats["compile_misses"]],
    }
    del eng, again
    gc.collect()
    ref, logits_ref, _ = run("ref")
    rep["ref_decode_route"] = resolve_decode_backend(ref.ctx.decode_backend,
                                                     cfg, ref.ctx)
    del ref
    gc.collect()
    scale = float(np.max(np.abs(logits_ref)))
    err = float(np.max(np.abs(logits - logits_ref)))
    rep.update(logit_max_abs=scale, logit_max_err=err,
               logit_rel_err=err / scale, logit_rtol=LOGIT_RTOL,
               argmax_agree=float(np.mean(logits.argmax(-1)
                                          == logits_ref.argmax(-1))),
               memory=_memory())
    assert rep["compile_misses"][0] == rep["compile_misses"][1], \
        "the same requests recompiled"
    assert np.all(np.isfinite(logits)), "non-finite logits"
    assert rep["ref_decode_route"] == "ref"
    assert err <= LOGIT_RTOL * scale, (err, scale)
    return rep


def four_chip_phase(arch: str = ARCH, *, clients: int = 4, rounds: int = 1,
                    T: int = 2, batch: int = 16, density: float = 1e-3,
                    seed: int = 0) -> dict:
    """The same training problem on a 2x2 FSDP mesh and unsharded: do the
    aggregated parameters and the GradIP trajectories match bit for bit?"""
    import jax
    import numpy as np

    from repro.launch import train

    def run(mesh):
        t0 = time.perf_counter()
        # both sides on the pytree ZO route, the only one a sharded round
        # takes: bit parity compares the sharding, not two ZO routes
        argv = _train_argv(arch, clients=clients, rounds=rounds, T=T,
                           batch=batch, density=density, seed=seed, mesh=mesh)
        server = train.main(argv + ["--zo-backend", "ref"])
        out = ([np.asarray(l) for l in jax.tree.leaves(server.params)],
               [np.asarray(t) for t in server.vp_trajectories],
               server.round_seconds, time.perf_counter() - t0,
               sorted(server.early_stopped))
        del server
        gc.collect()
        return out

    p_mesh, g_mesh, r_mesh, w_mesh, f_mesh = run("2x2")
    p_one, g_one, r_one, w_one, f_one = run(None)
    diff = [i for i, (a, b) in enumerate(zip(p_mesh, p_one))
            if not np.array_equal(a, b)]
    rep = {
        "arch": _cfg(arch).name,
        "mesh": "2x2 fsdp",
        "params_bitmatch": not diff and len(p_mesh) == len(p_one),
        "params_leaves_differing": len(diff),
        "params_max_abs_diff": max(
            (float(np.max(np.abs(p_mesh[i].astype(np.float32)
                                 - p_one[i].astype(np.float32))))
             for i in diff), default=0.0),
        "gradip_bitmatch": all(np.array_equal(a, b)
                               for a, b in zip(g_mesh, g_one)),
        "gradip_max_abs_diff": max(float(np.max(np.abs(a - b)))
                                   for a, b in zip(g_mesh, g_one)),
        "vp_flagged": {"mesh": f_mesh, "single": f_one},
        "smoke_round_s": {"mesh": r_mesh, "single": r_one},
        "smoke_wall_s": {"mesh": w_mesh, "single": w_one},
        "memory": _memory(),
    }
    assert rep["params_bitmatch"] and rep["gradip_bitmatch"], rep
    return rep


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 sharded round and the unsharded "
                         "round it is compared with")
    a = ap.parse_args(argv)
    n_chips = 4 if a.four_chips else 1
    device = device_phase(n_chips)
    _emit("device", **device)
    from repro.launch.compile_cache import enable_compile_cache
    _emit("compile_cache", dir=enable_compile_cache())
    if a.four_chips:
        _emit("four_chips", **four_chip_phase())
    else:
        _emit("kernels", **kernel_phase())
        _emit("train", **train_phase())
        gc.collect()
        _emit("serve", **serve_phase())
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
