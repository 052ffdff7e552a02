"""Kernel microbench: interpret-mode correctness + wall timings for the
GradIP reduction and flash-decode Pallas kernels over a shape sweep,
against the ref.py jnp oracles.

Timings on CPU interpret mode are NOT TPU performance — they validate the
kernel bodies; the roofline analysis (launch/roofline.py) covers perf.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from benchmarks import common as C
from repro.kernels import ref
from repro.kernels.ops import flash_decode, gradip_flat


def _t(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps


def run(quick: bool = True, seed: int = 0) -> dict:
    key = jax.random.key(seed)
    rows = []

    sizes = [1024, 65_536] if quick else [1024, 65_536, 1_048_576]
    for n in sizes:
        k1, k2, k3, key = jax.random.split(key, 4)
        w = jax.random.normal(k1, (n,), jnp.float32)
        z = jax.random.normal(k2, (n,), jnp.float32)

        g = gradip_flat(w, z, 1.7)
        rg = ref.gradip_reduce_ref(w, z, 1.7)
        err = float(jnp.abs(g - rg) / (jnp.abs(rg) + 1e-9))
        dt = _t(gradip_flat, w, z, 1.7)
        rows.append(dict(kernel="gradip_reduce", n=n, max_err=err,
                         ms=dt * 1e3, ok=err < 1e-4))

    shapes = ([(2, 2, 4, 64, 1024)] if quick
              else [(2, 2, 4, 64, 1024), (4, 8, 4, 128, 4096)])
    for (B, KVH, G, dh, S) in shapes:
        k1, k2, k3, key = jax.random.split(key, 4)
        q = jax.random.normal(k1, (B, KVH, G, dh), jnp.float32)
        kk = jax.random.normal(k2, (B, S, KVH, dh), jnp.float32)
        vv = jax.random.normal(k3, (B, S, KVH, dh), jnp.float32)
        length = S * 3 // 4
        o = flash_decode(q, kk, vv, length)
        r = ref.decode_attention_ref(q, kk, vv, length)
        err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                    - r.astype(jnp.float32))))
        dt = _t(flash_decode, q, kk, vv, length)
        rows.append(dict(kernel="flash_decode", n=f"B{B}S{S}", max_err=err,
                         ms=dt * 1e3, ok=err < 2e-2))

    for r in rows:
        print(f"  {r['kernel']:16s} n={r['n']!s:10s} err={r['max_err']:.2e} "
              f"{r['ms']:8.1f}ms {'ok' if r['ok'] else 'FAIL'}")

    return {"table": "microbench", "rows": rows,
            "all_ok": all(r["ok"] for r in rows)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    res = run(quick=not a.full, seed=a.seed)
    print("saved:", C.save_result("microbench", res))


if __name__ == "__main__":
    main()
