"""Theorem 2.1 validation: the steady-state error floor grows with T.

Federation of heterogeneous quadratics: client k minimizes
f_k(w) = 0.5 (w - t_k)^T H_k (w - t_k) with per-client diagonal curvature
H_k and spread targets t_k.  With T > 1 local steps per round, averaging
the clients' T-step maps has a fixed point that is *biased away* from the
global optimum w* = (sum H_k)^-1 sum H_k t_k — the classic Non-IID client
drift the paper's steady-state term O(T/(2+u)) captures; T = 1 removes
the bias (only the ZO variance floor remains).

Note a subtlety this benchmark is built around: with *homogeneous*
curvature (H_k = I) the averaged local maps have fixed point exactly w*
for every T — heterogeneous curvature is what makes local steps drift.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.core import DenseSpace, round_keys
from repro.core.fl_step import make_fl_round_step


def run(quick: bool = True, seed: int = 0, d: int = 32, K: int = 8,
        lr: float = 2e-2, spread: float = 3.0) -> dict:
    Ts = [1, 5, 20] if quick else [1, 2, 5, 10, 20, 50]
    total_steps = 4000 if quick else 12000
    tail_frac = 0.25

    k1, k2 = jax.random.split(jax.random.key(seed))
    targets = spread * jax.random.normal(k1, (K, d))          # client optima
    # log-uniform per-client diagonal curvature in [0.2, 2.0]
    H = jnp.exp(jax.random.uniform(k2, (K, d),
                                   minval=jnp.log(0.2), maxval=jnp.log(2.0)))
    w_star = (H * targets).sum(0) / H.sum(0)

    def global_loss(w):
        return float(0.5 * jnp.mean(jnp.sum(H * (w - targets) ** 2, -1)))

    f_star = global_loss(w_star)
    params = {"w": jnp.zeros((d,))}
    space = DenseSpace(params)

    def loss(p, b):  # b carries the client's (t_k, h_k) row
        return 0.5 * jnp.sum(b["h"] * (p["w"] - b["t"]) ** 2)

    rows = []
    for T in Ts:
        rounds = total_steps // T
        step = jax.jit(make_fl_round_step(loss, space, eps=1e-4, lr=lr))
        p = params
        tail = []
        for r in range(rounds):
            keys = round_keys(seed, r, T)
            batches = {"t": jnp.broadcast_to(targets[:, None, :], (K, T, d)),
                       "h": jnp.broadcast_to(H[:, None, :], (K, T, d))}
            p, _ = step(p, keys, batches)
            if r >= int(rounds * (1 - tail_frac)):
                tail.append(global_loss(p["w"] if isinstance(p, dict)
                                        else p) - f_star)
        floor = float(np.mean(tail))
        rows.append(dict(T=T, rounds=rounds, floor=floor))
        print(f"  T={T:3d} rounds={rounds:5d} steady-state excess loss "
              f"= {floor:.5f}")
    floors = [r["floor"] for r in rows]
    monotone = all(floors[i] <= floors[i + 1] * 1.1
                   for i in range(len(floors) - 1))
    return {"table": "error_floor", "rows": rows, "f_star": f_star,
            "claim_floor_grows_with_T": bool(monotone
                                             and floors[-1] > 1.5 * floors[0])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    res = run(quick=not a.full, seed=a.seed)
    print("saved:", C.save_result("error_floor", res))


if __name__ == "__main__":
    main()
