"""Production federated-ZO train steps (what the dry-run lowers).

On the TPU mesh, FL clients are the (pod, data) shards.  With the shared
per-step seeds of Alg. 2/3, every client perturbs with the *same* z, so the
high-frequency (T=1) MEERKAT step is exactly:

    z  = N(0, I_n)                       (n = sparse coords, same everywhere)
    f+ = per-client loss at w + eps*z    (pure data-parallel forward)
    f- = per-client loss at w - eps*z
    g_k = (f+_k - f-_k) / 2 eps          (K scalars)
    w' = w - lr * mean_k(g_k) * z        (one sparse scatter)

The only cross-client collective is the scalar mean — the paper's 1000x
communication saving, visible structurally in the lowered HLO.  Every
perturb is a sparse scatter at the space's coordinates.

``make_fl_round_step`` is the T>1 variant (clients' deltas diverge within a
round; used by simulations and small-scale runs).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.zo import make_local_run


def _masked_mean(g_clients, report_mask):
    """Survivor/cohort mean of the per-client scalars: ``None`` (and an
    all-ones mask) is the plain mean; a 0/1 mask excludes clients as a
    *runtime operand* — one compiled program for every fault pattern and
    every sampled cohort."""
    if report_mask is None:
        return jnp.mean(g_clients)
    m = report_mask.astype(g_clients.dtype)
    return jnp.sum(g_clients * m) / jnp.maximum(jnp.sum(m), 1.0)


def make_fl_train_step(per_example_loss: Callable, space, *, eps: float,
                       lr: float, n_clients: int, constrain_params=None,
                       quantize=None):
    """T=1 high-frequency MEERKAT step (Alg. 3). Returns jittable fn
    (params, key, batch) -> (params', g_clients [K], metrics).

    ``quantize`` (:class:`repro.core.quantize.QuantSpec`) rounds each
    client's scalar to the uplink wire grid before the collective — the
    compiled-path form of the fleet uplink codec: the aggregated g is
    the mean of exactly the values the server dequantizes.

    ``constrain_params`` re-applies the parameter sharding after each sparse
    scatter — the flat-index scatter otherwise erases GSPMD's weight
    shardings and replicates all downstream matmuls (see DESIGN.md §perf).

    The optional trailing ``report_mask`` ([K] 0/1) is the compiled-path
    dropout model: clients whose upload was lost are excluded from the
    scalar collective — ``g = sum(mask * g_k) / max(1, sum(mask))`` — so
    the step aggregates over survivors without recompiling per fault
    pattern (the mask is a runtime operand).  ``None`` (and an all-ones
    mask) is exactly the fault-free mean."""
    cp = constrain_params or (lambda p: p)

    def step(params, key, batch, report_mask=None):
        z = space.sample_z(key)
        w_plus = cp(space.add(params, eps * z))
        l_plus = per_example_loss(w_plus, batch)           # [B_global]
        w_minus = cp(space.add(w_plus, (-2.0 * eps) * z))  # in-place chain
        l_minus = per_example_loss(w_minus, batch)
        g_clients = (l_plus - l_minus).reshape(n_clients, -1).mean(-1) \
            / (2.0 * eps)
        if quantize is not None:
            g_clients = quantize.apply(g_clients, key)
        g = _masked_mean(g_clients, report_mask)          # scalar collective
        new_params = cp(space.add(w_minus, (eps - lr * g) * z))
        metrics = {"loss": jnp.mean(l_plus + l_minus) / 2.0, "g": g}
        return new_params, g_clients, metrics

    return step


def make_fl_train_loop(per_example_loss: Callable, space, *, eps: float,
                       lr: float, n_clients: int, n_steps: int,
                       constrain_params=None, quantize=None):
    """``n_steps`` T=1 high-frequency MEERKAT steps in one jitted scan —
    the compiled training burst (no host round-trip per step).

    Returns jittable (params, key, batches[, report_masks]) -> (params',
    g_clients [n_steps, K], metrics of the last step), with batches
    carrying a leading [n_steps, ...] axis.  The scan body is
    :func:`make_fl_train_step`'s step at the step keys
    ``jax.random.split(key, n_steps)``; ``constrain_params`` and
    ``quantize`` mean what they mean there.

    The optional trailing ``report_masks`` ([n_steps, K] 0/1) is the
    per-step survivor/cohort mask, a *runtime operand* scanned alongside
    the batches: sampled cohorts and dropout patterns change per step
    without recompiling."""
    step = make_fl_train_step(per_example_loss, space, eps=eps, lr=lr,
                              n_clients=n_clients,
                              constrain_params=constrain_params,
                              quantize=quantize)

    def loop(params, key, batches, report_masks=None):
        def one(p, inp):
            k, b, mask = inp
            p, g_cl, metrics = step(p, k, b, mask)
            return p, (g_cl, metrics)

        keys = jax.random.split(key, n_steps)
        p_T, (gs, metrics) = jax.lax.scan(one, params,
                                          (keys, batches, report_masks))
        return p_T, gs, jax.tree.map(lambda m: m[-1], metrics)

    return loop


def make_fl_round_step(loss_fn: Callable, space, *, eps: float, lr: float):
    """Full MEERKAT round with T local steps per client (Alg. 2).

    batches: pytree with leading [K, T, b, ...]; keys: [T] (shared across
    clients).  Returns (params', gs [K, T]).  Each client runs
    :func:`repro.core.zo.make_local_run`'s loop under ``jax.lax.map``, as
    the server's group program does; the mean of their deltas is applied
    with ``space.add``."""
    run = make_local_run(loss_fn, space, eps, lr)

    def round_step(params, keys, batches):
        zeros = jnp.zeros((space.n,), jnp.float32)
        deltas, gs = jax.lax.map(lambda b: run(params, keys, b, zeros),
                                 batches)
        agg = jnp.mean(deltas, axis=0)                    # [n] sparse collective
        return space.add(params, agg), gs

    return round_step
