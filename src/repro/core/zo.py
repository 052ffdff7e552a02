"""Sparse zeroth-order estimator (paper Eq. 1).

g = (f(w + eps*(z(.)m); B) - f(w - eps*(z(.)m); B)) / (2 eps)
grad_hat = g * (z (.) m)

We sample z only at the masked coordinates (space semantics), which is
mathematically identical to the dense ``z (.) m`` formulation.

Every entry point dispatches between two execution routes (see
``core/dispatch.py``):

* ``backend="pallas"`` — the hot path.  Parameters live as one flat [N]
  vector; each perturb phase is a single fused
  :func:`repro.kernels.ops.zo_dual_perturb_flat` pass (one HBM read of
  (w, z, m) producing both perturbed copies) and each update a single
  :func:`repro.kernels.ops.zo_fused_update_flat` pass, instead of chained
  per-leaf pytree scatters.
* ``backend="ref"``    — the original ``space.add`` pytree route (reference
  semantics; required for sharded weights and odd layouts).
* ``backend=None``/"auto" picks pallas whenever the flat layout supports it.

Both routes name their phases with ``jax.named_scope`` — ``zo.sample``
(z and its dense scatter), ``zo.perturb`` (the perturbed parameters),
``zo.forward`` (the two loss evaluations) and ``zo.update`` (g and the
delta update) — so each compiled instruction's ``op_name`` metadata says
which phase it belongs to.  Scopes change metadata only, never numbers.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.dispatch import get_backing, resolve_backend
from repro.kernels.ops import zo_dual_perturb_flat, zo_fused_update_flat


def _maybe_quantize(g, key, quantize):
    """Exact-replay quantization hook (core/quantize.py): the client
    rounds each projected-gradient scalar to the wire grid *before*
    applying it, so the value it uploads (and the server dequantizes) is
    bit-identical to the value its local trajectory used.  The rounding
    noise key is the step/direction key folded with QUANT_FOLD — a
    stream disjoint from z sampling, derivable from the seed ladder.
    Identical in the ref and flat-kernel routes (backend bit-parity)."""
    if quantize is None:
        return g
    return quantize.apply(g, key)


def _dual_losses(loss_fn, backing, base_flat, z_flat, eps, batch):
    """Fused perturb + the two loss evaluations; returns (l+, l-).

    z_flat comes pre-masked from ``backing.expand`` (zero off the space
    coordinates), so the kernels run without the mask operand stream."""
    with jax.named_scope("zo.perturb"):
        w_plus, w_minus = zo_dual_perturb_flat(base_flat, z_flat, None, eps)
        p_plus = backing.unflatten(w_plus)
    with jax.named_scope("zo.forward"):
        lp = loss_fn(p_plus, batch)
    with jax.named_scope("zo.perturb"):
        p_minus = backing.unflatten(w_minus)
    with jax.named_scope("zo.forward"):
        return lp, loss_fn(p_minus, batch)


def _multi_dir_update(loss_fn, backing, space, base_flat, key, eps: float,
                      n_dirs: int, batch, quantize=None):
    """K-direction fused estimator at ``base_flat``: splits the step key
    into K direction keys (matching ``reconstruct_delta``'s [T, K] replay)
    and returns (mean_k g_k * z_k as a dense flat vector, gs [K]).

    Scanned with a running sum so peak dense memory stays one [n_pad]
    accumulator (not [K, n_pad]) and the loss graph compiles once."""

    def one(acc, k):
        with jax.named_scope("zo.sample"):
            z_flat = backing.expand(space.sample_z(k))
        lp, lm = _dual_losses(loss_fn, backing, base_flat, z_flat, eps,
                              batch)
        with jax.named_scope("zo.update"):
            g = _maybe_quantize((lp - lm) / (2.0 * eps), k, quantize)
            return acc + g * z_flat, g

    upd_sum, gs = jax.lax.scan(one, jnp.zeros((backing.n_pad,), jnp.float32),
                               jax.random.split(key, n_dirs))
    return upd_sum / n_dirs, gs


def projected_gradient(loss_fn: Callable, params, space, delta, z, eps: float,
                       batch, backend: Optional[str] = None,
                       sharded: bool = False):
    """Scalar projected gradient g at (params + delta) along z.

    ``sharded=True`` declares that ``params`` live sharded on a mesh, so
    ``backend="auto"`` resolves to the pytree route (the flat reshape is
    not GSPMD-representable; see core/dispatch.py)."""
    backing = get_backing(space, params)
    if resolve_backend(backend, backing, sharded=sharded) == "ref":
        with jax.named_scope("zo.perturb"):
            p_plus = space.add(params, delta + eps * z)
        with jax.named_scope("zo.forward"):
            lp = loss_fn(p_plus, batch)
        with jax.named_scope("zo.perturb"):
            p_minus = space.add(params, delta - eps * z)
        with jax.named_scope("zo.forward"):
            lm = loss_fn(p_minus, batch)
        with jax.named_scope("zo.update"):
            return (lp - lm) / (2.0 * eps)
    with jax.named_scope("zo.perturb"):
        base = backing.flatten(params) + backing.expand(delta)
    with jax.named_scope("zo.sample"):
        z_flat = backing.expand(z)
    lp, lm = _dual_losses(loss_fn, backing, base, z_flat, eps, batch)
    with jax.named_scope("zo.update"):
        return (lp - lm) / (2.0 * eps)


def local_step(loss_fn: Callable, params, space, delta, key, eps: float,
               lr: float, batch, n_dirs: int = 1,
               backend: Optional[str] = None, sharded: bool = False,
               quantize=None):
    """One client-side ZO step on the sparse delta. Returns (delta', g).

    ``n_dirs > 1`` (beyond-paper) averages the estimator over K independent
    directions per step — K x the forwards for ~1/K x the estimator
    variance (Lemma B.7) while the upload grows only to K scalars per
    step; the virtual path stays reconstructible because the K direction
    keys derive from the shared step key (``reconstruct_delta`` accepts
    gs of shape [T, K]).  n_dirs=1 is exactly the paper's Eq. 1 step.

    ``quantize`` (a :class:`repro.core.quantize.QuantSpec`) rounds each
    g to the uplink wire grid before the update — exact-replay mode: the
    applied scalar equals the dequantized upload bit-for-bit."""
    backing = get_backing(space, params)
    if resolve_backend(backend, backing, sharded=sharded) == "ref":
        return _local_step_ref(loss_fn, params, space, delta, key, eps, lr,
                               batch, n_dirs, quantize)

    with jax.named_scope("zo.perturb"):
        base = backing.flatten(params) + backing.expand(delta)
    if n_dirs == 1:
        with jax.named_scope("zo.sample"):
            z = space.sample_z(key)
            z_flat = backing.expand(z)
        lp, lm = _dual_losses(loss_fn, backing, base, z_flat, eps, batch)
        with jax.named_scope("zo.update"):
            g = _maybe_quantize((lp - lm) / (2.0 * eps), key, quantize)
            return delta - lr * g * z, g

    upd, gs = _multi_dir_update(loss_fn, backing, space, base, key, eps,
                                n_dirs, batch, quantize)
    with jax.named_scope("zo.update"):
        return delta - lr * backing.restrict(upd), gs


def _local_step_ref(loss_fn, params, space, delta, key, eps, lr, batch,
                    n_dirs, quantize=None):
    if n_dirs == 1:
        with jax.named_scope("zo.sample"):
            z = space.sample_z(key)
        g = projected_gradient(loss_fn, params, space, delta, z, eps, batch,
                               backend="ref")
        with jax.named_scope("zo.update"):
            g = _maybe_quantize(g, key, quantize)
            return delta - lr * g * z, g

    def one(k):
        with jax.named_scope("zo.sample"):
            z = space.sample_z(k)
        g = projected_gradient(loss_fn, params, space, delta, z, eps, batch,
                               backend="ref")
        with jax.named_scope("zo.update"):
            g = _maybe_quantize(g, k, quantize)
            return g * z, g

    with jax.named_scope("zo.sample"):
        keys = jax.random.split(key, n_dirs)
    gz, gs = jax.vmap(one)(keys)
    with jax.named_scope("zo.update"):
        return delta - lr * gz.mean(0), gs


def make_local_run(loss_fn: Callable, space, eps: float, lr: float,
                   n_dirs: int = 1, backend: Optional[str] = None,
                   sharded: bool = False,
                   quantize=None):
    """Jittable T-step client loop.

    batches: pytree with leading [T, ...]; keys: [T] PRNG keys.
    Returns (delta_T [n], gs [T]) (gs: [T, K] when n_dirs > 1).
    ``sharded=True`` declares mesh-sharded parameters: ``backend="auto"``
    then takes the pytree route, whose N-D scatters keep the weight
    leaves sharded (DESIGN.md §9).
    ``quantize`` (:class:`repro.core.quantize.QuantSpec`) turns on
    exact-replay uplink quantization: each step's g is rounded to the
    wire grid before it is applied *and* before it is returned, so the
    trajectory is bit-reconstructible from the quantized upload.

    On the pallas backend the flat parameter vector is built ONCE outside
    the scan and the scan carries the *dense* flat delta, so every local
    step is exactly one fused dual-perturb pass plus one fused update pass
    over HBM — no per-step pytree scatter chain."""

    def run(params, keys, batches, delta0):
        backing = get_backing(space, params)
        if resolve_backend(backend, backing, sharded=sharded) == "ref":
            def step(delta, inp):
                key, batch = inp
                delta, g = _local_step_ref(loss_fn, params, space, delta,
                                           key, eps, lr, batch, n_dirs,
                                           quantize)
                return delta, g

            return jax.lax.scan(step, delta0, (keys, batches))

        with jax.named_scope("zo.perturb"):
            w_flat = backing.flatten(params)
        # dense z buffer carried across the scan: the coordinate set is
        # static, so each step refreshes the sparse values in place
        # (scatter_into) instead of re-materializing n_pad zeros
        with jax.named_scope("zo.sample"):
            z0 = jnp.zeros((backing.n_pad,), jnp.float32)

        def step(carry, inp):
            delta_dense, z_buf = carry
            key, batch = inp
            with jax.named_scope("zo.perturb"):
                base = w_flat + delta_dense
            if n_dirs == 1:
                with jax.named_scope("zo.sample"):
                    z_flat = backing.scatter_into(z_buf, space.sample_z(key))
                lp, lm = _dual_losses(loss_fn, backing, base, z_flat, eps,
                                      batch)
                with jax.named_scope("zo.update"):
                    g = _maybe_quantize((lp - lm) / (2.0 * eps), key,
                                        quantize)
                    return (zo_fused_update_flat(delta_dense, z_flat, None,
                                                 -lr * g), z_flat), g
            upd, gs = _multi_dir_update(loss_fn, backing, space, base, key,
                                        eps, n_dirs, batch, quantize)
            with jax.named_scope("zo.update"):
                return (zo_fused_update_flat(delta_dense, upd, None, -lr),
                        z_buf), gs

        (delta_T, _), gs = jax.lax.scan(step, (backing.expand(delta0), z0),
                                        (keys, batches))
        return backing.restrict(delta_T), gs

    return run
