"""GradIP score (paper Definition 2.3) and trajectory computation.

GradIP_t = < grad_f_pretrain , grad_hat_k^t >  where grad_hat_k^t is the
ZO-reconstructed client gradient.  In sparse coordinates this is simply
``g_k^t * dot(gp[mask], z_t)`` — the server never materializes dense
gradients.

The inner reduction has two routes:

* ``backend="pallas"`` — the blocked Pallas reduction
  (``kernels/gradip_reduce.py`` via ``kernels/ops.gradip_flat``): ``gp``
  and each ``z_t`` stream once through a (R, 128)-tiled VMEM accumulator.
* ``backend="ref"``    — plain ``jnp.dot``; the only route for traced or
  mesh-sharded ``gp`` vectors (a pallas_call cannot consume a
  GSPMD-sharded operand, so the sharded server keeps GradIP on the
  replicated host copy — DESIGN.md §9).
* ``backend=None``/"auto" picks pallas for concrete single-device
  vectors, ref otherwise.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _resolve_gradip_backend(backend: Optional[str], gp_vec) -> str:
    """'auto'/None -> 'pallas' | 'ref' for a given [n] gp vector.

    Traced values (inside an outer jit) and mesh-committed sharded arrays
    take the jnp route; concrete single-device vectors take the kernel."""
    backend = backend or "auto"
    if backend in ("pallas", "ref"):
        return backend
    if backend != "auto":
        raise ValueError(f"gradip backend must be auto|pallas|ref, "
                         f"got {backend!r}")
    if isinstance(gp_vec, jax.core.Tracer):
        return "ref"
    try:
        sharded = len(gp_vec.sharding.device_set) > 1
    except AttributeError:  # numpy input
        sharded = False
    return "ref" if sharded else "pallas"


def gradip_trajectory(space, keys, gs, gp_vec,
                      backend: Optional[str] = None):
    """Per-step GradIP of one client's virtual path.

    Args:
      space: the sparse coordinate space (``sample_z`` regenerates each
        step's direction from the shared seed ladder).
      keys: [T] PRNG keys (the round's seed list).
      gs: [T] f32 projected-gradient scalars uploaded by the client
        (units: loss per unit step along z).
      gp_vec: [n] f32 pre-training gradient restricted to the space.
      backend: reduction route, see module docstring.

    Returns (gradip [T], grad_norm [T], cosine [T]) — all f32:
    ``gradip_t = g_t * <gp, z_t>``, ``grad_norm_t = |g_t| * ||z_t||``
    (the reconstructed ZO gradient's L2 norm), and the cosine similarity
    between the reconstructed gradient and ``gp``."""
    gp = gp_vec.astype(jnp.float32)
    gp_norm = jnp.linalg.norm(gp) + 1e-12
    be = _resolve_gradip_backend(backend, gp_vec)

    if be == "pallas":
        from repro.kernels.ops import gradip_flat

        def one(_, inp):
            key, g = inp
            z = space.sample_z(key)
            ip = gradip_flat(gp, z, g)
            gnorm = jnp.abs(g) * jnp.linalg.norm(z)
            cos = ip / (gp_norm * gnorm + 1e-12)
            return None, (ip, gnorm, cos)

        _, (ips, norms, coss) = jax.lax.scan(one, None, (keys, gs))
        return ips, norms, coss

    def one(key, g):
        z = space.sample_z(key)
        ip = g * jnp.dot(gp, z)
        gnorm = jnp.abs(g) * jnp.linalg.norm(z)
        cos = ip / (gp_norm * gnorm + 1e-12)
        return ip, gnorm, cos

    ips, norms, coss = jax.vmap(one)(keys, gs)
    return ips, norms, coss


def gradip_matrix(entries, T: Optional[int] = None):
    """Stack one client's per-round GradIP log into a dense matrix with
    explicit gaps.

    ``entries`` is ``FederatedZO.gradip_log[cid]`` — one [T_r] array per
    round the client reported, ``None`` for rounds it was dropped,
    straggling (until arrival), or **unsampled** (fleet-scale client
    sampling logs a gap for every client outside the round's cohort, so
    trajectory analyses see the participation structure instead of a
    silently shortened log).

    Returns ``(mat [R, T] f32, present [R] bool)``: gap rounds are NaN
    rows; shorter entries (e.g. an early-stopped client's T=1 rounds)
    are NaN-padded on the right.  ``T`` defaults to the longest present
    entry and must be given when the log is all gaps."""
    entries = list(entries)
    present = np.array([e is not None for e in entries], bool)
    lens = [int(np.asarray(e).reshape(-1).shape[0])
            for e in entries if e is not None]
    if T is None:
        if not lens:
            raise ValueError("gradip_matrix: all-gap log needs explicit T")
        T = max(lens)
    mat = np.full((len(entries), int(T)), np.nan, np.float32)
    for i, e in enumerate(entries):
        if e is not None:
            row = np.asarray(e, np.float32).reshape(-1)
            mat[i, :row.shape[0]] = row
    return mat, present


def pretrain_gradient_vec(loss_fn, params, space, batches):
    """Server-held pre-training gradient restricted to the space.

    Args:
      loss_fn: scalar LM loss ``(params, batch) -> f32``.
      params: parameter pytree (unsharded — the gradient is a first-order
        calibration pass run once, before any mesh placement).
      space: sparse coordinate space (``slice`` restricts the gradient).
      batches: iterable of C4-proxy batches.

    Returns the mean gradient over the batches at the space's
    coordinates: [n] f32."""
    from repro.models.layers import differentiable_attn
    grad_fn = jax.jit(jax.grad(loss_fn))
    acc = jnp.zeros((space.n,), jnp.float32)
    n = 0
    for b in batches:
        with differentiable_attn():  # grad-appropriate attn route
            acc = acc + space.slice(grad_fn(params, b))
        n += 1
    return acc / max(n, 1)
