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
  semantics; required for sharded weights and odd layouts).  Its T-step
  loop over a :class:`MaskedSpace` at one direction perturbs in place
  (:func:`_run_ref_inplace`), bit for bit the same as ``space.add``.
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
import numpy as np

from repro import obs
from repro.core.dispatch import get_backing, resolve_backend
from repro.core.spaces import MaskedSpace
from repro.kernels import block_place
from repro.kernels.ops import (_default_interpret, zo_dual_perturb_flat,
                               zo_fused_update_flat)


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


# The TPU's default layout stores each (8, 128) tile of a weight's two minor
# dims contiguously, and its scatter addresses a 1-D buffer.  A leaf viewed
# as (..., R/8, C/128, 8, 128) reads that memory in order, so the view, its
# 1-D flattening and the way back are bitcasts there, where a scatter at
# N-D indices of the leaf's own shape relays the whole leaf out to 1-D and
# back.
_TILE_R, _TILE_C = 8, 128


def _tileable(shape) -> bool:
    return (len(shape) >= 2 and shape[-2] % _TILE_R == 0
            and shape[-1] % _TILE_C == 0)


def _tile_perm(nlead: int):
    """(..., a, r, b, c) <-> (..., a, b, r, c): its own inverse."""
    return (*range(nlead), nlead, nlead + 2, nlead + 1, nlead + 3)


def _to_tiles(w):
    *lead, r, c = w.shape
    t = w.reshape(*lead, r // _TILE_R, _TILE_R, c // _TILE_C, _TILE_C)
    return t.transpose(_tile_perm(len(lead)))


def _from_tiles(t):
    *lead, a, b, r, c = t.shape
    return t.transpose(_tile_perm(len(lead))).reshape(*lead, a * r, b * c)


def _tile_positions(idx: np.ndarray, shape) -> np.ndarray:
    """Row-major flat indices of a tileable leaf -> their positions in the
    1-D flattening of its tile view."""
    R, C = shape[-2], shape[-1]
    lead, rc = np.divmod(idx.astype(np.int64), R * C)
    r, c = np.divmod(rc, C)
    return (lead * R * C + r // _TILE_R * _TILE_R * C + c // _TILE_C
            * _TILE_R * _TILE_C + r % _TILE_R * _TILE_C + c % _TILE_C)


def _run_ref_inplace(loss_fn, params, space, backing, keys, batches, delta0,
                     eps, lr, quantize=None, sharded: bool = False):
    """The ref route's T-step loop at one direction, perturbing in place.

    The leaves that hold mask coordinates are copied once into a working
    set that the step loop carries, and their values at the coordinates,
    ``w0``, are gathered once.  Each step scatter-*sets*
    ``w0 + (delta +- eps z)`` there, in the leaf dtype: every value written
    derives from ``w0``, so ``w+``/``w-`` equal ``space.add(params,
    delta +- eps z)`` bit for bit, and the next step's ``w+`` overwrites the
    last ``w-`` (no restore).  ``params`` stays loop-invariant and unread
    by the scatters, so XLA updates the carried leaves in place rather
    than copying every touched leaf twice a step.

    Where the backing has checked the concrete indices sorted and unique,
    the scatters declare so (XLA then sorts no indices per step), and
    unless ``sharded`` each tileable leaf is carried in its tile view and
    scattered through that view's 1-D flattening, at its coordinates'
    positions there, sorted: the carry stays in the forward's layout.
    Each step then brings ``delta +- eps z`` into that order with one sort
    keyed by the constant ranks (on the TPU a sort costs a fraction of a
    gather with the same permutation; the keys are distinct, and a stable
    sort would only add an operand and compile time); a permutation moves
    values and changes no bits, and ``delta`` stays in the space's own
    order.  GSPMD-sharded leaves (``sharded``), whose 1-D view is not
    representable, are scattered at N-D indices in their own shape, as
    ``MaskedSpace.add`` does, and stay sharded.

    A tiled leaf that holds many coordinates is placed block by block
    instead (``kernels/block_place.py``): a Pallas kernel rewrites, in
    place, only the ``[bm, 128]`` row blocks of the tile view that hold
    coordinates, and writes the bits the scatter would.  A leaf takes it
    where the program is compiled for the TPU and the kernel's cost model,
    from tables built once here from the concrete positions, beats a
    point scatter's (``block_place.wins``); every other leaf, and every
    run off the TPU, keeps the point scatter.  The counters
    ``zo.perturb.block_coords`` and ``zo.perturb.point_coords`` record the
    split each time the loop is traced."""
    obs.count("zo.perturb_inplace")
    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    i_leaves = jax.tree_util.tree_leaves(space.idx_tree)
    live = [k for k, i in enumerate(i_leaves) if i.shape[0]]
    exact = backing.sorted_unique
    flags = (dict(indices_are_sorted=True, unique_indices=True)
             if exact else {})
    tiled = {k for k in live
             if not sharded and exact and _tileable(p_leaves[k].shape)}
    # where each live leaf's coordinates lie in its working form, and the
    # order of the space's [n] vectors in which the scatters take them
    ix, order, blocks = {}, [], {}
    for k in live:
        off = int(space.offsets[k])
        if k in tiled:
            pos = _tile_positions(np.asarray(i_leaves[k]), p_leaves[k].shape)
            o = np.argsort(pos, kind="stable")
            ix[k] = jnp.asarray(pos[o], jnp.int32)
            order.append(off + o)
            if not _default_interpret():
                plan = block_place.plan(pos[o], p_leaves[k].size // _TILE_C)
                if block_place.wins(plan, p_leaves[k].dtype):
                    blocks[k] = plan
        else:
            ix[k] = jnp.unravel_index(i_leaves[k], p_leaves[k].shape)
            order.append(off + np.arange(space.sizes[k]))
    # each coordinate's place in that order, the key that sorts into it
    rank = (jnp.asarray(np.argsort(np.concatenate(order)), jnp.int32)
            if tiled else None)
    n_block = sum(p.n for p in blocks.values())
    obs.count("zo.perturb.block_coords", n_block)
    obs.count("zo.perturb.point_coords", space.n - n_block)

    def flat(k, w):
        return w.reshape(-1) if k in tiled else w

    with jax.named_scope("zo.perturb"):
        work0 = tuple(_to_tiles(p_leaves[k]) if k in tiled else p_leaves[k]
                      for k in live)
        w0 = [flat(k, w)[ix[k]] for k, w in zip(live, work0)]

    def perturbed(work, vec):
        segs = space._segments(vec)
        out = []
        for k, w, b in zip(live, work, w0):
            v = b + segs[k].astype(w.dtype)
            if k in blocks:
                new = block_place.place(w.reshape(-1, _TILE_C), v, blocks[k])
            else:
                new = flat(k, w).at[ix[k]].set(v, mode="drop", **flags)
            out.append(new.reshape(w.shape))
        return tuple(out)

    def tree(work):
        leaves = list(p_leaves)
        for k, w in zip(live, work):
            leaves[k] = _from_tiles(w) if k in tiled else w
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def step(carry, inp):
        delta, work = carry
        key, batch = inp
        with jax.named_scope("zo.sample"):
            z = space.sample_z(key)
        # each barrier orders a forward's reads of the working leaves
        # before the next scatter into them; unordered, XLA copies the
        # leaves rather than write where a read may still be pending
        with jax.named_scope("zo.perturb"):
            vp, vm = delta + eps * z, delta - eps * z
            if rank is not None:
                vp, vm = jax.lax.sort((rank, vp, vm), num_keys=1,
                                      is_stable=False)[1:]
            work = perturbed(work, vp)
        with jax.named_scope("zo.forward"):
            lp, work = jax.lax.optimization_barrier(
                (loss_fn(tree(work), batch), work))
        with jax.named_scope("zo.perturb"):
            work = perturbed(work, vm)
        with jax.named_scope("zo.forward"):
            lm, work = jax.lax.optimization_barrier(
                (loss_fn(tree(work), batch), work))
        with jax.named_scope("zo.update"):
            g = _maybe_quantize((lp - lm) / (2.0 * eps), key, quantize)
            return (delta - lr * g * z, work), g

    (delta_T, _), gs = jax.lax.scan(step, (delta0, work0), (keys, batches))
    return delta_T, gs


def make_local_run(loss_fn: Callable, space, eps: float, lr: float,
                   n_dirs: int = 1, backend: Optional[str] = None,
                   sharded: bool = False,
                   quantize=None):
    """Jittable T-step client loop.

    batches: pytree with leading [T, ...]; keys: [T] PRNG keys.
    Returns (delta_T [n], gs [T]) (gs: [T, K] when n_dirs > 1).
    ``sharded=True`` declares mesh-sharded parameters: ``backend="auto"``
    then takes the pytree route, whose N-D scatters keep the weight
    leaves sharded (DESIGN.md §9), and the in-place perturb scatters each
    leaf in its own shape.
    ``quantize`` (:class:`repro.core.quantize.QuantSpec`) turns on
    exact-replay uplink quantization: each step's g is rounded to the
    wire grid before it is applied *and* before it is returned, so the
    trajectory is bit-reconstructible from the quantized upload.

    On the pallas backend the flat parameter vector is built ONCE outside
    the scan and the scan carries the *dense* flat delta, so every local
    step is exactly one fused dual-perturb pass plus one fused update pass
    over HBM — no per-step pytree scatter chain.  On the ref backend a
    :class:`MaskedSpace` at ``n_dirs == 1`` perturbs a carried working copy
    of its touched leaves in place (:func:`_run_ref_inplace`); ``n_dirs >
    1`` keeps the vmapped ``space.add`` step, whose mean's summation order
    fixes its bits."""

    def run(params, keys, batches, delta0):
        backing = get_backing(space, params)
        if resolve_backend(backend, backing, sharded=sharded) == "ref":
            if n_dirs == 1 and isinstance(space, MaskedSpace):
                return _run_ref_inplace(loss_fn, params, space, backing,
                                        keys, batches, delta0, eps, lr,
                                        quantize, sharded)

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
