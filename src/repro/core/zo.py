"""Sparse zeroth-order estimator (paper Eq. 1).

g = (f(w + eps*(z(.)m); B) - f(w - eps*(z(.)m); B)) / (2 eps)
grad_hat = g * (z (.) m)

We sample z only at the masked coordinates (space semantics), which is
mathematically identical to the dense ``z (.) m`` formulation.

Every entry point perturbs the weights through the space's sparse ``[n]``
vectors; nothing dense in the parameter count is built.  The T-step
client loop (:func:`make_local_run`) has two bodies:

* a :class:`MaskedSpace` at one direction — the server's path in every
  cell — perturbs a loop-carried working copy of the touched leaves in
  place (:func:`_run_inplace`): point scatters through each leaf's
  (8, 128) tile view, or, for a leaf that holds many coordinates under a
  TPU compile, the block-wise Pallas kernel of ``kernels/block_place.py``;
* ``n_dirs > 1`` and the other spaces scan :func:`local_step`, which
  perturbs with ``space.add``.

The two write the same bits wherever both apply.

Both name their phases with ``jax.named_scope`` — ``zo.sample`` (z),
``zo.perturb`` (the perturbed parameters), ``zo.forward`` (the two loss
evaluations) and ``zo.update`` (g and the delta update) — so each compiled
instruction's ``op_name`` metadata says which phase it belongs to.  Scopes
change metadata only, never numbers.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.spaces import MaskedSpace
from repro.kernels import block_place
from repro.kernels.ops import _default_interpret


def _maybe_quantize(g, key, quantize):
    """Exact-replay quantization hook (core/quantize.py): the client
    rounds each projected-gradient scalar to the wire grid *before*
    applying it, so the value it uploads (and the server dequantizes) is
    bit-identical to the value its local trajectory used.  The rounding
    noise key is the step/direction key folded with QUANT_FOLD — a
    stream disjoint from z sampling, derivable from the seed ladder."""
    if quantize is None:
        return g
    return quantize.apply(g, key)


def projected_gradient(loss_fn: Callable, params, space, delta, z, eps: float,
                       batch):
    """Scalar projected gradient g at (params + delta) along z."""
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


def local_step(loss_fn: Callable, params, space, delta, key, eps: float,
               lr: float, batch, n_dirs: int = 1, quantize=None):
    """One client-side ZO step on the sparse delta. Returns (delta', g).

    ``n_dirs > 1`` (beyond-paper) averages the estimator over K independent
    directions per step — K x the forwards for ~1/K x the estimator
    variance (Lemma B.7) while the upload grows only to K scalars per
    step; the virtual path stays reconstructible because the K direction
    keys derive from the shared step key (``reconstruct_delta`` accepts
    gs of shape [T, K]).  n_dirs=1 is exactly the paper's Eq. 1 step.

    ``quantize`` (a :class:`repro.core.quantize.QuantSpec`) rounds each
    g to the uplink wire grid before the update — exact-replay mode: the
    applied scalar equals the dequantized upload bit-for-bit.

    It perturbs through ``space.add``; at ``n_dirs > 1`` the summation
    order of the mean over the vmapped directions fixes its bits."""
    if n_dirs == 1:
        with jax.named_scope("zo.sample"):
            z = space.sample_z(key)
        g = projected_gradient(loss_fn, params, space, delta, z, eps, batch)
        with jax.named_scope("zo.update"):
            g = _maybe_quantize(g, key, quantize)
            return delta - lr * g * z, g

    def one(k):
        with jax.named_scope("zo.sample"):
            z = space.sample_z(k)
        g = projected_gradient(loss_fn, params, space, delta, z, eps, batch)
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


def _run_inplace(loss_fn, params, space, keys, batches, delta0, eps, lr,
                 quantize=None, sharded: bool = False):
    """The T-step loop of a :class:`MaskedSpace` at one direction,
    perturbing in place.

    The leaves that hold mask coordinates are copied once into a working
    set that the step loop carries, and their values at the coordinates,
    ``w0``, are gathered once.  Each step scatter-*sets*
    ``w0 + (delta +- eps z)`` there, in the leaf dtype: every value written
    derives from ``w0``, so ``w+``/``w-`` equal ``space.add(params,
    delta +- eps z)`` bit for bit, and the next step's ``w+`` overwrites the
    last ``w-`` (no restore).  ``params`` stays loop-invariant and unread
    by the scatters, so XLA updates the carried leaves in place rather
    than copying every touched leaf twice a step.

    Where the space has checked its concrete indices sorted and unique,
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
    exact = space.sorted_unique
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
                   n_dirs: int = 1, sharded: bool = False, quantize=None):
    """Jittable T-step client loop.

    batches: pytree with leading [T, ...]; keys: [T] PRNG keys.
    Returns (delta_T [n], gs [T]) (gs: [T, K] when n_dirs > 1).
    ``sharded=True`` declares GSPMD-sharded parameters: the in-place
    perturb then scatters each leaf at N-D indices in its own shape, which
    keeps the weight leaves sharded (DESIGN.md §9).
    ``quantize`` (:class:`repro.core.quantize.QuantSpec`) turns on
    exact-replay uplink quantization: each step's g is rounded to the
    wire grid before it is applied *and* before it is returned, so the
    trajectory is bit-reconstructible from the quantized upload.

    A :class:`MaskedSpace` at ``n_dirs == 1`` perturbs a carried working
    copy of its touched leaves in place (:func:`_run_inplace`); ``n_dirs >
    1`` and the other spaces scan :func:`local_step`."""

    def run(params, keys, batches, delta0):
        if n_dirs == 1 and isinstance(space, MaskedSpace):
            return _run_inplace(loss_fn, params, space, keys, batches,
                                delta0, eps, lr, quantize, sharded)

        def step(delta, inp):
            key, batch = inp
            return local_step(loss_fn, params, space, delta, key, eps, lr,
                              batch, n_dirs, quantize)

        return jax.lax.scan(step, delta0, (keys, batches))

    return run
