"""Transferable sparse-mask selection (paper §2.1).

MEERKAT's mask marks the top-``u`` fraction of parameters by *average squared
gradient on pre-training data* (the C4 proxy corpus here).  Baselines:
weight-magnitude, random.  Masks are static for the whole FL run and
transferable across downstream tasks.
"""
from __future__ import annotations

import functools
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.spaces import MaskedSpace


def _n_select(total: int, density: float) -> int:
    return max(1, int(round(total * density)))


@functools.partial(jax.jit, donate_argnums=0)
def _accumulate_square(acc, g):
    return jax.tree.map(lambda a, gg: a + jnp.square(gg.astype(jnp.float32)),
                        acc, g)


@functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
def _divide(acc, n: int):
    return jax.tree.map(lambda a: a / n, acc)


def sensitivity_scores(loss_fn: Callable, params, batches: Iterable):
    """Average squared per-parameter gradient over pre-training batches.

    The f32 accumulator is updated in place (donated), so at published
    width the device holds the weights, one gradient and one accumulator
    (Qwen2-1.5B in bf16: 3.1 + 3.1 + 6.2 GB), never two accumulators.
    (Squaring inside the gradient program instead needs more: XLA then
    keeps f32 gradient temporaries beside the accumulator.)"""
    from repro.models.layers import differentiable_attn
    grad_fn = jax.jit(jax.grad(loss_fn))
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    n = 0
    for batch in batches:
        with differentiable_attn():  # grad-appropriate attn route
            g = grad_fn(params, batch)
        acc = _accumulate_square(acc, g)
        del g
        n += 1
    return _divide(acc, max(n, 1))


def _global_topk_indices(score_tree, density: float):
    """Per-leaf int32 flat-index arrays of the global top-k scores.

    Exactly k indices: every score above the k-th largest, then the
    lowest-index ties at it.  The selection runs on the host over one
    f32 copy of the scores plus one partition buffer (no int64 index
    array of the full model).  Spans: ``mask.to_host`` (the copy, which
    waits for the scores), ``mask.topk`` (the selection) and
    ``mask.to_device`` (the index leaves)."""
    leaves, treedef = jax.tree_util.tree_flatten(score_tree)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    total = int(offsets[-1])
    k = _n_select(total, density)
    with obs.span("mask.to_host"):
        flat = np.empty((total,), np.float32)
        for l, o, s in zip(leaves, offsets[:-1], sizes):
            flat[o:o + s] = np.asarray(l, np.float32).ravel()
    with obs.span("mask.topk"):
        kth = np.partition(flat, total - k)[total - k]
        top = np.flatnonzero(flat > kth)
        ties = np.flatnonzero(flat == kth)[:k - top.size]
        top = np.sort(np.concatenate([top, ties]))
        del flat
        bounds = [np.searchsorted(top, [offsets[i], offsets[i + 1]])
                  for i in range(len(leaves))]
    with obs.span("mask.to_device"):
        idx_leaves = [jnp.asarray(top[lo:hi] - offsets[i], jnp.int32)
                      for i, (lo, hi) in enumerate(bounds)]
    return jax.tree_util.tree_unflatten(treedef, idx_leaves)


def sensitivity_mask(loss_fn, params, pretrain_batches, density: float
                     ) -> MaskedSpace:
    """MEERKAT's mask: global top-u by avg squared pre-training gradient.
    ``mask.scores`` spans the dispatch of the gradient accumulation; the
    counter ``mask.coords.<leaf>`` adds each leaf's chosen coordinates
    (``mask.coords.stack.p0.wq``), which sum to the mask's count."""
    with obs.span("mask.scores"):
        scores = sensitivity_scores(loss_fn, params, pretrain_batches)
    idx_tree = _global_topk_indices(scores, density)
    for path, ix in jax.tree_util.tree_flatten_with_path(idx_tree)[0]:
        obs.count("mask.coords." + jax.tree_util.keystr(
            path, simple=True, separator="."), int(ix.shape[0]))
    return MaskedSpace(idx_tree)


def magnitude_mask(params, density: float) -> MaskedSpace:
    """Weight-magnitude baseline: top-u by |w|."""
    scores = jax.tree.map(lambda p: jnp.abs(p.astype(jnp.float32)), params)
    return MaskedSpace(_global_topk_indices(scores, density))


def random_mask(params, density: float, seed: int = 0,
                balanced: bool = True) -> MaskedSpace:
    """Uniform random mask.  ``balanced`` selects round(n_i * u) coords per
    leaf (the shard-friendly layout used for the large-arch dry-runs)."""
    rng = np.random.default_rng(seed)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    sizes = [int(np.prod(np.asarray(l.shape))) for l in leaves]
    if balanced:
        idx_leaves = []
        for s in sizes:
            k = max(1, int(round(s * density)))
            idx_leaves.append(jnp.asarray(
                np.sort(rng.choice(s, size=min(k, s), replace=False)),
                jnp.int32))
    else:
        total = sum(sizes)
        k = _n_select(total, density)
        top = np.sort(rng.choice(total, size=k, replace=False))
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        idx_leaves = [jnp.asarray(
            top[(top >= offsets[i]) & (top < offsets[i + 1])] - offsets[i],
            jnp.int32) for i in range(len(leaves))]
    return MaskedSpace(jax.tree_util.tree_unflatten(treedef, idx_leaves))


def abstract_mask(abstract_params, density: float,
                  max_coords: int = 8_388_608):
    """Index-tree of ShapeDtypeStructs for the dry-run (no allocation).

    Density is clamped so the coordinate count stays <= ``max_coords``
    (the paper validates densities down to 5e-5, Table 7) — for
    trillion-parameter archs we dry-run at the smaller density.
    """
    leaves, treedef = jax.tree_util.tree_flatten(abstract_params)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    total = sum(sizes)
    eff_density = min(density, max_coords / total)
    shapes = [jax.ShapeDtypeStruct((max(1, int(s * eff_density)),), jnp.int32)
              for s in sizes]
    return jax.tree_util.tree_unflatten(treedef, shapes), eff_density


def concrete_balanced_mask_like(abstract_idx_tree, abstract_params, seed=0):
    """Concrete random indices matching an abstract mask (for smoke tests)."""
    rng = np.random.default_rng(seed)
    p_leaves = jax.tree_util.tree_leaves(abstract_params)
    i_leaves, treedef = jax.tree_util.tree_flatten(abstract_idx_tree)
    out = []
    for p, i in zip(p_leaves, i_leaves):
        size = int(np.prod(p.shape))
        k = min(int(i.shape[0]), size)
        out.append(jnp.asarray(
            np.sort(rng.choice(size, size=k, replace=False)), jnp.int32))
    return jax.tree_util.tree_unflatten(treedef, out)
