"""Trainable-parameter spaces for sparse zeroth-order optimization.

A *space* is the subset of coordinates that ZO perturbs and updates.  It maps
a flat value vector ``v in R^n`` into the parameter pytree:

* :class:`MaskedSpace` — MEERKAT: ``n = u * d`` sparse coordinates given by
  per-leaf flat indices (paper Eq. 1: ``z (.) m`` — we sample z only at the
  masked coordinates, mathematically identical, O(n) memory).
* :class:`DenseSpace`  — Full-FedZO: all parameters.
* :class:`LoRASpace`   — LoRA-FedZO: all ``lora_*`` adapter leaves.

All operations are jittable; index trees can be abstract for the dry-run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _leaves_with_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), l) for p, l in flat]


class MaskedSpace:
    """Sparse coordinate space from per-leaf flat index arrays.

    ``idx_tree`` has the same treedef as ``params``; each leaf is an int32
    array of distinct flat indices into the (raveled) parameter leaf, in
    any order (the order fixes which entry of a value vector lands where).
    Leaves with no selected coordinates hold an empty array.
    """

    def __init__(self, idx_tree):
        self.idx_tree = idx_tree
        leaves = jax.tree_util.tree_leaves(idx_tree)
        self.sizes = [int(l.shape[0]) for l in leaves]
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)]).astype(int)
        self.n = int(self.offsets[-1])

    def sample_z(self, key):
        return jax.random.normal(key, (self.n,), jnp.float32)

    def _segments(self, vec):
        return [vec[self.offsets[i]:self.offsets[i + 1]]
                for i in range(len(self.sizes))]

    def add(self, params, vec):
        """params + scatter(vec) at the masked coordinates.

        Uses N-D scatter indices (``unravel_index`` of the stored flat
        indices) rather than reshaping the leaf to 1-D: a flat reshape is not
        representable for tensor-parallel shardings, so GSPMD would
        all-gather the weight; the N-D scatter keeps the operand sharded and
        only replicates the (tiny) index/update vectors."""
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        i_leaves = jax.tree_util.tree_leaves(self.idx_tree)
        segs = self._segments(vec)
        out = []
        for p, idx, s in zip(p_leaves, i_leaves, segs):
            if idx.shape[0] == 0:
                out.append(p)
                continue
            nd = jnp.unravel_index(idx, p.shape)
            out.append(p.at[nd].add(s.astype(p.dtype), mode="drop"))
        return jax.tree_util.tree_unflatten(treedef, out)

    def slice(self, tree):
        """Restrict a pytree (e.g. a gradient) to the masked coords -> [n]."""
        t_leaves = jax.tree_util.tree_leaves(tree)
        i_leaves = jax.tree_util.tree_leaves(self.idx_tree)
        segs = [l[jnp.unravel_index(idx, l.shape)].astype(jnp.float32)
                for l, idx in zip(t_leaves, i_leaves)]
        return jnp.concatenate(segs) if segs else jnp.zeros((0,), jnp.float32)

    @functools.cached_property
    def sorted_unique(self) -> bool:
        """Whether every leaf's indices are strictly increasing (so sorted
        and unique), checked once on the concrete index leaves.  A traced
        index tree (the dry-run's abstract masks) never qualifies.  Only
        then may a scatter at them declare ``indices_are_sorted`` /
        ``unique_indices``: the index contract allows any order, and a
        false declaration gives wrong numbers."""
        leaves = jax.tree_util.tree_leaves(self.idx_tree)
        return not any(isinstance(i, jax.core.Tracer) for i in leaves) and \
            all(bool(np.all(np.diff(np.asarray(i)) > 0)) for i in leaves)


class DenseSpace:
    """All parameters, flattened (Full-FedZO)."""

    def __init__(self, template):
        leaves = jax.tree_util.tree_leaves(template)
        self.template = template
        self.sizes = [int(np.prod(l.shape)) for l in leaves]
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)]).astype(int)
        self.n = int(self.offsets[-1])

    def sample_z(self, key):
        return jax.random.normal(key, (self.n,), jnp.float32)

    def add(self, params, vec):
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        out = []
        for i, p in enumerate(p_leaves):
            s = vec[self.offsets[i]:self.offsets[i + 1]]
            out.append(p + s.reshape(p.shape).astype(p.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    def slice(self, tree):
        leaves = jax.tree_util.tree_leaves(tree)
        return jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                                for l in leaves])


class LoRASpace:
    """Only ``lora_*`` adapter leaves (dense within the adapters)."""

    def __init__(self, template):
        self._is_lora = [("lora_" in path)
                         for path, _ in _leaves_with_paths(template)]
        leaves = jax.tree_util.tree_leaves(template)
        self.sizes = [int(np.prod(l.shape)) if m else 0
                      for l, m in zip(leaves, self._is_lora)]
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)]).astype(int)
        self.n = int(self.offsets[-1])
        if self.n == 0:
            raise ValueError("no lora_* leaves found; set cfg.lora_rank > 0")

    def sample_z(self, key):
        return jax.random.normal(key, (self.n,), jnp.float32)

    def add(self, params, vec):
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        out = []
        for i, (p, m) in enumerate(zip(p_leaves, self._is_lora)):
            if not m:
                out.append(p)
                continue
            s = vec[self.offsets[i]:self.offsets[i + 1]]
            out.append(p + s.reshape(p.shape).astype(p.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    def slice(self, tree):
        leaves = jax.tree_util.tree_leaves(tree)
        segs = [l.reshape(-1).astype(jnp.float32)
                for l, m in zip(leaves, self._is_lora) if m]
        return jnp.concatenate(segs)
