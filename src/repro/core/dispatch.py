"""Kernel-dispatch layer: flat-vector backing for the sparse-ZO hot path.

The MEERKAT inner loop perturbs and updates the parameter vector at every
step.  Written over pytrees (``space.add``), each phase is a chain of
per-leaf scatters — three full HBM round-trips per step.  The fused Pallas
kernels (``kernels/zo_update.py``) do each phase in a single pass, but they
operate on flat ``[N]`` vectors in the (R, 128) tile layout.

:class:`FlatBacking` bridges the two worlds for a (space, param-template)
pair.  It caches the static layout (leaf shapes / dtypes / offsets) plus the
dense 0/1 mask and the int32 global scatter indices that map the space's
``[n]`` sparse value vectors into the flat ``[N]`` coordinate system:

* ``flatten(params)``   pytree -> ``[N]`` (leaf-concatenation order)
* ``unflatten(flat)``   ``[N]`` -> pytree (casts back to each leaf dtype)
* ``expand(vec)``       ``[n]`` sparse values -> dense ``[N]`` f32
* ``restrict(flat)``    dense ``[N]`` -> ``[n]`` values at the space coords

Backend selection (``resolve_backend``):

* ``"pallas"`` — flat route through ``zo_dual_perturb_flat`` /
  ``zo_fused_update_flat``.  On TPU the kernels run compiled; on CPU (tests,
  simulations) they run in interpret mode (``kernels/ops.py`` flips
  automatically).
* ``"ref"``    — the original pytree ``space.add`` route (the reference
  semantics, and the only correct choice on the sharded production mesh:
  a flat reshape of a tensor-parallel weight is not representable for
  GSPMD, so the flat route would all-gather every weight — DESIGN.md §perf).
* ``"auto"``   — pallas when the layout supports it (uniform leaf dtype,
  N < 2**31 so int32 indices are exact, non-empty space) and the step is
  not sharding-constrained; ref otherwise.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.zo_update import LANE, SUB

_INT32_MAX = 2**31 - 1
_TILE = SUB * LANE  # (8, 128) sublane tile quantum of the fused kernels
BACKENDS = ("auto", "pallas", "ref")


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


class FlatBacking:
    """Flat [N] view of a space over a parameter template (see module doc)."""

    def __init__(self, space, template):
        self.space = space
        leaves, self.treedef = jax.tree_util.tree_flatten(template)
        if not leaves:
            raise ValueError("empty parameter template")
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [jnp.dtype(l.dtype) for l in leaves]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)]).astype(
            np.int64)
        self.n_flat = int(self.offsets[-1])
        # flat vectors are carried at the kernel tile quantum so the (R, 128)
        # reshape inside ops.py never has to pad-copy any operand
        self.n_pad = -(-self.n_flat // _TILE) * _TILE
        self.dtype = self.dtypes[0] if len(set(self.dtypes)) == 1 else None
        # identity: the space covers every coordinate *in storage order*
        # (DenseSpace; or a mask selecting everything) — skip the scatter.
        # Spaces that guarantee this structurally say so (identity_layout),
        # costing nothing.  A merely full-coverage mask is verified against
        # arange — the index contract allows any per-leaf order, and a
        # permuted full mask must take the scatter path.
        self.identity = bool(getattr(space, "identity_layout",
                                     lambda: False)()
                             and space.n == self.n_flat)
        self._idx_leaves = None
        self._idx_concrete = True
        if not self.identity:
            idx_leaves = space.leaf_index_arrays(template)
            concrete = not any(_is_tracer(i) for i in idx_leaves)
            if space.n == self.n_flat and concrete:
                self.identity = all(
                    np.array_equal(np.asarray(i), np.arange(s))
                    for i, s in zip(idx_leaves, self.sizes))
            if not self.identity:
                self._idx_leaves = idx_leaves
                self._idx_concrete = concrete
        self._global_index = None
        self._mask = None
        self._sorted_unique = None

    @property
    def global_index(self):
        """[n] int32 flat positions of the space coords (None if identity).

        Built lazily — the ref backend and huge layouts never pay for it.
        Concrete index trees build in numpy and cache (jnp constructors
        inside a jit trace yield tracers, which must never end up in the
        per-space cache); traced trees (dry-run) rebuild in-graph per use."""
        if self.identity:
            return None
        if self.n_flat > _INT32_MAX:
            raise ValueError(
                f"flat layout of {self.n_flat} coords exceeds int32 indexing;"
                " use backend='ref'")
        if self._global_index is not None:
            return self._global_index
        if self._idx_concrete:
            gidx = np.concatenate(
                [np.asarray(i, np.int64) + off
                 for i, off in zip(self._idx_leaves, self.offsets[:-1])])
            self._global_index = gidx.astype(np.int32)
            return self._global_index
        return jnp.concatenate(  # traced: per-use, uncached
            [jnp.asarray(i, jnp.int32) + jnp.int32(off)
             for i, off in zip(self._idx_leaves, self.offsets[:-1])])

    @property
    def mask(self):
        """Dense [n_pad] f32 0/1 mask (diagnostics / 3-operand kernels).

        The hot paths run the pre-masked kernel variants and never read it;
        built lazily like :attr:`global_index`."""
        if self._mask is not None:
            return self._mask
        if self.identity:
            mask = np.zeros((self.n_pad,), np.float32)
            mask[:self.n_flat] = 1.0
            self._mask = mask
            return mask
        gidx = self.global_index
        if self._idx_concrete:
            mask = np.zeros((self.n_pad,), np.float32)
            mask[gidx] = 1.0
            self._mask = mask
            return mask
        return jnp.zeros((self.n_pad,), jnp.float32).at[gidx].set(1.0)

    @property
    def sorted_unique(self) -> bool:
        """Whether every leaf's indices are strictly increasing (so sorted
        and unique), checked once on the concrete index leaves.  Traced
        index trees (dry-run) never qualify.  Only then may a scatter at
        them declare ``indices_are_sorted``/``unique_indices``: the index
        contract allows any order, and a false declaration gives wrong
        numbers."""
        if self._sorted_unique is None:
            self._sorted_unique = self.identity or (
                self._idx_concrete and all(
                    bool(np.all(np.diff(np.asarray(i)) > 0))
                    for i in self._idx_leaves))
        return self._sorted_unique

    @property
    def supported(self) -> bool:
        """Whether the flat kernel route is usable for this layout."""
        return (self.dtype is not None and self.n_flat <= _INT32_MAX
                and self.space.n > 0)

    @property
    def cacheable(self) -> bool:
        return self._idx_concrete

    def flatten(self, params):
        """Concatenate raveled leaves -> [n_pad] (uniform dtype, or f32).

        The tail beyond ``n_flat`` is zeros; every kernel operand therefore
        arrives already in the (R, 128)-tileable length."""
        leaves = jax.tree_util.tree_leaves(params)
        dt = self.dtype or jnp.float32
        segs = [l.reshape(-1).astype(dt) for l in leaves]
        if self.n_pad > self.n_flat:
            segs.append(jnp.zeros((self.n_pad - self.n_flat,), dt))
        return jnp.concatenate(segs)

    def unflatten(self, flat):
        """Split a flat [n_pad] (or [N]) vector back into the pytree."""
        out = [flat[int(o):int(o) + s].reshape(sh).astype(dt)
               for o, s, sh, dt in zip(self.offsets[:-1], self.sizes,
                                       self.shapes, self.dtypes)]
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def expand(self, vec):
        """Sparse [n] values -> dense [n_pad] f32 (zeros elsewhere)."""
        if self.identity:
            v = vec.astype(jnp.float32)
            if self.n_pad > self.n_flat:
                v = jnp.concatenate([v, jnp.zeros((self.n_pad - self.n_flat,),
                                                  jnp.float32)])
            return v
        return jnp.zeros((self.n_pad,), jnp.float32).at[
            self.global_index].set(vec.astype(jnp.float32))

    def restrict(self, flat):
        """Dense [n_pad] (or [N]) -> the [n] values at the space coords."""
        if self.identity:
            return flat[:self.n_flat].astype(jnp.float32)
        return flat[self.global_index].astype(jnp.float32)

    def scatter_into(self, buf, vec):
        """Overwrite the space's coordinates of a dense [n_pad] f32 buffer
        with ``vec`` [n].  Equivalent to :meth:`expand` whenever ``buf`` is
        zero off the coordinates (the coordinate set is static, so every
        overwrite leaves the off-coordinate zeros untouched) — without
        re-materializing the n_pad zero vector.  The scanned hot loops
        carry one dense z buffer and refresh it in place each step, saving
        a full-vector write per step."""
        v = vec.astype(jnp.float32)
        if self.identity:
            return jax.lax.dynamic_update_slice(buf, v, (0,))
        return buf.at[self.global_index].set(v)


def _layout_key(template):
    leaves, treedef = jax.tree_util.tree_flatten(template)
    return (treedef, tuple((tuple(l.shape), str(jnp.dtype(l.dtype)))
                           for l in leaves))


def get_backing(space, template) -> FlatBacking:
    """FlatBacking for (space, template), cached on the space instance.

    The cached arrays (mask, global indices) derive only from the space's
    index tree and the template's *shapes* — never from parameter values —
    so the cache is safe to reuse across jit traces.  When the index tree
    itself is traced (the dry-run's abstract masks) nothing is cached.
    """
    key = _layout_key(template)
    cached = getattr(space, "_flat_backing", None)
    if cached is not None and cached[0] == key:
        return cached[1]
    backing = FlatBacking(space, template)
    if backing.cacheable:
        space._flat_backing = (key, backing)
    return backing


# What the flat route holds per client, in dense [n_pad] vectors of at most
# 4 bytes a coordinate: the flat weights, the dense delta and z buffers,
# their sum, and the two perturbed copies (T=1 steps hold fewer; the
# estimate is an upper bound).  The ref route touches only sparse [n]
# vectors and in-place scatters.
FLAT_ROUTE_BYTES_PER_COORD = 6 * 4
# XLA:CPU reports no device memory; CPU simulations cap the flat route at
# 256 MiB of dense carry (one f32 vector per client), i.e. this budget
HOST_FLAT_ROUTE_BYTES = 6 * 256 * 1024 * 1024


def _flat_route_budget() -> int:
    """Bytes the flat route may take: what the default device reports
    free (``bytes_limit - bytes_in_use``), or the host cap where it
    reports nothing."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return HOST_FLAT_ROUTE_BYTES
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def resolve_backend(backend: Optional[str], backing: FlatBacking, *,
                    sharded: bool = False, dense_carry: int = 1) -> str:
    """Map a requested backend ('auto'/None included) to 'pallas' | 'ref'.

    ``dense_carry`` is the number of clients whose flat-route state may be
    live at once — one per client of a group in make_local_run /
    make_fl_round_step, one for a single T=1 step.  Auto requires their
    total (``FLAT_ROUTE_BYTES_PER_COORD`` per coordinate each) to fit the
    device's free memory, so a model at published width never trades
    sparse [n] traffic for an out-of-memory dense route."""
    backend = backend or "auto"
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend == "auto":
        if sharded or not backing.supported:
            return "ref"
        if (FLAT_ROUTE_BYTES_PER_COORD * backing.n_pad * max(1, dense_carry)
                > _flat_route_budget()):
            return "ref"
        return "pallas"
    return backend
