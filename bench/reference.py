"""Plain reference of one federated ZO round, and the numbers that decide
``correct``.

It imports nothing of the program.  It regenerates the cell's weights from
the seed (the benchmark's own maker), and takes from the run only what the
round's protocol makes public: the mask's coordinates, the scalars each
client uploaded, and the server's parameters at the mask coordinates.

The parameters, and every sparse vector added to them, are held in the
configuration's dtype (bfloat16): ``w' = store(w + store(v))``.  All other
arithmetic is float32 at HIGHEST precision.

* **Model forward and ZO client loop.**  For each client's first
  ``g_steps`` ZO steps it computes the projected gradient
  ``g = (L(w + d + eps z) - L(w + d - eps z)) / (2 eps)``, where ``z``
  comes from the seed ladder (``fold_in(key(seed), round)`` split into T
  keys, a standard normal over the mask coordinates) and
  ``d = -lr * sum(g z)`` over the client's earlier steps of the round,
  from the scalars the program uploaded (the reference is held to the
  program's trajectory, so one step's rounding does not carry into the
  next step's comparison).  ``g_norm_gap`` is the norm of the gap over
  the norm of the reference's scalars.
* **Server.**  It replays every client's path from the uploaded scalars,
  averages, adds the mean to the parameters, and compares the change at
  the mask coordinates leaf by leaf.
* **Mask calibration.**  The mask has exactly ``round(N * density)``
  coordinates.  In one group of leaves drawn from the seed it recomputes
  each coordinate's mean squared gradient of the next-token loss over the
  pre-training batches, and checks that the coordinates the program chose
  there are the group's top ones by that score (a global top-k restricted
  to a group is the top of that group).  The group is drawn among those
  where the mask chose coordinates.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def round_keys(fl_seed: int, rnd: int, T: int):
    k = jax.random.fold_in(jax.random.key(fl_seed), rnd & 0xFFFFFFFF)
    return jax.random.split(k, T)


@dataclass
class RoundRecord:
    """What one run hands to the reference: the mask (per-leaf flat
    indices, in the weights' leaf order), each round's uploaded scalars
    ``gs[r]`` of shape [K, T], and the parameters at the mask coordinates
    before the first round (``p[0]``), after it (``p[1]``) and after the
    last recorded round (``p[-1]``)."""
    idx: List[np.ndarray]
    gs: List[np.ndarray]
    p: List[np.ndarray]
    client_tokens: List[np.ndarray]
    client_labels: List[np.ndarray]
    pretrain_tokens: List[np.ndarray]


class Reference:
    def __init__(self, family, config: dict, dtype: str, traffic: dict,
                 fl_seed: int, weights_seed: int):
        self.family, self.config = family, config
        self.hashable = tuple(sorted(config.items(), key=lambda kv: kv[0]))
        self.dtype = jnp.dtype(dtype)
        self.tr = traffic
        self.fl_seed, self.weights_seed = fl_seed, weights_seed
        self.T = traffic["local_steps"]
        self.B = traffic["batch_size"]

    # -- pieces ------------------------------------------------------------
    def weights(self):
        return self.family.make_weights(self.config, self.weights_seed,
                                        self.dtype.name)

    def _placer(self, sizes):
        @jax.jit
        def place(weights, idx, vals):
            leaves, treedef = jax.tree_util.tree_flatten(weights)
            out, o = [], 0
            for leaf, ix, n in zip(leaves, idx, sizes):
                f = leaf.astype(jnp.float32)
                if n:
                    f = f.reshape(-1).at[ix].set(vals[o:o + n]).reshape(
                        leaf.shape)
                out.append(f)
                o += n
            return jax.tree_util.tree_unflatten(treedef, out)
        return place

    def _batch(self, rec: RoundRecord, k: int, step: int, rows: int):
        n = len(rec.client_labels[k])
        sel = (step * self.B + np.arange(self.B)) % n
        sel = sel[:rows]
        return (jnp.asarray(rec.client_tokens[k][sel]),
                jnp.asarray(rec.client_labels[k][sel]))

    def _z(self, rnd: int, n: int):
        return [jax.random.normal(k, (n,), jnp.float32)
                for k in round_keys(self.fl_seed, rnd, self.T)]

    def _store(self, x):
        return jnp.asarray(x, jnp.float32).astype(self.dtype).astype(
            jnp.float32)

    # -- the server --------------------------------------------------------
    def _apply(self, p, v):
        return self._store(p + self._store(v))

    def replay(self, rec: RoundRecord, rounds: int, clients=None):
        """Mask-coordinate parameters after each of ``rounds`` rounds,
        replayed from the uploaded scalars (``clients`` restricts the
        average, for the fault that leaves uploads out)."""
        lr = np.float32(self.tr["lr"])
        p = jnp.asarray(rec.p[0], jnp.float32)
        n = p.shape[0]
        out = [p]
        for r in range(rounds):
            zs = self._z(r, n)
            gs = np.asarray(rec.gs[r], np.float32)
            ks = range(gs.shape[0]) if clients is None else clients
            agg = jnp.zeros((n,), jnp.float32)
            for k in ks:
                d = jnp.zeros((n,), jnp.float32)
                for t in range(self.T):
                    d = d - (lr * gs[k, t]) * zs[t]
                agg = agg + d
            p = self._apply(p, agg / len(ks))
            out.append(p)
        return out

    # -- the clients -------------------------------------------------------
    def steps(self, rec: RoundRecord):
        """(round, t) of the ZO steps that are compared, per client."""
        want = self.tr["check"]["g_steps"]
        return [(r, t) for r in range(len(rec.gs))
                for t in range(self.T)][:want]

    def client_gs(self, rec: RoundRecord, *, fp8: bool = False,
                  rows: int = 0, weights=None) -> np.ndarray:
        """[K, g_steps] projected gradients at the program's trajectory."""
        eps, lr = self.tr["eps"], np.float32(self.tr["lr"])
        W = self.weights() if weights is None else weights
        sizes = [len(i) for i in rec.idx]
        place = self._placer(sizes)
        idx = [jnp.asarray(i) for i in rec.idx]
        n = sum(sizes)
        steps = self.steps(rec)
        params = self.replay(rec, max(r for r, _ in steps))
        zs = {r: self._z(r, n) for r in {r for r, _ in steps}}
        gs = [np.asarray(g, np.float32) for g in rec.gs]
        out = np.zeros((gs[0].shape[0], len(steps)), np.float64)
        C = self.tr["task"]["n_classes"]
        for k in range(out.shape[0]):
            for j, (r, t) in enumerate(steps):
                z = zs[r]
                d = jnp.zeros((n,), jnp.float32)
                for s in range(t):
                    d = d - (lr * gs[r][k, s]) * z[s]
                toks, labels = self._batch(rec, k, r * self.T + t,
                                           rows or self.B)
                loss = []
                for sign in (1.0, -1.0):
                    off = d + eps * z[t] if sign > 0 else d - eps * z[t]
                    w = place(W, idx, self._apply(params[r], off))
                    loss.append(self.family.classify_loss(
                        w, toks, labels, c=self.hashable, n_classes=C,
                        fp8=fp8))
                    del w
                out[k, j] = (float(loss[0]) - float(loss[1])) / (2 * eps)
        return out


    # -- mask calibration ----------------------------------------------------
    def mask_group(self, sizes, chosen) -> List[int]:
        """The leaves whose mask is checked: consecutive leaves packed into
        groups of at most a quarter of the coordinates (or the largest
        leaf), one group drawn from the seed among those where the mask
        chose coordinates (``chosen``: the count per leaf).  A group with
        none would compare an empty choice, which every run passes."""
        cap = max(max(sizes), -(-sum(sizes) // 4))
        groups, cur, n = [], [], 0
        for i, s in enumerate(sizes):
            if cur and n + s > cap:
                groups.append(cur)
                cur, n = [], 0
            cur.append(i)
            n += s
        groups.append(cur)
        held = [g for g in groups if sum(chosen[i] for i in g)] or groups
        rng = np.random.default_rng(self.weights_seed)
        return held[int(rng.integers(len(held)))]

    def mask_scores(self, rec: RoundRecord, group: List[int], *,
                    fp8: bool = False, weights=None) -> np.ndarray:
        """Mean squared float32 gradient of the next-token loss over the
        pre-training batches, for the leaves of ``group``, flat."""
        W = self.weights() if weights is None else weights
        leaves, treedef = jax.tree_util.tree_flatten(W)
        rest = [a for i, a in enumerate(leaves) if i not in group]

        @partial(jax.jit, donate_argnums=0)
        def sq_grad(acc, diff, rest, tokens):
            def loss(diff):
                it_d, it_r = iter(diff), iter(rest)
                ls = [next(it_d) if i in group else next(it_r)
                      for i in range(len(leaves))]
                return self.family.lm_loss(
                    jax.tree_util.tree_unflatten(treedef, ls), tokens,
                    c=self.hashable, fp8=fp8)
            return [a + jnp.square(g)
                    for a, g in zip(acc, jax.grad(loss)(diff))]

        diff = [leaves[i].astype(jnp.float32) for i in group]
        acc = [jnp.zeros_like(a) for a in diff]
        for toks in rec.pretrain_tokens:
            acc = sq_grad(acc, diff, rest, jnp.asarray(toks))
        n = len(rec.pretrain_tokens)
        return np.concatenate([np.asarray(a).ravel() / np.float32(n)
                               for a in acc])

    def mask_numbers(self, rec: RoundRecord, *, weights=None,
                     scores=None) -> Dict[str, float]:
        """``mask_count_gap`` over the whole mask and ``mask_gap`` over the
        group the seed draws (an index outside its leaf counts as wrong)."""
        sizes = self.leaf_sizes()
        group = self.mask_group(sizes, [len(i) for i in rec.idx])
        if scores is None:
            scores = self.mask_scores(rec, group, weights=weights)
        off = np.concatenate([[0], np.cumsum([sizes[i] for i in group])])
        chosen = []
        for i, o in zip(group, off):
            ix = np.asarray(rec.idx[i], np.int64)
            chosen.append(np.where((ix >= 0) & (ix < sizes[i]), ix + o, -1))
        chosen = np.concatenate(chosen)
        k = max(1, int(round(sum(sizes) * self.tr["density"])))
        n_chosen = sum(len(i) for i in rec.idx)
        return {"mask_count_gap": abs(n_chosen - k) / k,
                "mask_gap": topk_gap(scores, chosen)}

    def leaf_sizes(self) -> List[int]:
        shapes = jax.tree_util.tree_leaves(
            self.family.weight_shapes(self.config),
            is_leaf=lambda x: isinstance(x, tuple))
        return [int(np.prod(s)) for s in shapes]


# ---------------------------------------------------------------- numbers
def top_indices(scores: np.ndarray, m: int) -> np.ndarray:
    """Flat indices of the ``m`` largest scores (lowest index on ties)."""
    if m <= 0:
        return np.zeros((0,), np.int64)
    kth = np.partition(scores, scores.size - m)[scores.size - m]
    top = np.flatnonzero(scores > kth)
    return np.concatenate([top, np.flatnonzero(scores == kth)[:m - top.size]])


def topk_gap(scores: np.ndarray, chosen: np.ndarray) -> float:
    """Share of the ``m`` chosen coordinates that are not among the ``m``
    top scores (a repeated or out-of-range coordinate counts as not)."""
    m = len(chosen)
    if m == 0:
        return 0.0
    top = np.zeros(scores.size, bool)
    top[top_indices(scores, m)] = True
    u = np.unique(chosen)
    u = u[(u >= 0) & (u < scores.size)]
    return 1.0 - int(top[u].sum()) / m


def g_norm_gap(g_prog: np.ndarray, g_ref: np.ndarray) -> float:
    """Norm of the gap between the program's and the reference's scalars
    of the compared steps (every client), over the norm of the
    reference's."""
    g_prog, g_ref = np.asarray(g_prog, np.float64), np.asarray(g_ref)
    if not np.all(np.isfinite(g_prog)):
        return float("inf")
    return float(np.linalg.norm(g_prog - g_ref) / np.linalg.norm(g_ref))


def leaf_norm_gap(p0, p1_prog, p1_ref, sizes) -> float:
    """Worst leaf of |‖change_prog‖ - ‖change_ref‖|, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  Changes are taken at the mask coordinates."""
    d_prog = np.asarray(p1_prog, np.float64) - np.asarray(p0, np.float64)
    d_ref = np.asarray(p1_ref, np.float64) - np.asarray(p0, np.float64)
    if not np.all(np.isfinite(d_prog)):
        return float("inf")
    o = np.concatenate([[0], np.cumsum(sizes)])
    segs = [(o[i], o[i + 1]) for i in range(len(sizes)) if sizes[i]]
    n_prog = np.array([np.linalg.norm(d_prog[a:b]) for a, b in segs])
    n_ref = np.array([np.linalg.norm(d_ref[a:b]) for a, b in segs])
    den = np.maximum(n_ref, np.median(n_ref))
    return float(np.max(np.abs(n_prog - n_ref) / den))


def compare(ref: Reference, rec: RoundRecord, g_ref=None,
            mask_scores=None) -> Dict[str, float]:
    """The numbers that decide ``correct`` for one run."""
    sizes = [len(i) for i in rec.idx]
    if g_ref is None:
        g_ref = ref.client_gs(rec)
    K = np.asarray(rec.gs[0]).shape[0]
    g_prog = np.array([[np.asarray(rec.gs[r])[k, t]
                        for r, t in ref.steps(rec)] for k in range(K)])
    replayed = ref.replay(rec, len(rec.gs))
    return {
        **ref.mask_numbers(rec, scores=mask_scores),
        "g_norm_gap": g_norm_gap(g_prog, g_ref),
        "update_norm_gap": leaf_norm_gap(rec.p[0], rec.p[1],
                                         np.asarray(replayed[1]), sizes),
        "change_norm_gap": leaf_norm_gap(rec.p[0], rec.p[-1],
                                         np.asarray(replayed[-1]), sizes),
    }
