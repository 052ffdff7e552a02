"""The ref route's in-place perturb (``core/zo.py:_run_ref_inplace``)
against a plain oracle built from ``MaskedSpace.add`` through
``projected_gradient``: the same client group under ``jax.lax.map``, the
same T-step scan, bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import MaskedSpace, make_local_run, projected_gradient
from repro.core.dispatch import get_backing
from repro.core.quantize import QuantSpec
from repro.core.zo import _maybe_quantize

L, D, F, V = 2, 128, 256, 16
EPS, LR = 1e-3, 5e-2


def _params(dtype):
    """Leaves in (8, 128) tiles (``emb``, ``w1``, ``w2``), and leaves
    that are not (``b1``, ``norm``)."""
    ks = jax.random.split(jax.random.key(0), 4)
    return {"emb": jax.random.normal(ks[0], (V, D)).astype(dtype),
            "w1": (jax.random.normal(ks[1], (L, D, F)) / 11).astype(dtype),
            "w2": (jax.random.normal(ks[2], (L, F, D)) / 16).astype(dtype),
            "b1": (jax.random.normal(ks[3], (L, F)) / 8).astype(dtype),
            "norm": jnp.ones((D,), dtype)}


def _loss(params, batch):
    """A stacked-layer MLP over embedded tokens, in the leaves' dtype."""
    h = params["emb"][batch["tokens"]]

    def layer(h, w):
        return h + jnp.tanh(h @ w[0] + w[2]) @ w[1], None

    h, _ = jax.lax.scan(layer, h,
                        (params["w1"], params["w2"], params["b1"]))
    h = h * params["norm"]
    logits = (h @ params["emb"].T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, batch["label"][..., None], -1)[..., 0]
    return jnp.mean(lse - tgt)


def _space(params, mask, seed=3):
    rng = np.random.default_rng(seed)
    out = {}
    for name, p in params.items():
        size = int(np.prod(p.shape))
        k = 0 if mask == "some_empty" and name in ("w2", "norm") else max(
            1, size // 97)
        idx = np.sort(rng.choice(size, size=k, replace=False))
        if mask == "permuted":
            idx = rng.permutation(idx)
        out[name] = jnp.asarray(idx, jnp.int32)
    return MaskedSpace(out)


def _oracle(space, quantize, T):
    """The group body of ``FederatedZO._batch_run_for`` with each step's
    perturbs through ``projected_gradient``'s ``space.add``."""

    def run(params, keys, batches):
        def step(delta, inp):
            key, batch = inp
            z = space.sample_z(key)
            g = projected_gradient(_loss, params, space, delta, z, EPS,
                                   batch, backend="ref")
            g = _maybe_quantize(g, key, quantize)
            return delta - LR * g * z, g

        zeros = jnp.zeros((space.n,), jnp.float32)
        return jax.lax.map(
            lambda b: jax.lax.scan(step, zeros, (keys, b)), batches)

    return jax.jit(run)


def _group(space, quantize):
    run = make_local_run(_loss, space, EPS, LR, backend="ref",
                         quantize=quantize)

    def group(params, keys, batches):
        zeros = jnp.zeros((space.n,), jnp.float32)
        return jax.lax.map(lambda b: run(params, keys, b, zeros), batches)

    return jax.jit(group)


@pytest.mark.parametrize("mask", ["all", "some_empty", "permuted"])
@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_inplace_matches_space_add_bitwise(dtype, T, quant, mask):
    params = _params(jnp.dtype(dtype))
    space = _space(params, mask)
    assert get_backing(space, params).sorted_unique == (mask != "permuted")
    quantize = QuantSpec(bits=8) if quant else None
    K, b, S = 3, 2, 8
    rng = np.random.default_rng(T)
    batches = {"tokens": jnp.asarray(rng.integers(0, V, (K, T, b, S)),
                                     jnp.int32),
               "label": jnp.asarray(rng.integers(0, V, (K, T, b, S)),
                                    jnp.int32)}
    keys = jax.random.split(jax.random.key(7), T)

    before = obs.totals()["counters"].get("zo.perturb_inplace", 0)
    deltas, gs = _group(space, quantize)(params, keys, batches)
    # traced once for the group program, however many clients it maps
    assert obs.totals()["counters"]["zo.perturb_inplace"] == before + 1
    want_deltas, want_gs = _oracle(space, quantize, T)(params, keys,
                                                       batches)
    assert deltas.shape == (K, space.n) and gs.shape == (K, T)
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(want_gs))
    np.testing.assert_array_equal(np.asarray(deltas),
                                  np.asarray(want_deltas))
    # every client moved, and the clients differ only through their data
    assert np.all(np.asarray(gs) != 0)
    assert not np.array_equal(np.asarray(gs[0]), np.asarray(gs[2]))


def test_multi_direction_and_dense_keep_space_add():
    """``n_dirs > 1`` and spaces without an index tree take no in-place
    path."""
    from repro.core import DenseSpace
    params = _params(jnp.float32)
    batch = {"tokens": jnp.zeros((1, 2, 6), jnp.int32),
             "label": jnp.zeros((1, 2, 6), jnp.int32)}
    keys = jax.random.split(jax.random.key(1), 1)
    before = obs.totals()["counters"].get("zo.perturb_inplace", 0)
    for space, n_dirs in [(_space(params, "all"), 2),
                          (DenseSpace(params), 1)]:
        run = make_local_run(_loss, space, EPS, LR, n_dirs=n_dirs,
                             backend="ref")
        jax.jit(run)(params, keys, batch, jnp.zeros((space.n,)))
    assert obs.totals()["counters"].get("zo.perturb_inplace", 0) == before


@pytest.mark.parametrize("shape", [(16, 128), (3, 8, 256), (2, 1, 24, 384)])
def test_tile_view_positions(shape):
    """The tile view is a permutation of the leaf, undone by its inverse,
    and ``_tile_positions`` finds each row-major coordinate in its 1-D
    flattening."""
    from repro.core.zo import _from_tiles, _tile_positions, _to_tiles
    n = int(np.prod(shape))
    w = jnp.arange(n, dtype=jnp.int32).reshape(shape)
    t = _to_tiles(w)
    assert t.shape[-2:] == (8, 128)
    np.testing.assert_array_equal(np.asarray(_from_tiles(t)), np.asarray(w))
    idx = np.random.default_rng(0).choice(n, size=n // 5, replace=False)
    flat = np.asarray(t).reshape(-1)
    np.testing.assert_array_equal(flat[_tile_positions(idx, shape)], idx)
