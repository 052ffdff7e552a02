"""The ZO loop's in-place perturb (``core/zo.py:_run_inplace``) against a
plain oracle built from ``MaskedSpace.add`` through
``projected_gradient``: the same client group under ``jax.lax.map``, the
same T-step scan, bit for bit.  Under a TPU compile the leaves that hold
many coordinates are placed block by block (``kernels/block_place.py``);
here that route runs in the Pallas interpreter, forced by the test.  With
``sharded=True`` (GSPMD-sharded weights, ``rule="tp"``) every leaf is
scattered at N-D indices in its own shape instead."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import MaskedSpace, make_local_run, projected_gradient
from repro.core import zo
from repro.core.quantize import QuantSpec
from repro.core.zo import _from_tiles, _maybe_quantize, _to_tiles
from repro.kernels import block_place

L, D, F, V = 2, 128, 256, 16
EPS, LR = 1e-3, 5e-2


def _params(dtype, wide=False):
    """Leaves in (8, 128) tiles (``emb``, ``w1``, ``w2``), and leaves
    that are not (``b1``, ``norm``); ``wide`` adds ``w3``, four of the
    block kernel's row blocks."""
    ks = jax.random.split(jax.random.key(0), 4)
    params = {
        "emb": jax.random.normal(ks[0], (V, D)).astype(dtype),
        "w1": (jax.random.normal(ks[1], (L, D, F)) / 11).astype(dtype),
        "w2": (jax.random.normal(ks[2], (L, F, D)) / 16).astype(dtype),
        "b1": (jax.random.normal(ks[3], (L, F)) / 8).astype(dtype),
        "norm": jnp.ones((D,), dtype)}
    if wide:
        rows = 4 * block_place.BLOCK_ROWS * block_place.LANE // D
        params["w3"] = (jax.random.normal(jax.random.key(4), (D, rows)) / 32
                        ).astype(dtype)
    return params


def _loss(params, batch):
    """A stacked-layer MLP over embedded tokens, in the leaves' dtype."""
    h = params["emb"][batch["tokens"]]

    def layer(h, w):
        return h + jnp.tanh(h @ w[0] + w[2]) @ w[1], None

    h, _ = jax.lax.scan(layer, h,
                        (params["w1"], params["w2"], params["b1"]))
    if "w3" in params:
        h = h + jnp.tanh(h @ params["w3"]) @ params["w3"].T / 32
    h = h * params["norm"]
    logits = (h @ params["emb"].T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, batch["label"][..., None], -1)[..., 0]
    return jnp.mean(lse - tgt)


def _row_major(shape, tile_pos):
    """Row-major flat indices of a tileable leaf at positions of its tile
    view's 1-D flattening."""
    n = int(np.prod(shape))
    order = _to_tiles(jnp.arange(n, dtype=jnp.int32).reshape(shape))
    return np.asarray(order).reshape(-1)[tile_pos]


def _blocks_space(params, rng):
    """``w3``'s coordinates skewed: 520 in the second of its four row
    blocks of the kernel (more than a chunk), none in the first and third,
    30 in the fourth, so the last chunk is part full; ``w1``'s and
    ``w2``'s spread; the other leaves too few for the kernel to beat
    points."""
    block = block_place.BLOCK_ROWS * block_place.LANE
    w3 = np.concatenate([block + rng.choice(block, 520, replace=False),
                         3 * block + rng.choice(block, 30, replace=False)])
    out = {"w3": np.sort(_row_major(params["w3"].shape, w3))}
    for name in ("emb", "w1", "w2", "b1", "norm"):
        size = params[name].size
        k = 300 if name in ("w1", "w2") else max(1, size // 97)
        out[name] = np.sort(rng.choice(size, size=k, replace=False))
    return MaskedSpace({k: jnp.asarray(v, jnp.int32) for k, v in out.items()})


def _space(params, mask, seed=3):
    rng = np.random.default_rng(seed)
    if mask == "blocks":
        return _blocks_space(params, rng)
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
                                   batch)
            g = _maybe_quantize(g, key, quantize)
            return delta - LR * g * z, g

        zeros = jnp.zeros((space.n,), jnp.float32)
        return jax.lax.map(
            lambda b: jax.lax.scan(step, zeros, (keys, b)), batches)

    return jax.jit(run)


def _group(space, quantize, sharded=False):
    run = make_local_run(_loss, space, EPS, LR, sharded=sharded,
                         quantize=quantize)

    def group(params, keys, batches):
        zeros = jnp.zeros((space.n,), jnp.float32)
        return jax.lax.map(lambda b: run(params, keys, b, zeros), batches)

    return jax.jit(group)


def _counters():
    c = obs.totals()["counters"]
    return {k: c.get(k, 0) for k in ("zo.perturb_inplace",
                                     "zo.perturb.block_coords",
                                     "zo.perturb.point_coords")}


@pytest.mark.parametrize("mask", ["all", "some_empty", "permuted", "blocks"])
@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_inplace_matches_space_add_bitwise(dtype, T, quant, mask,
                                           monkeypatch):
    params = _params(jnp.dtype(dtype), wide=mask == "blocks")
    space = _space(params, mask)
    assert space.sorted_unique == (mask != "permuted")
    quantize = QuantSpec(bits=8) if quant else None
    K, b, S = 3, 2, 8
    rng = np.random.default_rng(T)
    batches = {"tokens": jnp.asarray(rng.integers(0, V, (K, T, b, S)),
                                     jnp.int32),
               "label": jnp.asarray(rng.integers(0, V, (K, T, b, S)),
                                    jnp.int32)}
    keys = jax.random.split(jax.random.key(7), T)

    before = _counters()
    deltas, gs = _group(space, quantize)(params, keys, batches)
    # traced once for the group program, however many clients it maps;
    # off the TPU every coordinate is left to the point scatters
    assert _counters() == {
        "zo.perturb_inplace": before["zo.perturb_inplace"] + 1,
        "zo.perturb.block_coords": before["zo.perturb.block_coords"],
        "zo.perturb.point_coords": before["zo.perturb.point_coords"]
        + space.n}
    want_deltas, want_gs = _oracle(space, quantize, T)(params, keys,
                                                       batches)
    assert deltas.shape == (K, space.n) and gs.shape == (K, T)
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(want_gs))
    np.testing.assert_array_equal(np.asarray(deltas),
                                  np.asarray(want_deltas))
    # every client moved, and the clients differ only through their data
    assert np.all(np.asarray(gs) != 0)
    assert not np.array_equal(np.asarray(gs[0]), np.asarray(gs[2]))
    if mask != "blocks":
        return
    # the same group program as a TPU compile routes it: ``w1``, ``w2`` and
    # ``w3`` placed by the block kernel (in the interpreter), the rest by
    # points
    monkeypatch.setattr(zo, "_default_interpret", lambda: False)
    before = _counters()
    b_deltas, b_gs = _group(space, quantize)(params, keys, batches)
    hot = sum(space.idx_tree[k].shape[0] for k in ("w1", "w2", "w3"))
    after = _counters()
    assert after["zo.perturb.block_coords"] == (
        before["zo.perturb.block_coords"] + hot)
    assert after["zo.perturb.point_coords"] == (
        before["zo.perturb.point_coords"] + space.n - hot)
    np.testing.assert_array_equal(np.asarray(b_gs), np.asarray(gs))
    np.testing.assert_array_equal(np.asarray(b_deltas), np.asarray(deltas))


@pytest.mark.parametrize("mask", ["all", "some_empty", "permuted", "blocks"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_inplace_sharded_matches_space_add_bitwise(dtype, mask,
                                                   monkeypatch):
    """``sharded=True`` carries every leaf in its own shape and scatters
    at N-D indices, as ``MaskedSpace.add`` does: the same bits, and no
    coordinate left to the block kernel even where a TPU compile would
    place the leaf by blocks unsharded."""
    params = _params(jnp.dtype(dtype), wide=mask == "blocks")
    space = _space(params, mask)
    K, T, b, S = 2, 3, 2, 8
    rng = np.random.default_rng(T)
    batches = {"tokens": jnp.asarray(rng.integers(0, V, (K, T, b, S)),
                                     jnp.int32),
               "label": jnp.asarray(rng.integers(0, V, (K, T, b, S)),
                                    jnp.int32)}
    keys = jax.random.split(jax.random.key(11), T)
    monkeypatch.setattr(zo, "_default_interpret", lambda: False)
    before = _counters()
    deltas, gs = _group(space, None, sharded=True)(params, keys, batches)
    after = _counters()
    assert after["zo.perturb.block_coords"] == before[
        "zo.perturb.block_coords"]
    assert after["zo.perturb.point_coords"] == before[
        "zo.perturb.point_coords"] + space.n
    want_deltas, want_gs = _oracle(space, None, T)(params, keys, batches)
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(want_gs))
    np.testing.assert_array_equal(np.asarray(deltas),
                                  np.asarray(want_deltas))
    assert np.all(np.asarray(gs) != 0)


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
        run = make_local_run(_loss, space, EPS, LR, n_dirs=n_dirs)
        jax.jit(run)(params, keys, batch, jnp.zeros((space.n,)))
    assert obs.totals()["counters"].get("zo.perturb_inplace", 0) == before


@pytest.mark.parametrize("shape", [(16, 128), (3, 8, 256), (2, 1, 24, 384)])
def test_tile_view_positions(shape):
    """The tile view is a permutation of the leaf, undone by its inverse,
    and ``_tile_positions`` finds each row-major coordinate in its 1-D
    flattening."""
    from repro.core.zo import _tile_positions
    n = int(np.prod(shape))
    w = jnp.arange(n, dtype=jnp.int32).reshape(shape)
    t = _to_tiles(w)
    assert t.shape[-2:] == (8, 128)
    np.testing.assert_array_equal(np.asarray(_from_tiles(t)), np.asarray(w))
    idx = np.random.default_rng(0).choice(n, size=n // 5, replace=False)
    flat = np.asarray(t).reshape(-1)
    np.testing.assert_array_equal(flat[_tile_positions(idx, shape)], idx)


@pytest.mark.parametrize("blocks,n,skew", [
    (2, 700, 0.0), (4, 3000, 0.9), (16, 1500, 0.5), (16, 9000, 0.0),
    (1, 1, 0.0)])
def test_block_plan_covers_each_coordinate_once(blocks, n, skew):
    """Each coordinate lies in exactly one (row block, chunk) step of the
    kernel's grid; every step's block holds a coordinate of its chunk, so
    no block without coordinates is visited; the steps run in block order,
    so a block's steps are consecutive."""
    bm, k, lane = block_place.BLOCK_ROWS, block_place.CHUNK, block_place.LANE
    rng = np.random.default_rng(n)
    dense = int(n * skew)
    first = rng.integers(0, blocks) * bm * lane
    pos = np.union1d(first + rng.choice(bm * lane, dense, replace=False),
                     rng.choice(blocks * bm * lane, n - dense, replace=False))
    p = block_place.plan(pos, blocks * bm)
    pairs = list(zip(p.blk.tolist(), p.chk.tolist()))
    assert pairs == sorted(set(pairs))
    of = [(int(q) // lane // bm, i // k) for i, q in enumerate(pos)]
    assert set(of) == set(pairs)
    assert p.pos.shape == (-(-len(pos) // k), 1, k)
    np.testing.assert_array_equal(p.pos.reshape(-1)[:len(pos)], pos)
    assert np.all(p.pos.reshape(-1)[len(pos):] == -1)
    assert set(p.blk.tolist()) == {b for b, _ in of}
