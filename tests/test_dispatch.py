"""The one ZO route against a plain float64 numpy evaluation of Eq. 1.

Every entry point (``projected_gradient``, ``local_step``,
``make_local_run``, the ``fl_step`` factories) is compared with numpy at
the same ``z`` (``space.sample_z(key)``), on the same layouts: the oracle
finds the space's coordinates from its index tree (or its leaves), adds
``delta +- eps z`` there in each leaf's own dtype, and evaluates the loss
in float64.  It never calls ``space.add``.  Sizes include sub-lane
(48), non-multiple-of-128 (5000) and multi-block (40,000) layouts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DenseSpace, LoRASpace, MaskedSpace, QuantSpec,
                        magnitude_mask, random_mask, round_keys,
                        sensitivity_mask)
from repro.core.fl_step import (make_fl_round_step, make_fl_train_loop,
                                make_fl_train_step)
from repro.core.virtual_path import reconstruct_delta
from repro.core.zo import local_step, make_local_run, projected_gradient


def vec_params(key, sizes=((24,), (4, 6))):
    ks = jax.random.split(key, len(sizes))
    return {f"p{i}": jax.random.normal(k, s)
            for i, (k, s) in enumerate(zip(ks, sizes))}


def total_size(params):
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))


def vec_loss(params, batch):
    # mean keeps the loss O(1) at every size: (l+ - l-) / 2eps amplifies f32
    # rounding of the loss ~500x, so parity needs a well-conditioned problem
    v = jnp.concatenate([l.reshape(-1) for l in jax.tree.leaves(params)])
    return 0.5 * jnp.mean((v - batch["target"]) ** 2)


def vec_per_example(params, batch):
    v = jnp.concatenate([l.reshape(-1) for l in jax.tree.leaves(params)])
    return 0.5 * jnp.mean((v[None, :] - batch["target"]) ** 2, axis=-1)


def loss64(v, batch):
    return 0.5 * np.mean((v - np.asarray(batch["target"], np.float64)) ** 2)


def per_example64(v, batch):
    t = np.asarray(batch["target"], np.float64)
    return 0.5 * np.mean((v[None, :] - t) ** 2, axis=-1)


class Oracle:
    """Eq. 1 in numpy over the concatenation of the raveled leaves."""

    def __init__(self, space, params):
        self.space = space
        self.leaves = [np.asarray(l) for l in jax.tree.leaves(params)]
        sizes = [l.size for l in self.leaves]
        self.offsets = np.concatenate([[0], np.cumsum(sizes)])
        if isinstance(space, MaskedSpace):
            self.pos = np.concatenate([
                off + np.asarray(i, np.int64) for off, i in
                zip(self.offsets, jax.tree.leaves(space.idx_tree))])
        elif isinstance(space, LoRASpace):
            paths = [jax.tree_util.keystr(p) for p, _ in
                     jax.tree_util.tree_flatten_with_path(params)[0]]
            self.pos = np.concatenate([
                off + np.arange(s) for off, s, p in
                zip(self.offsets, sizes, paths) if "lora_" in p])
        else:
            assert isinstance(space, DenseSpace)
            self.pos = np.arange(self.offsets[-1])
        assert len(self.pos) == space.n

    def at(self, v):
        """float64 weights with ``v`` [n] added at the coordinates, each
        leaf's sum taken in the leaf's dtype."""
        out = []
        for leaf, lo, hi in zip(self.leaves, self.offsets, self.offsets[1:]):
            sel = (self.pos >= lo) & (self.pos < hi)
            w = leaf.reshape(-1).copy()
            i = self.pos[sel] - lo
            w[i] = w[i] + np.asarray(v)[sel].astype(leaf.dtype)
            out.append(w.astype(np.float64))
        return np.concatenate(out)

    def z(self, key):
        return np.asarray(self.space.sample_z(key))

    def g(self, loss, delta, z, eps, batch):
        """(f(w + delta + eps z) - f(w + delta - eps z)) / 2 eps, with
        ``delta +- eps z`` formed in float32 as the step forms it."""
        d = np.asarray(delta, np.float32)
        e = np.float32(eps) * z
        return (loss(self.at(d + e), batch)
                - loss(self.at(d - e), batch)) / (2.0 * eps)

    def step(self, loss, delta, key, eps, lr, batch, n_dirs=1):
        if n_dirs == 1:
            z = self.z(key)
            g = self.g(loss, delta, z, eps, batch)
            return delta - lr * g * z, g
        zs = [self.z(k) for k in jax.random.split(key, n_dirs)]
        gs = np.asarray([self.g(loss, delta, z, eps, batch) for z in zs])
        return delta - lr * np.mean([g * z for g, z in zip(gs, zs)], 0), gs

    def run(self, loss, keys, batches, eps, lr, n_dirs=1):
        delta, gs = np.zeros(self.space.n), []
        for t, key in enumerate(keys):
            delta, g = self.step(loss, delta, key, eps, lr,
                                 jax.tree.map(lambda x: x[t], batches),
                                 n_dirs)
            gs.append(g)
        return delta, np.asarray(gs)


def _close(got, want, rtol, atol):
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=rtol,
                               atol=atol)


def _flat(tree):
    return np.concatenate([np.asarray(l, np.float64).reshape(-1)
                           for l in jax.tree.leaves(tree)])


# ------------------------------------------------------- step parity --------

PARITY_SIZES = [((24,), (4, 6)), ((40, 125), (3,)), ((163, 245), (65,))]


@pytest.mark.parametrize("sizes", PARITY_SIZES)
@pytest.mark.parametrize("n_dirs", [1, 3])
def test_local_step_parity(sizes, n_dirs):
    params = vec_params(jax.random.key(7), sizes=sizes)
    space = random_mask(params, density=0.2, seed=3)
    batch = {"target": jax.random.normal(jax.random.key(8),
                                         (total_size(params),))}
    delta = 0.01 * jax.random.normal(jax.random.key(9), (space.n,))
    key = jax.random.key(10)
    got_d, got_g = local_step(vec_loss, params, space, delta, key, 1e-3,
                              1e-2, batch, n_dirs=n_dirs)
    want_d, want_g = Oracle(space, params).step(
        loss64, np.asarray(delta, np.float64), key, 1e-3, 1e-2, batch,
        n_dirs)
    _close(got_d, want_d, 1e-3, 1e-5)
    _close(got_g, want_g, 1e-2, 5e-4)


@pytest.mark.parametrize("n_dirs", [1, 2])
def test_local_run_parity_and_virtual_path_exactness(n_dirs):
    """The T-step loop matches Eq. 1 AND stays exactly reconstructible
    from the uploaded scalars (paper Alg. 2 step 2)."""
    T, lr = 4, 1e-2
    params = vec_params(jax.random.key(11))
    space = random_mask(params, density=0.4, seed=4)
    keys = round_keys(5, 0, T)
    batches = {"target": jax.random.normal(jax.random.key(12),
                                           (T, total_size(params)))}
    run = jax.jit(make_local_run(vec_loss, space, 1e-3, lr, n_dirs=n_dirs))
    d_T, gs = run(params, keys, batches, jnp.zeros((space.n,), jnp.float32))
    assert gs.shape == ((T, n_dirs) if n_dirs > 1 else (T,))
    want_d, want_g = Oracle(space, params).run(loss64, keys, batches, 1e-3,
                                               lr, n_dirs)
    _close(gs, want_g, 1e-2, 5e-4)
    _close(d_T, want_d, 1e-3, 1e-5)
    d_srv = reconstruct_delta(space, keys, gs, lr)
    np.testing.assert_allclose(np.asarray(d_T), np.asarray(d_srv),
                               atol=1e-6)


def test_full_coverage_mask_local_run_shapes():
    """density=1.0 makes a MaskedSpace over every coordinate; the loop
    still returns [n]-shaped deltas that the server replays."""
    params = vec_params(jax.random.key(30))
    space = random_mask(params, density=1.0, seed=0)
    assert space.n == total_size(params) and space.sorted_unique
    keys = round_keys(0, 0, 2)
    batches = {"target": jax.random.normal(jax.random.key(31),
                                           (2, total_size(params)))}
    run = jax.jit(make_local_run(vec_loss, space, 1e-3, 1e-2))
    d_T, gs = run(params, keys, batches, jnp.zeros((space.n,), jnp.float32))
    assert d_T.shape == (space.n,)
    want_d, want_g = Oracle(space, params).run(loss64, keys, batches, 1e-3,
                                               1e-2)
    _close(gs, want_g, 1e-2, 5e-4)
    _close(d_T, want_d, 1e-3, 1e-5)
    d_srv = reconstruct_delta(space, keys, gs, 1e-2)
    np.testing.assert_allclose(np.asarray(d_T), np.asarray(d_srv), atol=1e-6)


def test_projected_gradient_parity():
    params = vec_params(jax.random.key(13))
    space = DenseSpace(params)
    batch = {"target": jnp.zeros(total_size(params))}
    z = space.sample_z(jax.random.key(14))
    delta = jnp.zeros((space.n,))
    got = float(projected_gradient(vec_loss, params, space, delta, z, 1e-4,
                                   batch))
    want = Oracle(space, params).g(loss64, np.zeros(space.n), np.asarray(z),
                                   1e-4, batch)
    assert abs(got - want) < 1e-3 * max(1.0, abs(want))


def _train_step_oracle(space, params, key, batch, n_clients, eps, lr):
    """(params', g_clients, loss) of one T=1 step of Alg. 3."""
    o = Oracle(space, params)
    z = o.z(key)
    e = np.float32(eps) * z
    lp = per_example64(o.at(e), batch)
    lm = per_example64(o.at(-e), batch)
    g_cl = ((lp - lm) / (2.0 * eps)).reshape(n_clients, -1).mean(-1)
    w = o.at(np.zeros(space.n, np.float32))
    w[o.pos] -= lr * g_cl.mean() * z
    return w, g_cl, np.mean(lp + lm) / 2.0


@pytest.mark.parametrize("sizes", PARITY_SIZES)
def test_fl_train_step_parity(sizes):
    n_clients, bs = 4, 2
    params = vec_params(jax.random.key(15), sizes=sizes)
    space = random_mask(params, density=0.2, seed=6)
    batch = {"target": jax.random.normal(jax.random.key(16),
                                         (n_clients * bs,
                                          total_size(params)))}
    step = jax.jit(make_fl_train_step(vec_per_example, space, eps=1e-3,
                                      lr=1e-2, n_clients=n_clients))
    p, g_cl, metrics = step(params, jax.random.key(17), batch)
    want_p, want_g, want_loss = _train_step_oracle(
        space, params, jax.random.key(17), batch, n_clients, 1e-3, 1e-2)
    _close(g_cl, want_g, 1e-2, 5e-4)
    _close(_flat(p), want_p, 1e-4, 1e-5)
    assert np.allclose(float(metrics["loss"]), want_loss, rtol=1e-4)


@pytest.mark.parametrize("masks", [None, "partial"])
@pytest.mark.parametrize("uplink", [None, "int8"])
def test_fl_train_loop_parity(masks, uplink):
    """The scanned burst == folding make_fl_train_step over the same step
    keys, batches and report masks, bit for bit."""
    n_clients, bs, n_steps = 4, 2, 3
    params = vec_params(jax.random.key(40), sizes=((48,), (8, 12)))
    space = random_mask(params, density=0.2, seed=41)
    batches = {"target": jax.random.normal(
        jax.random.key(42), (n_steps, n_clients * bs, total_size(params)))}
    kw = dict(eps=1e-3, lr=1e-2, n_clients=n_clients,
              quantize=QuantSpec(bits=8) if uplink else None)
    report = (None if masks is None else
              jnp.asarray([[1, 1, 0, 1], [0, 1, 1, 1], [1, 0, 0, 1]],
                          jnp.float32))
    key = jax.random.key(43)

    loop = jax.jit(make_fl_train_loop(vec_per_example, space,
                                      n_steps=n_steps, **kw))
    p_loop, gs_loop, m_loop = loop(params, key, batches, report)

    step = jax.jit(make_fl_train_step(vec_per_example, space, **kw))
    p, gs = params, []
    for t, k in enumerate(jax.random.split(key, n_steps)):
        p, g_cl, m = step(p, k, jax.tree.map(lambda x: x[t], batches),
                          None if report is None else report[t])
        gs.append(np.asarray(g_cl))
    np.testing.assert_array_equal(np.asarray(gs_loop), np.stack(gs))
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p_loop)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in ("loss", "g"):
        np.testing.assert_array_equal(np.asarray(m[name]),
                                      np.asarray(m_loop[name]))


def test_fl_round_step_parity_vmapped_clients():
    T, K, lr = 3, 2, 1e-2
    params = vec_params(jax.random.key(18))
    space = random_mask(params, density=0.3, seed=7)
    keys = round_keys(8, 0, T)
    batches = {"target": jax.random.normal(jax.random.key(19),
                                           (K, T, total_size(params)))}
    step = jax.jit(make_fl_round_step(vec_loss, space, eps=1e-3, lr=lr))
    p, gs = step(params, keys, batches)
    o = Oracle(space, params)
    runs = [o.run(loss64, keys, jax.tree.map(lambda x: x[c], batches), 1e-3,
                  lr) for c in range(K)]
    _close(gs, np.stack([g for _, g in runs]), 1e-2, 5e-4)
    w = o.at(np.zeros(space.n, np.float32))
    w[o.pos] += np.mean([d for d, _ in runs], 0)
    _close(_flat(p), w, 1e-3, 1e-5)


def test_updates_only_masked_coords():
    """Off-mask coordinates survive the T=1 step and the round bitwise."""
    params = vec_params(jax.random.key(20))
    space = random_mask(params, density=0.1, seed=9)
    off = np.ones(total_size(params), bool)
    off[Oracle(space, params).pos] = False
    step = jax.jit(make_fl_train_step(vec_per_example, space, eps=1e-3,
                                      lr=1e-2, n_clients=1))
    p1, _, _ = step(params, jax.random.key(22),
                    {"target": jnp.zeros((2, total_size(params)))})
    rnd = jax.jit(make_fl_round_step(vec_loss, space, eps=1e-3, lr=1e-2))
    p2, _ = rnd(params, round_keys(1, 0, 2),
                {"target": jnp.ones((2, 2, total_size(params)))})
    w0 = _flat(params)
    for p in (p1, p2):
        np.testing.assert_array_equal(_flat(p)[off], w0[off])
        assert np.any(_flat(p)[~off] != w0[~off])


def test_mixed_dtypes():
    """Leaves of two dtypes: each perturb rounds in its leaf's dtype, as
    the oracle does."""
    params = {"a": jnp.linspace(-1.0, 1.0, 64, dtype=jnp.float32),
              "b": jnp.linspace(0.5, 2.0, 64).astype(jnp.bfloat16)}
    space = random_mask(params, density=0.5, seed=0)
    batch = {"target": jax.random.normal(jax.random.key(23), (128,))}
    key = jax.random.key(24)
    got_d, got_g = local_step(vec_loss, params, space,
                              jnp.zeros((space.n,)), key, 1e-2, 1e-2, batch)
    want_d, want_g = Oracle(space, params).step(loss64, np.zeros(space.n),
                                                key, 1e-2, 1e-2, batch)
    _close(got_g, want_g, 1e-2, 5e-4)
    _close(got_d, want_d, 1e-3, 1e-5)


# ---------------------------------------- virtual-path exactness ----------

def _space_of(kind, params):
    if kind == "sensitivity":
        pre = [{"target": jax.random.normal(jax.random.key(50 + i),
                                            (total_size(params),))}
               for i in range(2)]
        return sensitivity_mask(vec_loss, params, pre, 0.25)
    if kind == "magnitude":
        return magnitude_mask(params, 0.25)
    if kind == "random":
        return random_mask(params, density=0.25, seed=5, balanced=False)
    return DenseSpace(params) if kind == "dense" else LoRASpace(params)


@pytest.mark.parametrize("kind", ["sensitivity", "magnitude", "random",
                                  "dense", "lora"])
@pytest.mark.parametrize("n_dirs", [1, 2])
@pytest.mark.parametrize("uplink", [None, "int8"])
def test_local_run_replays_on_every_server_space(kind, n_dirs, uplink):
    """For each space the server takes, the server's replay of the
    uploaded scalars (``reconstruct_delta``) is the client's delta."""
    T, lr = 3, 1e-2
    ks = jax.random.split(jax.random.key(51), 3)
    params = {"w": jax.random.normal(ks[0], (8, 16)),
              "lora_a": 0.1 * jax.random.normal(ks[1], (8, 2)),
              "lora_b": 0.1 * jax.random.normal(ks[2], (2, 16))}
    space = _space_of(kind, params)
    keys = round_keys(9, 1, T)
    batches = {"target": jax.random.normal(jax.random.key(52),
                                           (T, total_size(params)))}
    run = jax.jit(make_local_run(
        vec_loss, space, 1e-3, lr, n_dirs=n_dirs,
        quantize=QuantSpec(bits=8) if uplink else None))
    d_T, gs = run(params, keys, batches, jnp.zeros((space.n,), jnp.float32))
    assert gs.shape == ((T, n_dirs) if n_dirs > 1 else (T,))
    assert np.all(np.asarray(gs) != 0)
    d_srv = reconstruct_delta(space, keys, gs, lr)
    np.testing.assert_allclose(np.asarray(d_T), np.asarray(d_srv), atol=1e-6)
